"""Fault-tolerance layer: deadlines, retry/backoff, poison bisection,
circuit breaking, replica health, fault injection, archive integrity.

Everything timing-like runs over injected fake clocks/sleeps — no test in
this file waits on wall-clock backoff or breaker cool-downs.
"""

import math
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from _hypothesis_shim import given, settings, st
from repro.compile import (ArtifactIntegrityError, Target, compile, load)
from repro.models import train_decision_tree
from repro.serve import (BatchingPolicy, BreakerPolicy, CircuitBreaker,
                         CircuitOpenError, DeadlineExceeded, DispatchError,
                         FaultPlan, FaultRule, InferenceService, MicroBatcher,
                         RetryPolicy, TransientError)
from repro.serve import faults
from repro.serve.batching import _Request
from repro.serve.reliability import ServeError
from repro.sharding import ReplicaHealthPolicy, ReplicaHealthTracker


class FakeClock:
    """Injectable monotonic clock shared across threads."""

    def __init__(self, t=0.0):
        self._t = t
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            return self._t

    def advance(self, dt):
        with self._lock:
            self._t += dt


@pytest.fixture(scope="module")
def tree_art(blobs):
    xtr, ytr, _, _, c = blobs
    model = train_decision_tree(xtr, ytr, c, max_depth=6)
    return compile(model, Target(number_format="fxp16", backend="xla"))


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# RetryPolicy: backoff bounds + jitter (property tests)
# ---------------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(base=st.floats(min_value=1e-4, max_value=0.1),
       mult=st.floats(min_value=1.0, max_value=4.0),
       cap=st.floats(min_value=0.01, max_value=2.0),
       jitter=st.floats(min_value=0.0, max_value=0.9),
       attempt=st.integers(min_value=0, max_value=20),
       seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_backoff_bounded_and_jittered(base, mult, cap, jitter, attempt, seed):
    import random

    policy = RetryPolicy(backoff_base_s=base, multiplier=mult,
                         backoff_max_s=cap, jitter=jitter)
    s = policy.backoff_s(attempt, random.Random(seed))
    nominal = min(cap, base * mult ** attempt)
    assert 0.0 <= s <= cap * (1.0 + jitter) + 1e-12
    assert nominal * (1.0 - jitter) - 1e-12 <= s <= nominal * (1.0 + jitter) + 1e-12


def test_backoff_grows_then_caps():
    import random

    policy = RetryPolicy(backoff_base_s=0.01, multiplier=2.0,
                         backoff_max_s=0.05, jitter=0.0)
    seq = [policy.backoff_s(a, random.Random(0)) for a in range(8)]
    assert seq[:3] == [0.01, 0.02, 0.04]
    assert all(s == 0.05 for s in seq[3:])  # capped forever after


def test_retryable_classification():
    policy = RetryPolicy()
    assert policy.retryable(TransientError("flaky"))
    assert policy.retryable(ConnectionError())
    assert policy.retryable(TimeoutError())
    assert not policy.retryable(ValueError("bad rows"))
    assert not policy.retryable(RuntimeError("deterministic"))


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.0)
    with pytest.raises(ValueError):
        RetryPolicy(multiplier=0.5)


# ---------------------------------------------------------------------------
# deadlines: expired-in-queue requests are never dispatched
# ---------------------------------------------------------------------------
def test_expired_in_queue_never_dispatched():
    clock = FakeClock()
    gate = threading.Event()
    entered = threading.Event()
    dispatched_rows = []

    def predict(x):
        entered.set()
        gate.wait(5.0)
        dispatched_rows.append(np.array(x[:, 0]))
        return x[:, 0]

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=8, warmup=False),
                      clock=clock, sleep=lambda s: None)
    try:
        # Occupy the worker so subsequent requests provably sit in queue:
        # only submit them once the worker is inside predict (batch closed).
        blocker = mb.submit(np.array([[0.0]], np.float32))
        assert entered.wait(5.0)
        doomed = mb.submit(np.array([[7.0]], np.float32), timeout_s=5.0)
        alive = mb.submit(np.array([[3.0]], np.float32))  # no deadline
        clock.advance(10.0)  # the queued deadline passes
        gate.set()
        with pytest.raises(DeadlineExceeded) as exc:
            doomed.result(timeout=5)
        assert exc.value.status == 504
        assert exc.value.code == "deadline_exceeded"
        assert alive.result(timeout=5) == [3.0]
        assert blocker.result(timeout=5) == [0.0]
        flat = np.concatenate(dispatched_rows)
        assert 7.0 not in flat, "expired request was dispatched"
        assert mb.n_expired == 1
    finally:
        gate.set()
        mb.close(drain=False)


def test_deadline_math_with_fake_clock():
    clock = FakeClock(100.0)
    mb = MicroBatcher(lambda x: x[:, 0],
                      BatchingPolicy(max_batch=4, warmup=False), clock=clock)
    try:
        req = _Request(np.zeros((1, 2), np.float32), Future(),
                       t_enqueue=clock(), deadline=clock() + 2.0)
        assert not mb._expired(req)
        clock.advance(1.999)
        assert not mb._expired(req)
        clock.advance(0.002)
        assert mb._expired(req)
        assert mb._expired(req, now=103.0)
        no_deadline = _Request(np.zeros((1, 2), np.float32), Future(),
                               t_enqueue=clock())
        clock.advance(1e9)
        assert not mb._expired(no_deadline)
    finally:
        mb.close(drain=False)


# ---------------------------------------------------------------------------
# poison-batch bisection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 4, 8, 16])
def test_bisection_isolates_single_poison_in_olog_dispatches(n):
    POISON = 666.0
    calls = []

    def predict(x):
        calls.append(x.shape[0])
        if (x[:, 0] == POISON).any():
            raise RuntimeError("poison row")
        return x[:, 0] * 2

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=n, warmup=False))
    try:
        poison_slot = n // 3
        reqs = [_Request(np.full((1, 2), POISON if i == poison_slot else i,
                                 np.float32), Future(), 0.0)
                for i in range(n)]
        mb._serve(list(reqs))
        for i, r in enumerate(reqs):
            if i == poison_slot:
                with pytest.raises(DispatchError) as exc:
                    r.future.result(timeout=0)
                assert exc.value.isolated
                assert "poison row" in str(exc.value)
            else:
                assert r.future.result(timeout=0) == [2.0 * i]
        assert len(calls) <= 2 * int(math.log2(n)) + 1, (
            f"bisection used {len(calls)} dispatches for one poison in {n}")
        assert mb.n_failed_requests == 1
    finally:
        mb.close(drain=False)


def test_bisection_survivor_results_bit_identical(tree_art, blobs):
    """Rows served out of a bisected batch equal the rows served with no
    poison at all — isolation must not perturb batchmates."""
    _, _, xte, _, _ = blobs
    POISON = np.float32(1e30)
    base = tree_art.predict

    def predict(x):
        if (np.asarray(x) >= POISON).any():
            raise RuntimeError("poison row")
        return base(x)

    golden = base(xte[:8])
    mb = MicroBatcher(predict, BatchingPolicy(max_batch=16, warmup=False))
    try:
        reqs = [_Request(xte[i:i + 1], Future(), 0.0) for i in range(8)]
        reqs.insert(3, _Request(np.full_like(xte[:1], POISON), Future(), 0.0))
        mb._serve(list(reqs))
        got = [r.future.result(timeout=0) for i, r in enumerate(reqs)
               if i != 3]
        np.testing.assert_array_equal(np.concatenate(got), golden)
        with pytest.raises(DispatchError):
            reqs[3].future.result(timeout=0)
    finally:
        mb.close(drain=False)


# ---------------------------------------------------------------------------
# transient retry in the scheduler
# ---------------------------------------------------------------------------
def test_transient_dispatch_failures_are_retried_with_backoff():
    sleeps = []
    attempts = []

    def predict(x):
        attempts.append(len(attempts))
        if len(attempts) <= 2:
            raise TransientError("flaky device")
        return x[:, 0]

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=4, warmup=False),
                      retry=RetryPolicy(max_attempts=3, backoff_base_s=0.25,
                                        multiplier=2.0, backoff_max_s=10.0,
                                        jitter=0.0),
                      sleep=sleeps.append)
    try:
        assert mb.submit(np.array([[5.0]], np.float32)).result(timeout=5) == [5.0]
        assert len(attempts) == 3
        assert sleeps == [0.25, 0.5]  # exponential, via injected sleep
        assert mb.n_retries == 2 and mb.n_dispatch_failures == 2
        assert mb.n_failed_requests == 0
    finally:
        mb.close(drain=False)


def test_retry_budget_exhaustion_fails_structured():
    def predict(x):
        raise TransientError("never recovers")

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=4, warmup=False),
                      retry=RetryPolicy(max_attempts=3, backoff_base_s=0.0,
                                        jitter=0.0),
                      sleep=lambda s: None)
    try:
        fut = mb.submit(np.array([[1.0]], np.float32))
        with pytest.raises(DispatchError) as exc:
            fut.result(timeout=5)
        assert "never recovers" in str(exc.value)
        assert isinstance(exc.value.cause, TransientError)
        assert mb.n_dispatch_failures == 3
    finally:
        mb.close(drain=False)


def test_nonretryable_failure_skips_retries():
    attempts = []

    def predict(x):
        attempts.append(0)
        raise ValueError("deterministic rot")

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=4, warmup=False),
                      retry=RetryPolicy(max_attempts=5), sleep=lambda s: None)
    try:
        with pytest.raises(DispatchError):
            mb.submit(np.array([[1.0]], np.float32)).result(timeout=5)
        assert len(attempts) == 1  # went straight to isolation
    finally:
        mb.close(drain=False)


# ---------------------------------------------------------------------------
# worker crash semantics (satellite regression)
# ---------------------------------------------------------------------------
def test_worker_survives_predict_exception_and_keeps_serving():
    state = {"explode": True}

    def predict(x):
        if state["explode"]:
            raise RuntimeError("kernel exploded")
        return x[:, 0]

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=4, warmup=False))
    try:
        with pytest.raises(RuntimeError, match="kernel exploded"):
            mb.submit(np.array([[1.0]], np.float32)).result(timeout=5)
        assert mb._worker.is_alive(), "worker died on a predict exception"
        state["explode"] = False
        assert mb.submit(np.array([[9.0]], np.float32)).result(timeout=5) == [9.0]
    finally:
        mb.close(drain=False)


def test_worker_survives_incompatible_row_shapes():
    """Requests whose rows cannot concatenate (schema drift between
    clients) must not kill the worker loop: every affected future resolves
    and later well-formed traffic is served.  (Regression: concatenation
    ran outside the dispatch guard and an escaping exception stranded
    every queued future until close().)"""
    mb = MicroBatcher(lambda x: x[:, 0],
                      BatchingPolicy(max_batch=8, max_wait_ms=100.0,
                                     eager_when_idle=False, warmup=False))
    try:
        a = mb.submit(np.zeros((1, 4), np.float32))
        b = mb.submit(np.ones((1, 5), np.float32))  # incompatible width
        ra, rb = None, None
        try:
            ra = a.result(timeout=5)
        except ServeError:
            ra = "error"
        try:
            rb = b.result(timeout=5)
        except ServeError:
            rb = "error"
        assert ra is not None and rb is not None  # both RESOLVED, not hung
        assert mb._worker.is_alive()
        assert mb.submit(np.zeros((1, 3), np.float32)).result(timeout=5) == [0.0]
    finally:
        mb.close(drain=False)


def test_cancelled_future_does_not_break_batch_scatter():
    gate = threading.Event()

    def predict(x):
        gate.wait(5.0)
        return x[:, 0]

    mb = MicroBatcher(predict, BatchingPolicy(max_batch=8, max_wait_ms=50.0,
                                              eager_when_idle=False,
                                              warmup=False))
    try:
        blocker = mb.submit(np.array([[0.0]], np.float32))
        f1 = mb.submit(np.array([[1.0]], np.float32))
        f2 = mb.submit(np.array([[2.0]], np.float32))
        f1.cancel()  # a caller gave up while queued
        gate.set()
        assert blocker.result(timeout=5) == [0.0]
        assert f2.result(timeout=5) == [2.0]  # batchmate unaffected
        assert mb._worker.is_alive()
    finally:
        gate.set()
        mb.close(drain=False)


# ---------------------------------------------------------------------------
# circuit breaker state machine (fake clock throughout)
# ---------------------------------------------------------------------------
def _breaker(clock, **kw):
    defaults = dict(consecutive_failures=3, error_rate=0.5, window=8,
                    min_samples=4, open_s=10.0, half_open_probes=1,
                    close_after=2)
    defaults.update(kw)
    return CircuitBreaker(BreakerPolicy(**defaults), clock=clock)


def test_breaker_trips_on_consecutive_failures():
    clock = FakeClock()
    br = _breaker(clock)
    for _ in range(2):
        br.record_failure()
    assert br.state == CircuitBreaker.CLOSED and br.allow()
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert not br.allow()
    assert br.trips == 1 and br.rejected == 1
    assert 0.0 < br.retry_after_s() <= 10.0


def test_breaker_trips_on_error_rate():
    clock = FakeClock()
    br = _breaker(clock, consecutive_failures=100,  # disable fast trigger
                  min_samples=6)
    # alternate: never 2 consecutive, but 50% of the window fails
    br.record_success(); br.record_failure()
    br.record_success(); br.record_failure()
    assert br.state == CircuitBreaker.CLOSED  # min_samples not yet decisive
    br.record_success(); br.record_failure()
    assert br.state == CircuitBreaker.OPEN  # 3/6 >= 0.5 with n >= 6
    assert br.trips == 1


def test_breaker_half_open_probe_cycle():
    clock = FakeClock()
    br = _breaker(clock, close_after=2)
    for _ in range(3):
        br.record_failure()
    assert not br.allow()
    clock.advance(10.0)  # cool-down elapses
    assert br.allow()  # first probe admitted
    assert br.state == CircuitBreaker.HALF_OPEN
    assert not br.allow()  # probe budget (1) exhausted
    br.record_success()
    assert br.state == CircuitBreaker.HALF_OPEN  # needs close_after=2
    assert br.allow()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED
    assert br.allow()


def test_breaker_failed_probe_reopens_and_restarts_cooldown():
    clock = FakeClock()
    br = _breaker(clock)
    for _ in range(3):
        br.record_failure()
    clock.advance(10.0)
    assert br.allow()
    br.record_failure()  # the probe fails
    assert br.state == CircuitBreaker.OPEN
    assert br.trips == 2
    clock.advance(9.0)
    assert not br.allow()  # cool-down restarted at the probe failure
    clock.advance(1.5)
    assert br.allow()


def test_breaker_snapshot_counters():
    clock = FakeClock()
    br = _breaker(clock)
    br.record_success()
    br.record_failure()
    snap = br.snapshot()
    assert snap["state"] == "closed"
    assert snap["window_samples"] == 2
    assert snap["window_error_rate"] == 0.5
    assert snap["consecutive_failures"] == 1


# ---------------------------------------------------------------------------
# endpoint integration: breaker gate + fault injection + stats surface
# ---------------------------------------------------------------------------
def test_endpoint_breaker_opens_and_fails_fast(tree_art, blobs):
    _, _, xte, _, _ = blobs
    svc = InferenceService()
    try:
        svc.register("ep", artifact=tree_art,
                     breaker=CircuitBreaker(BreakerPolicy(
                         consecutive_failures=2, window=128,
                         min_samples=100, open_s=60.0)))
        svc.predict("ep", xte[:4])  # healthy baseline
        plan = FaultPlan([FaultRule(site="endpoint.dispatch",
                                    transient=False)])
        with faults.inject(plan):
            for _ in range(2):
                with pytest.raises(DispatchError):
                    svc.submit("ep", xte[0]).result(timeout=5)
            with pytest.raises(CircuitOpenError) as exc:
                svc.submit("ep", xte[0])
            assert exc.value.status == 503
            assert exc.value.retry_after_s > 0
        snap = svc.stats()["ep"]
        assert snap["breaker"]["state"] == "open"
        assert snap["breaker"]["trips"] == 1
        assert snap["failed_requests"] == 2
    finally:
        svc.close()


def test_endpoint_transient_faults_retry_to_golden_results(tree_art, blobs):
    """A flaky dispatch (every 2nd attempt faults transiently) serves every
    request bit-identically to the fault-free path, through retries."""
    _, _, xte, _, _ = blobs
    golden = tree_art.predict(xte[:32])
    svc = InferenceService()
    try:
        # warmup=False keeps the fault rule's event parity deterministic
        # (warmup dispatches would consume eligible events)
        svc.register("flaky", artifact=tree_art,
                     policy=BatchingPolicy(max_batch=64, warmup=False),
                     retry=RetryPolicy(max_attempts=4, backoff_base_s=1e-4))
        plan = FaultPlan([FaultRule(site="endpoint.dispatch", every=2,
                                    transient=True)])
        with faults.inject(plan) as inj:
            preds = svc.predict("flaky", xte[:32])
            assert inj.stats()["fired_total"] >= 1
        np.testing.assert_array_equal(preds, golden)
        assert svc.stats()["flaky"]["dispatch_retries"] >= 1
    finally:
        svc.close()


def test_governor_overload_hint_engages_degradation():
    from repro.serve import DegradationPolicy, PrecisionGovernor

    gov = PrecisionGovernor(DegradationPolicy(queue_high=1000, min_hold_s=0))
    assert gov.observe(0, None, now=0.0) is False
    assert gov.observe(0, None, now=1.0, overload_hint=True) is True
    # hint asserted: recovery blocked even with an idle queue
    assert gov.observe(0, None, now=2.0, overload_hint=True) is True
    assert gov.observe(0, None, now=3.0) is False


# ---------------------------------------------------------------------------
# fault injection determinism
# ---------------------------------------------------------------------------
def test_fault_plan_roundtrips_json():
    plan = FaultPlan([FaultRule(site="endpoint.dispatch", kind="delay",
                                delay_s=0.5, match="ep", every=3),
                      FaultRule(site="artifact.load", kind="corrupt",
                                corrupt_bytes=4)], seed=7)
    again = FaultPlan.from_json(plan.to_json())
    assert again.seed == 7
    assert again.rules == plan.rules


def test_fault_rules_fire_deterministically():
    def pattern(plan):
        inj = faults.FaultInjector(plan)
        fired = []
        for i in range(40):
            try:
                inj.fire("endpoint.dispatch", name="ep")
                fired.append(0)
            except faults.InjectedFault:
                fired.append(1)
        return fired

    plan = FaultPlan([FaultRule(site="endpoint.dispatch", p=0.3)], seed=42)
    a, b = pattern(plan), pattern(plan)
    assert a == b, "same plan+seed must fire identically"
    assert 0 < sum(a) < 40
    other = pattern(FaultPlan([FaultRule(site="endpoint.dispatch", p=0.3)],
                              seed=43))
    assert other != a  # the seed matters


def test_fault_first_every_count_gating():
    inj = faults.FaultInjector(FaultPlan(
        [FaultRule(site="endpoint.dispatch", first=2, every=3, count=2)]))
    fired = []
    for i in range(12):
        try:
            inj.fire("endpoint.dispatch")
            fired.append(0)
        except faults.InjectedFault:
            fired.append(1)
    # eligible events 2 and 5 fire; count=2 exhausts the rule afterwards
    assert fired == [0, 0, 1, 0, 0, 1, 0, 0, 0, 0, 0, 0]


def test_fault_poison_sentinel_matches_batch():
    inj = faults.FaultInjector(FaultPlan(
        [FaultRule(site="endpoint.dispatch", poison=666.0)]))
    inj.fire("endpoint.dispatch", batch=np.array([[1.0, 2.0]]))  # no poison
    with pytest.raises(faults.InjectedFault):
        inj.fire("endpoint.dispatch", batch=np.array([[1.0, 666.0]]))
    assert inj.stats()["rules"][0]["fired"] == 1


def test_fault_delay_uses_injected_sleep():
    sleeps = []
    inj = faults.FaultInjector(FaultPlan(
        [FaultRule(site="endpoint.dispatch", kind="delay", delay_s=2.5)]))
    inj.fire("endpoint.dispatch", sleep=sleeps.append)
    assert sleeps == [2.5]


def test_fault_filter_bytes_flips_seeded_bytes():
    inj = faults.FaultInjector(FaultPlan(
        [FaultRule(site="artifact.load", kind="corrupt", corrupt_bytes=3)],
        seed=5))
    data = bytes(range(64))
    out = inj.filter_bytes("artifact.load", data)
    diff = [i for i in range(64) if out[i] != data[i]]
    assert 1 <= len(diff) <= 3
    # a second injector from the same plan corrupts identically
    inj2 = faults.FaultInjector(FaultPlan(
        [FaultRule(site="artifact.load", kind="corrupt", corrupt_bytes=3)],
        seed=5))
    assert inj2.filter_bytes("artifact.load", data) == out


def test_no_plan_hooks_are_noops():
    faults.uninstall()
    faults.fire("endpoint.dispatch", name="anything")
    assert faults.filter_bytes("artifact.load", b"abc") == b"abc"
    assert not faults.active_for("endpoint.dispatch")


# ---------------------------------------------------------------------------
# replica health tracking
# ---------------------------------------------------------------------------
def test_replica_eviction_after_consecutive_faults():
    tr = ReplicaHealthTracker(4, ReplicaHealthPolicy(evict_after=2,
                                                     probe_every=100))
    tr.record_failure(1)
    assert tr.healthy_replicas() == [0, 1, 2, 3]  # one strike is not out
    tr.record_failure(1)
    assert tr.healthy_replicas() == [0, 2, 3]
    assert tr.snapshot()["evictions"] == 1
    # an evicted replica's nominal slot fails over to a healthy one
    assert all(c != 1 for c in tr.candidates(1))


def test_replica_success_resets_strikes():
    tr = ReplicaHealthTracker(2, ReplicaHealthPolicy(evict_after=2))
    tr.record_failure(0)
    tr.record_success(0)
    tr.record_failure(0)
    assert tr.healthy_replicas() == [0, 1]


def test_last_healthy_replica_never_evicted():
    tr = ReplicaHealthTracker(2, ReplicaHealthPolicy(evict_after=1))
    tr.record_failure(0)
    assert tr.healthy_replicas() == [1]
    for _ in range(10):
        tr.record_failure(1)
    assert tr.healthy_replicas() == [1], "last healthy replica was evicted"
    assert 1 in tr.candidates(0)


def test_evicted_replica_probed_and_readmitted():
    tr = ReplicaHealthTracker(2, ReplicaHealthPolicy(evict_after=1,
                                                     probe_every=3))
    tr.record_failure(0)
    assert tr.healthy_replicas() == [1]
    probed = []
    for _ in range(6):
        probed.append(tr.candidates(0)[0])
    assert 0 in probed, "evicted replica never offered a probe"
    tr.record_success(0)
    assert tr.healthy_replicas() == [0, 1]
    assert tr.snapshot()["readmissions"] == 1


def test_mesh_replica_fault_failover_is_bit_identical(tree_art, blobs):
    jax = pytest.importorskip("jax")
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (XLA_FLAGS host platform count)")
    from repro.sharding.rules import make_serving_mesh

    _, _, xte, _, _ = blobs
    golden = tree_art.predict(xte[:16])
    sharded = tree_art.specialize_mesh(make_serving_mesh(), "fused")
    np.testing.assert_array_equal(sharded.predict(xte[:16]), golden)
    # replica 0 hard-down: shards fail over to survivors, answers unchanged
    plan = FaultPlan([FaultRule(site="mesh.replica", match="0",
                                transient=True)])
    with faults.inject(plan):
        np.testing.assert_array_equal(sharded.predict(xte[:16]), golden)
    health = sharded.replica_health.snapshot()
    assert health["faults"] >= 1
    np.testing.assert_array_equal(sharded.predict(xte[:16]), golden)


# ---------------------------------------------------------------------------
# archive integrity (v3)
# ---------------------------------------------------------------------------
def test_archive_v3_roundtrip_predicts_identically(tree_art, blobs, tmp_path):
    _, _, xte, _, _ = blobs
    path = str(tmp_path / "tree.embml")
    tree_art.save(path)
    again = load(path)
    np.testing.assert_array_equal(again.predict(xte), tree_art.predict(xte))
    assert again.cache_key == tree_art.cache_key


def test_corrupt_archive_raises_integrity_error(tree_art, tmp_path):
    path = str(tmp_path / "tree.embml")
    tree_art.save(path)
    raw = open(path, "rb").read()
    # flip a byte mid-file: either the container fails to decode or a
    # member checksum mismatches — both must be ArtifactIntegrityError
    mangled = bytearray(raw)
    mangled[len(mangled) // 2] ^= 0xFF
    open(path, "wb").write(bytes(mangled))
    with pytest.raises(ArtifactIntegrityError):
        load(path)


def test_truncated_archive_raises_integrity_error(tree_art, tmp_path):
    path = str(tmp_path / "tree.embml")
    tree_art.save(path)
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:len(raw) // 2])
    with pytest.raises(ArtifactIntegrityError):
        load(path)


def test_member_checksum_mismatch_detected(tree_art, tmp_path):
    """Corrupt one member blob *inside* an otherwise-valid container: the
    sha256 map must catch it before deserialization."""
    import msgpack

    from repro.train.checkpoint import compress_bytes, decompress_bytes

    path = str(tmp_path / "tree.embml")
    tree_art.save(path)
    payload = msgpack.unpackb(decompress_bytes(open(path, "rb").read()),
                              raw=False, strict_map_key=False)
    params = bytearray(payload["members"]["params"])
    params[len(params) // 2] ^= 0x01
    payload["members"]["params"] = bytes(params)
    open(path, "wb").write(
        compress_bytes(msgpack.packb(payload, use_bin_type=True)))
    with pytest.raises(ArtifactIntegrityError, match="params"):
        load(path)


def test_fault_injected_archive_corruption_caught(tree_art, tmp_path):
    path = str(tmp_path / "tree.embml")
    tree_art.save(path)
    plan = FaultPlan([FaultRule(site="artifact.load", kind="corrupt",
                                corrupt_bytes=8)], seed=3)
    with faults.inject(plan):
        with pytest.raises(ArtifactIntegrityError):
            load(path)
    # with the plan gone the same file loads fine — nothing on disk changed
    assert load(path) is not None


def test_legacy_v2_archive_still_loads(tree_art, blobs, tmp_path):
    """Pre-integrity archives (members inline, no checksum map) load."""
    import dataclasses as dc

    import msgpack

    from repro.compile.artifact import _ARCHIVE_FORMAT, _encode
    from repro.train.checkpoint import compress_bytes

    _, _, xte, _, _ = blobs
    payload = {
        "format": _ARCHIVE_FORMAT,
        "version": 1,
        "kind": tree_art.kind,
        "target": dc.asdict(tree_art.target),
        "params": _encode(tree_art.params),
        "quant_plan": None,
        "metadata": {},
        "saved_at": 0.0,
    }
    path = str(tmp_path / "legacy.embml")
    open(path, "wb").write(
        compress_bytes(msgpack.packb(payload, use_bin_type=True)))
    again = load(path)
    np.testing.assert_array_equal(again.predict(xte), tree_art.predict(xte))


# ---------------------------------------------------------------------------
# compile-failure fault site (single-flight cache)
# ---------------------------------------------------------------------------
def test_injected_compile_failure_does_not_poison_cache(blobs):
    from repro.serve import ArtifactCache

    xtr, ytr, _, _, c = blobs
    model = train_decision_tree(xtr, ytr, c, max_depth=4)
    cache = ArtifactCache()
    target = Target(number_format="fxp16", backend="xla")
    plan = FaultPlan([FaultRule(site="cache.compile", count=1,
                                transient=True)])
    with faults.inject(plan):
        with pytest.raises(faults.InjectedFault):
            cache.get_or_compile(model, target)
        art = cache.get_or_compile(model, target)  # slot cleared: retry works
    assert art is cache.get_or_compile(model, target)
