"""Tests for fleet megabatching: cross-endpoint stacked dispatch.

* signature algebra: which artifacts may share a stacked program (pallas
  megakernel MLP/logistic/SVM yes; trees, xla backends, mixed containers no);
* FleetStack slot bit-identity: slot ``e`` of the stacked dispatch equals
  member ``e``'s own ``predict`` — shared rows and per-slot rows, for the
  heterogeneous (calibrated auto16) MLP path and the SVM path, calibrated
  rbf chains included;
* ONE dispatch per stacked forward (fresh-stack trace, the megakernel gate);
* ``enable_fleet`` golden bit-identity with mixed model kinds registered —
  incompatible endpoints (tree, xla) keep their own workers;
* cross-endpoint isolation property: adversarial interleaved threaded
  submits never route one endpoint's rows (or outputs) to another;
* zero-copy staging: the coalescer's buffer allocations plateau at two per
  bucket; the per-endpoint batch-1 fast path copies nothing;
* degradation and circuit breaking honored per member under coalescing;
* round order: a round that holds for stragglers finalizes the round in
  flight inside its hold, before its own launch, and keeps its straggler
  window; a round that does not hold launches first;
* lifecycle: close resolves every future; ``get_or_stack`` dedupes.
"""

import threading
import time

import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.compile import Target, compile, fleet_signature, stack_fleet
from repro.kernels import ops
from repro.models import (train_decision_tree, train_kernel_svm,
                          train_logistic, train_mlp)
from repro.serve import (ArtifactCache, BatchingPolicy, BreakerPolicy,
                         CircuitOpenError, DegradationPolicy,
                         InferenceService, MicroBatcher)

F, C, E = 8, 3, 3
PALLAS16 = Target(number_format="auto16", backend="pallas")


@pytest.fixture(scope="module")
def blobs():
    rng = np.random.RandomState(7)
    n = 360
    means = rng.randn(C, F) * 4.0
    y = rng.randint(0, C, n).astype(np.int32)
    x = (means[y] + rng.randn(n, F)).astype(np.float32)
    return x[:240], y[:240], x[240:], y[240:]


@pytest.fixture(scope="module")
def cache():
    return ArtifactCache()


@pytest.fixture(scope="module")
def fleet_models(blobs):
    xtr, ytr, _, _ = blobs
    return [train_mlp(xtr, ytr, C, hidden=(8,), epochs=6, seed=s)
            for s in range(E)]


@pytest.fixture(scope="module")
def fleet_arts(fleet_models, blobs, cache):
    """E structurally-identical MLPs with *different* weights and different
    calibration slices — the heterogeneous-schedule stacking path."""
    xtr = blobs[0]
    arts = [cache.get_or_compile(m, PALLAS16, calibration=xtr[40 * s:120 + 40 * s])
            for s, m in enumerate(fleet_models)]
    sigs = {fleet_signature(a) for a in arts}
    assert len(sigs) == 1 and None not in sigs
    return arts


def _policy():
    return BatchingPolicy(max_batch=4, max_wait_ms=2)


def _fleet_service(cache, arts):
    svc = InferenceService(cache=cache)
    for i, a in enumerate(arts):
        svc.register(f"m{i}", artifact=a, policy=_policy())
    formed = svc.enable_fleet()
    assert sum(len(m) for m in formed.values()) == len(arts)
    return svc


@pytest.fixture(scope="module")
def fleet_svc(cache, fleet_arts):
    svc = _fleet_service(cache, fleet_arts)
    yield svc
    svc.close()


# ---------------------------------------------------------------------------
# fleet_signature: the stacking-compatibility algebra
# ---------------------------------------------------------------------------
def test_signature_rules(fleet_arts, blobs, cache):
    xtr, ytr = blobs[0], blobs[1]
    sig = fleet_signature(fleet_arts[0])
    assert sig is not None and sig[0] == "mlp"
    assert all(fleet_signature(a) == sig for a in fleet_arts)
    # trees have no stacked program
    tree = compile(train_decision_tree(xtr, ytr, C, max_depth=4),
                   Target(number_format="fxp16", backend="pallas"))
    assert fleet_signature(tree) is None
    # the fleet kernels ARE pallas programs: xla artifacts cannot ride
    xla = cache.get_or_compile(train_mlp(xtr, ytr, C, hidden=(8,), epochs=2),
                               Target(number_format="fxp16", backend="xla"))
    assert fleet_signature(xla) is None
    # a logistic model is a 1-layer MLP to the stacked program
    logi = compile(train_logistic(xtr, ytr, C, epochs=4),
                   Target(number_format="fxp16", backend="pallas"))
    lsig = fleet_signature(logi)
    assert lsig is not None and lsig[0] == "mlp" and lsig[2] == (F, C)


def test_stack_fleet_rejects_incompatible(fleet_arts, blobs):
    xtr, ytr = blobs[0], blobs[1]
    with pytest.raises(ValueError):
        stack_fleet(fleet_arts[:1])  # a fleet of one is not a fleet
    svm = compile(train_kernel_svm(xtr, ytr, C, kernel="rbf",
                                   n_prototypes=16, epochs=3),
                  Target(number_format="fxp16", backend="pallas"))
    with pytest.raises(ValueError):
        stack_fleet([fleet_arts[0], svm])


# ---------------------------------------------------------------------------
# FleetStack: slot bit-identity + single dispatch
# ---------------------------------------------------------------------------
def test_stack_slot_identity_shared_rows(fleet_arts, blobs, cache):
    xte = blobs[2][:16]
    stack = cache.get_or_stack(fleet_arts)
    out = stack.predict(xte)
    assert out.shape == (E, 16)
    for e, art in enumerate(fleet_arts):
        np.testing.assert_array_equal(out[e], art.predict(xte))


def test_stack_slot_identity_per_slot_rows(fleet_arts, blobs, cache):
    """(E, M, F) staging-buffer input: every slot carries different rows."""
    xte = blobs[2]
    xs = np.stack([xte[8 * e:8 * e + 8] for e in range(E)])
    out = cache.get_or_stack(fleet_arts).predict(xs)
    for e, art in enumerate(fleet_arts):
        np.testing.assert_array_equal(out[e], art.predict(xs[e]))


def test_stack_is_one_dispatch(fleet_arts, blobs):
    """E models, one forward, ONE kernel dispatch — counted on a fresh
    stack so the trace-time tick lands inside the counter scope (same
    convention as the per-model megakernel gates)."""
    xte = blobs[2][:4]
    with ops.count_dispatches() as c:
        fresh = stack_fleet(fleet_arts)
        fresh.predict(xte)
    assert c.count == 1


def test_stack_svm_slot_identity(blobs):
    xtr, ytr, xte, _ = blobs
    arts = [compile(train_kernel_svm(xtr, ytr, C, kernel="rbf",
                                     n_prototypes=16, epochs=3 + s, seed=s),
                    Target(number_format="fxp16", backend="pallas"))
            for s in range(2)]
    sig = fleet_signature(arts[0])
    assert sig is not None and sig[0] == "svm"
    assert fleet_signature(arts[1]) == sig
    out = stack_fleet(arts).predict(xte[:12])
    for e, art in enumerate(arts):
        np.testing.assert_array_equal(out[e], art.predict(xte[:12]))


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "mixed"])
@pytest.mark.parametrize("fmt", ["auto16", "auto8"])
def test_stack_calibrated_rbf_slot_identity(blobs, fmt, uniform):
    """Calibrated rbf members carry their chain of formats into the stack:
    one plan for every member (the batched kernel), or a calibrated member
    beside a single-format fxp one (the ``lax.switch`` branches mix the
    chain with the single-format arithmetic)."""
    xtr, ytr, xte, _ = blobs
    model = train_kernel_svm(xtr, ytr, C, kernel="rbf", n_prototypes=16,
                             epochs=3, seed=0)
    bits = fmt[len("auto"):]
    second = (Target(number_format=fmt, backend="pallas") if uniform
              else Target(number_format=f"fxp{bits}", backend="pallas"))
    arts = [compile(model, Target(number_format=fmt, backend="pallas"),
                    calibration=xtr),
            compile(model, second, calibration=xtr if uniform else None)]
    assert arts[0].extras["emit_spec"]["chain"] is not None
    assert fleet_signature(arts[0]) == fleet_signature(arts[1])
    x = np.concatenate([xte[:12], xte[:4] * 5.0])
    out = stack_fleet(arts).predict(x)
    for e, art in enumerate(arts):
        np.testing.assert_array_equal(out[e], art.predict(x))


# ---------------------------------------------------------------------------
# enable_fleet: golden bit-identity, mixed kinds fall back per-kind
# ---------------------------------------------------------------------------
def test_enable_fleet_mixed_kinds_golden(cache, fleet_arts, blobs):
    """A registry mixing stackable MLPs with a tree and an xla endpoint:
    only the compatible group coalesces; every endpoint stays golden."""
    xtr, ytr, xte, _ = blobs
    tree = compile(train_decision_tree(xtr, ytr, C, max_depth=4),
                   Target(number_format="fxp16", backend="pallas"))
    xla = cache.get_or_compile(train_mlp(xtr, ytr, C, hidden=(8,), epochs=2),
                               Target(number_format="fxp16", backend="xla"))
    svc = InferenceService(cache=cache)
    try:
        for i, a in enumerate(fleet_arts):
            svc.register(f"m{i}", artifact=a, policy=_policy())
        svc.register("tree", artifact=tree, policy=_policy())
        svc.register("solo-xla", artifact=xla, policy=_policy())
        formed = svc.enable_fleet()
        assert list(formed.values()) == [["m0", "m1", "m2"]]

        names = [f"m{i}" for i in range(E)] + ["tree", "solo-xla"]
        golden = {"tree": tree.predict(xte), "solo-xla": xla.predict(xte)}
        for i, a in enumerate(fleet_arts):
            golden[f"m{i}"] = a.predict(xte)
        futs = [(n, i, svc.endpoint(n).submit(xte[i:i + 1]))
                for i in range(48) for n in names]
        for n, i, f in futs:
            assert f.result(timeout=120)[0] == golden[n][i], n
        snap = svc.stats()
        assert snap["_fleets"][0]["members"] == ["m0", "m1", "m2"]
        # heavy interleaved traffic: the coalescer must have stacked rounds
        assert snap["_fleets"][0]["stacked_dispatches"] >= 1
        # incompatible endpoints served by their own workers, never a fleet
        assert snap["tree"]["batches"] >= 1
        assert snap["solo-xla"]["batches"] >= 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# isolation property: coalescing never crosses endpoint boundaries
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fleet_isolation_under_adversarial_interleaving(
        fleet_svc, fleet_arts, blobs, seed):
    """Each endpoint's responses equal its OWN artifact's goldens, row for
    row, under concurrent interleaved submits of random-size slices with
    random jitter — rows and outputs never leak across slots."""
    xte = blobs[2]
    golden = [a.predict(xte) for a in fleet_arts]
    errors = []

    def client(e, sub_seed):
        rng = np.random.RandomState(sub_seed)
        ep = fleet_svc.endpoint(f"m{e}")
        futs = []
        for _ in range(12):
            n = int(rng.randint(1, 5))
            lo = int(rng.randint(0, xte.shape[0] - n))
            futs.append((lo, n, ep.submit(xte[lo:lo + n])))
            if rng.rand() < 0.3:
                time.sleep(float(rng.rand()) * 1e-3)
        for lo, n, f in futs:
            got = f.result(timeout=120)
            if not np.array_equal(got, golden[e][lo:lo + n]):
                errors.append((e, lo, n, got))

    rng = np.random.RandomState(seed)
    threads = [threading.Thread(target=client, args=(e, int(rng.randint(2**31))))
               for e in range(E)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


# ---------------------------------------------------------------------------
# zero-copy assembly
# ---------------------------------------------------------------------------
def test_staging_allocations_plateau(fleet_svc, blobs):
    """The coalescer preallocates two (E, bucket, F) buffers per bucket —
    steady-state traffic allocates nothing new."""
    xte = blobs[2]
    co = next(iter(fleet_svc._fleets.values()))

    def drive():
        futs = [fleet_svc.endpoint(f"m{e}").submit(xte[i:i + 1 + i % 4])
                for i in range(24) for e in range(E)]
        for f in futs:
            f.result(timeout=120)

    drive()
    n_buckets = len(fleet_svc.endpoint("m0").policy.buckets())
    assert 0 < co.n_staging_allocs <= 2 * n_buckets
    before = co.n_staging_allocs
    drive()
    assert co.n_staging_allocs == before  # plateau: buffers are reused
    snap = co.snapshot()
    assert snap["staging_allocs"] == before
    assert snap["assembly_s"] >= 0.0 and snap["device_s"] > 0.0


def test_batch1_fastpath_is_zero_copy(fleet_arts, blobs):
    """A lone full-bucket request is dispatched as-is: no staging copy, no
    concatenate — and still bit-identical."""
    art, xte = fleet_arts[0], blobs[2]
    with MicroBatcher(art.predict, _policy()) as mb:
        got = mb.submit(xte[:4]).result(timeout=120)  # 4 == top bucket
        stats = mb.assembly_stats()
    np.testing.assert_array_equal(got, art.predict(xte[:4]))
    assert stats["n_batch1_fastpath"] >= 1
    assert stats["n_concat_assemblies"] == 0


# ---------------------------------------------------------------------------
# degradation + breaker semantics survive coalescing
# ---------------------------------------------------------------------------
def test_degraded_member_leaves_stack(cache, fleet_arts, fleet_models, blobs):
    xtr, xte = blobs[0], blobs[2]
    fallback = cache.get_or_compile(
        fleet_models[0], Target(number_format="auto8", backend="pallas"),
        calibration=xtr)
    svc = _fleet_service(cache, fleet_arts)
    try:
        ep0 = svc.enable_degradation(
            "m0", artifact=fallback,
            policy=DegradationPolicy(min_hold_s=3600.0))
        ep0.governor.observe(ep0.governor.policy.queue_high, None)
        assert ep0.degraded
        want0 = fallback.predict(xte)  # degraded golden, NOT the primary's
        want1 = fleet_arts[1].predict(xte)
        futs = [(i, svc.endpoint("m0").submit(xte[i:i + 1]),
                 svc.endpoint("m1").submit(xte[i:i + 1])) for i in range(24)]
        for i, f0, f1 in futs:
            assert f0.result(timeout=120)[0] == want0[i]
            assert f0.batch_meta["degraded"] is True
            assert f1.result(timeout=120)[0] == want1[i]
    finally:
        svc.close()


def test_breaker_member_probes_solo_then_rejoins(cache, fleet_arts, blobs):
    xte = blobs[2]
    svc = _fleet_service(cache, fleet_arts)
    try:
        ep2 = svc.enable_breaker(
            "m2", BreakerPolicy(consecutive_failures=2, open_s=0.05))
        golden = fleet_arts[2].predict(xte)
        ep2.breaker.record_failure()
        ep2.breaker.record_failure()
        assert ep2.breaker.state == ep2.breaker.OPEN
        with pytest.raises(CircuitOpenError):
            ep2.submit(xte[:1])
        time.sleep(0.1)
        # half-open probes are served solo (feeding THIS breaker), still
        # bit-identical; enough successes close it and it rides again
        for i in range(4):
            assert ep2.submit(xte[i:i + 1]).result(timeout=120)[0] == golden[i]
        assert ep2.breaker.state == ep2.breaker.CLOSED
        futs = [ep2.submit(xte[i:i + 1]) for i in range(16)]
        for i, f in enumerate(futs):
            assert f.result(timeout=120)[0] == golden[i]
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# round order: the in-flight round is finalized inside a straggler hold
# ---------------------------------------------------------------------------
class _LoggedStack:
    """A fleet's stack that logs each launch into the test's event log."""

    def __init__(self, stack, log):
        self._stack, self._log = stack, log
        self.n_models, self.n_features = stack.n_models, stack.n_features

    def predict_device(self, buf):
        self._log.append(("launch",))
        return self._stack.predict_device(buf)


class _Stepper:
    """Runs the coalescer's rounds one at a time, when the test steps it;
    every round runs once the coalescer is closing."""

    def __init__(self, co):
        self._co, self._round = co, co._round
        self._permits = threading.Semaphore(0)
        self._done = threading.Semaphore(0)
        self._parked = threading.Event()
        co._round = self._gated
        assert self._parked.wait(30), "the coalescer never reached a round"

    def _gated(self):
        self._parked.set()
        while not self._co._closed:
            if self._permits.acquire(timeout=0.01):
                break
        try:
            return self._round()
        finally:
            self._done.release()

    def step(self):
        """Run exactly one round; return once the next one is parked."""
        self._parked.clear()
        self._permits.release()
        assert self._done.acquire(timeout=120)
        assert self._parked.wait(30)


@pytest.fixture()
def stepped(cache, fleet_arts, blobs):
    """A warmed fleet whose rounds the test steps, the shared event log
    (launches and future completions), and a submit that logs its
    future's completion under a tag."""
    xte = blobs[2]
    svc = _fleet_service(cache, fleet_arts)
    co = next(iter(svc._fleets.values()))
    for _ in range(50):
        for f in [svc.endpoint(f"m{e}").submit(xte[e]) for e in range(E)]:
            f.result(timeout=120)
        if co.n_stacked_dispatches:
            break
    log = []
    co.stack = _LoggedStack(co.stack, log)
    stepper = _Stepper(co)

    def submit(e, i, tag):
        f = svc.endpoint(f"m{e}").submit(xte[i:i + 1])
        f.add_done_callback(lambda _: log.append(("done", tag)))
        return f

    try:
        yield svc, co, stepper, log, submit
    finally:
        svc.close()


def _golden_check(fleet_arts, xte, served):
    for e, i, f in served:
        assert f.result(timeout=120)[0] == fleet_arts[e].predict(xte)[i]


def test_held_round_finalizes_the_round_in_flight_before_its_launch(
        stepped, fleet_arts, blobs):
    svc, co, stepper, log, submit = stepped
    first = [(e, e, submit(e, e, ("first", e))) for e in range(E)]
    stepper.step()  # all members ride: launched, left in flight
    assert log == [("launch",)]
    assert not any(f.done() for _, _, f in first)
    held = [(e, 5 + e, submit(e, 5 + e, ("held", e))) for e in (0, 1)]
    stepper.step()  # two of three ride: the round holds
    assert sorted(log[1:4]) == [("done", ("first", e)) for e in range(E)]
    assert log[4:] == [("launch",)]
    assert co.snapshot()["hold_finalizes"] == 1
    assert not any(f.done() for _, _, f in held)
    stepper.step()  # idle: the held round is forced out
    _golden_check(fleet_arts, blobs[2], first + held)
    assert co.snapshot()["hold_finalizes"] == 1


@pytest.mark.parametrize("riders", [(0, 1, 2), (0,)], ids=["full", "lone"])
def test_round_that_does_not_hold_finalizes_its_predecessor_after(
        stepped, fleet_arts, blobs, riders):
    svc, co, stepper, log, submit = stepped
    first = [(e, e, submit(e, e, ("first", e))) for e in range(E)]
    stepper.step()
    nxt = [(e, 7 + e, submit(e, 7 + e, ("next", e))) for e in riders]
    stepper.step()
    if len(riders) == E:
        # Launched first; the sync of the round before overlaps it.
        assert log[1] == ("launch",)
        assert sorted(log[2:]) == [("done", ("first", e)) for e in range(E)]
    else:
        # A lone rider is served on its own path, then the round before
        # is finalized; nothing is launched.
        assert log[1:] == [("done", ("next", 0))] + [
            ("done", ("first", e)) for e in range(E)]
    assert co.snapshot()["hold_finalizes"] == 0
    stepper.step()
    _golden_check(fleet_arts, blobs[2], first + nxt)


def test_straggler_submitted_during_the_finalize_rides_the_held_round(
        stepped, fleet_arts, blobs):
    svc, co, stepper, log, submit = stepped
    snap = co.snapshot()
    stacked_before, solo_before = snap["stacked_requests"], snap["solo_batches"]
    first = [(e, e, submit(e, e, ("first", e))) for e in range(E)]
    stepper.step()
    held = [(e, 5 + e, submit(e, 5 + e, ("held", e))) for e in (0, 1)]
    late = []
    # Runs on the coalescer thread while it finalizes the round in flight,
    # after the held round's first sweep.
    first[0][2].add_done_callback(
        lambda _: late.append((2, 9, submit(2, 9, ("late", 2)))))
    stepper.step()
    assert len(late) == 1
    assert log[-1] == ("launch",) and not late[0][2].done()
    assert co.snapshot()["hold_finalizes"] == 1
    stepper.step()  # idle: nothing was left queued for a third round
    assert log[-1] == ("done", ("late", 2)) and log.count(("launch",)) == 2
    assert late[0][2].batch_meta["coalesced"] is True
    snap = co.snapshot()
    assert snap["stacked_requests"] - stacked_before == E + E
    assert snap["solo_batches"] == solo_before
    _golden_check(fleet_arts, blobs[2], first + held + late)


def test_close_resolves_the_round_in_flight_and_the_held_round(
        stepped, fleet_arts, blobs):
    svc, co, stepper, log, submit = stepped
    first = [(e, e, submit(e, e, ("first", e))) for e in range(E)]
    stepper.step()
    held = [(e, 5 + e, submit(e, 5 + e, ("held", e))) for e in (0, 1)]
    svc.close()  # the parked round runs, then the coalescer stops
    assert all(f.done() for _, _, f in first + held)
    _golden_check(fleet_arts, blobs[2], first + held)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------
def test_close_resolves_every_future(cache, fleet_arts, blobs):
    xte = blobs[2]
    svc = _fleet_service(cache, fleet_arts)
    futs = [svc.endpoint(f"m{e}").submit(xte[i:i + 1])
            for i in range(16) for e in range(E)]
    svc.close()
    golden = [a.predict(xte) for a in fleet_arts]
    for j, f in enumerate(futs):
        i, e = divmod(j, E)
        assert f.result(timeout=120)[0] == golden[e][i]


def test_get_or_stack_dedupes(cache, fleet_arts):
    s1 = cache.get_or_stack(fleet_arts)
    s2 = cache.get_or_stack(fleet_arts)
    assert s1 is s2


@pytest.mark.parametrize("where", ["register", "fleet"])
def test_register_pretune_warms_ladder(cache, fleet_arts, blobs, where,
                                       monkeypatch):
    """pretune=<example> walks the bucket ladder at registration — the
    launcher's --pretune path — and serving stays golden.  A fleet warms
    every member's solo ladder through ``MicroBatcher.warm`` on its own
    thread before its first stacked round."""
    xte = blobs[2]
    if where == "fleet":
        warmed, real = [], MicroBatcher.warm

        def warm(self, example):
            warmed.append((self.name, example.shape,
                           threading.current_thread().name))
            return real(self, example)

        monkeypatch.setattr(MicroBatcher, "warm", warm)
        svc = _fleet_service(cache, fleet_arts)
        co = next(iter(svc._fleets.values()))
        try:
            for i in range(50):
                futs = [svc.endpoint(f"m{e}").submit(xte[i:i + 1])
                        for e in range(E)]
                for e, f in enumerate(futs):
                    assert f.result(timeout=120)[0] == \
                        fleet_arts[e].predict(xte[i:i + 1])[0]
                if co.n_stacked_dispatches:
                    break
            assert co.n_stacked_dispatches >= 1
        finally:
            svc.close()
        assert {(name, shape, thread) for name, shape, thread in warmed} \
            == {(f"m{e}", (1, F), "fleet-coalescer") for e in range(E)}
        assert co.snapshot()["warmup_failures"] == 0
        return
    svc = InferenceService(cache=cache)
    try:
        ep = svc.register("warm", artifact=fleet_arts[0], policy=_policy(),
                          pretune=xte[:1])
        got = ep.submit(xte[:4]).result(timeout=120)
        np.testing.assert_array_equal(got, fleet_arts[0].predict(xte[:4]))
    finally:
        svc.close()
