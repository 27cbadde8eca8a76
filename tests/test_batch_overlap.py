"""The micro-batcher's pipeline for full buckets.

The worker launches a batch and leaves its outputs unforced while a whole
``max_batch`` of rows is queued behind it: it gathers and launches the
next batch first, then forces and scatters the one before.  These tests
drive it with a predict that returns outputs which only ``np.asarray``
forces, and count how many were unforced at each call:

* a bulk request split into 8 full buckets gives the blocking order's
  rows, with 7 of its 8 dispatches launched behind an unforced batch;
* no launch ever finds more than one batch unforced;
* single-row traffic that never queues a full bucket is never overlapped;
* a pipelined batch whose launch or force fails is retried or bisected,
  and the other batch's answers are unaffected;
* ``close()`` and ``detach_worker()`` land the batch in flight;
* a staging buffer is never written while its batch is unforced.
"""

import threading
import time

import numpy as np
import pytest

from repro.compile import Target, compile
from repro.models import train_mlp
from repro.serve import BatchingPolicy, MicroBatcher, ModelRouter, faults
from repro.serve.faults import FaultPlan, FaultRule
from repro.serve.reliability import (DeadlineExceeded, DispatchError,
                                     RetryPolicy)

F = 8
POISON = -7.0


def answer(x):
    """What the probe's program computes: a row's id, doubled, plus one."""
    return (2 * x[:, 0] + 1).astype(np.int64)


class Lazy:
    """Outputs of one call that ``np.asarray`` forces; forcing checks that
    the input the call was handed was not written since."""

    def __init__(self, probe, x):
        self.probe, self.x, self.seen = probe, x, np.array(x)

    def __array__(self, dtype=None, copy=None):
        p = self.probe
        p.unforced -= 1
        if not np.array_equal(self.x, self.seen):
            p.dirty += 1
        if (self.seen[:, 0] == POISON).any():
            raise ValueError("poison row at the force")
        return answer(self.seen)


class Probe:
    """A predict that launches without forcing.  ``at_call[i]``: outputs
    still unforced when call ``i`` began.  The calls listed in ``gates``
    wait for their event (set by the test) before they return."""

    def __init__(self, gates=()):
        self.unforced = self.dirty = 0
        self.at_call = []
        self.gates = {i: threading.Event() for i in gates}
        self.entered = {i: threading.Event() for i in gates}

    def __call__(self, x):
        i = len(self.at_call)
        self.at_call.append(self.unforced)
        if i in self.gates:
            self.entered[i].set()
            assert self.gates[i].wait(30)
        self.unforced += 1
        return Lazy(self, x)


def ids(n, start=0):
    x = np.zeros((n, 2), np.float32)
    x[:, 0] = np.arange(start, start + n)
    return x


def batcher(probe, max_batch):
    return MicroBatcher(probe, BatchingPolicy(max_batch=max_batch,
                                              warmup=False))


def submit_blocked(b, probe, blocks):
    """Submit ``blocks`` while the first call waits at its gate, so all of
    them are queued when it returns; the futures, in order."""
    futs = [b.submit(blocks[0])]
    assert probe.entered[0].wait(30)
    futs += [b.submit(x) for x in blocks[1:]]
    probe.gates[0].set()
    return futs


# ---------------------------------------------------------------------------
# order and counts
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_artifact():
    rng = np.random.RandomState(5)
    x = rng.randn(256, F).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32) + (x[:, 1] > 0)
    model = train_mlp(x, y, 3, hidden=(8,), epochs=2, seed=0)
    return compile(model, Target(number_format="fxp16", backend="xla"))


def test_bulk_endpoint_predict_matches_the_blocking_order(mlp_artifact):
    x = np.random.RandomState(1).randn(32768, F).astype(np.float32) * 3
    router = ModelRouter()
    ep = router.register("bulk", mlp_artifact,
                         policy=BatchingPolicy(max_batch=4096, warmup=False))
    try:
        blocking = np.concatenate([mlp_artifact.predict(x[i:i + 4096])
                                   for i in range(0, len(x), 4096)])
        for _ in range(3):
            np.testing.assert_array_equal(ep.predict(x), blocking)
        assert ep.batcher.n_batch1_fastpath == 24
        assert ep.batcher.n_dispatch_failures == 0
    finally:
        router.close()


def test_seven_of_eight_bulk_dispatches_are_overlapped():
    probe = Probe(gates=(0,))
    b = batcher(probe, 4096)
    try:
        blocks = [ids(4096, 4096 * k) for k in range(8)]
        futs = submit_blocked(b, probe, blocks)
        for f, x in zip(futs, blocks):
            np.testing.assert_array_equal(f.result(timeout=30), answer(x))
    finally:
        b.close()
    assert probe.at_call == [0, 1, 1, 1, 1, 1, 1, 1]
    assert probe.unforced == 0


def test_no_launch_finds_more_than_one_unforced_batch():
    probe = Probe(gates=(0,))
    b = batcher(probe, 64)
    sizes = [64, 1, 63, 64, 64, 5, 64, 30, 34, 64, 64, 64, 2, 64] * 3
    starts = np.cumsum([0] + sizes)
    blocks = [ids(n, int(s)) for n, s in zip(sizes, starts)]
    futs = {}

    def produce(ks):
        for k in ks:
            futs[k] = b.submit(blocks[k])

    try:
        futs[0] = b.submit(blocks[0])
        assert probe.entered[0].wait(30)
        # Three producers race the worker once the first call returns.
        threads = [threading.Thread(target=produce,
                                    args=(range(1 + i, len(blocks), 3),))
                   for i in range(3)]
        for t in threads:
            t.start()
        probe.gates[0].set()
        for t in threads:
            t.join(30)
        for k, x in enumerate(blocks):
            np.testing.assert_array_equal(futs[k].result(timeout=30),
                                          answer(x))
    finally:
        b.close()
    assert max(probe.at_call) <= 1
    assert probe.unforced == 0


def test_single_row_traffic_is_never_overlapped():
    probe = Probe(gates=(0,))
    b = batcher(probe, 64)
    try:
        # A burst of 40 rows, queued behind a gated first call: less than
        # a bucket, so nothing is left in flight.
        burst = [ids(1, i) for i in range(40)]
        futs = submit_blocked(b, probe, burst)
        for f, x in zip(futs, burst):
            np.testing.assert_array_equal(f.result(timeout=30), answer(x))
        # Then one row at a time.
        for i in range(40, 60):
            np.testing.assert_array_equal(
                b.submit(ids(1, i)).result(timeout=30), answer(ids(1, i)))
    finally:
        b.close()
    assert probe.at_call and set(probe.at_call) == {0}


# ---------------------------------------------------------------------------
# failures of a pipelined batch
# ---------------------------------------------------------------------------
def _endpoint(artifact, plan_rules, retry=None):
    router = ModelRouter()
    ep = router.register("ep", artifact,
                         policy=BatchingPolicy(max_batch=512, warmup=False),
                         retry=retry)
    # The first dispatch waits while the other blocks queue up behind it.
    rules = [FaultRule(site="endpoint.dispatch", kind="delay", delay_s=0.3,
                       count=1)] + plan_rules
    return router, ep, FaultPlan(rules, seed=3)


def test_transient_fault_at_the_launch_of_a_pipelined_batch_is_retried(
        mlp_artifact):
    x = np.random.RandomState(2).randn(4096, F).astype(np.float32)
    blocking = mlp_artifact.predict(x)
    router, ep, plan = _endpoint(
        mlp_artifact,
        [FaultRule(site="endpoint.dispatch", first=3, count=1)],
        retry=RetryPolicy(max_attempts=2, backoff_base_s=0.001))
    try:
        with faults.inject(plan):
            np.testing.assert_array_equal(ep.predict(x), blocking)
        snap = ep.snapshot()
        assert snap["dispatch_failures"] == 1
        assert snap["dispatch_retries"] == 1
        assert snap["failed_requests"] == 0
    finally:
        router.close()


def test_poison_at_the_launch_of_a_pipelined_batch_fails_alone(
        mlp_artifact):
    x = np.random.RandomState(3).randn(4096, F).astype(np.float32)
    blocking = mlp_artifact.predict(x)
    x[1500, 2] = POISON  # in the third 512-row block, launched pipelined
    router, ep, plan = _endpoint(
        mlp_artifact,
        [FaultRule(site="endpoint.dispatch", poison=POISON,
                   transient=False)])
    try:
        with faults.inject(plan):
            futs = [ep.submit(x[i:i + 512]) for i in range(0, 4096, 512)]
            for k, f in enumerate(futs):
                if k == 2:
                    with pytest.raises(DispatchError):
                        f.result(timeout=30)
                else:
                    np.testing.assert_array_equal(
                        f.result(timeout=30), blocking[512 * k:512 * k + 512])
        assert ep.snapshot()["dispatch_failures"] == 1
    finally:
        router.close()


def test_exception_at_the_force_of_a_pipelined_batch_is_bisected():
    probe = Probe(gates=(0,))
    b = batcher(probe, 512)
    blocks = [ids(128, 128 * k) for k in range(32)]
    blocks[13][70, 0] = POISON  # the fourth 512-row batch, pipelined
    try:
        futs = submit_blocked(b, probe, blocks)
        for k, (f, x) in enumerate(zip(futs, blocks)):
            if k == 13:
                with pytest.raises(DispatchError) as err:
                    f.result(timeout=30)
                assert err.value.isolated
            else:
                np.testing.assert_array_equal(f.result(timeout=30),
                                              answer(x))
    finally:
        b.close()
    # The batch's first attempt, then its halves' failures down to the
    # poison request.
    assert b.n_dispatch_failures == 3
    assert b.n_failed_requests == 1
    # Block 0 alone (cut by the empty queue, so forced at once), then
    # batches of four blocks: [1-4] is left in flight, [5-8] to [17-20]
    # launch behind the batch before; the force of [13-16] fails, so
    # [17-20] is forced too and the four bisection dispatches run with
    # nothing in flight; [21-24] starts the pipeline again, and [29-31]
    # has no full bucket behind [25-28].
    assert probe.at_call == [0, 0, 1, 1, 1, 1, 0, 0, 0, 0, 0, 1, 0]
    assert probe.unforced == 0


# ---------------------------------------------------------------------------
# leaving with a batch in flight
# ---------------------------------------------------------------------------
def test_close_with_a_batch_in_flight_resolves_every_future():
    probe = Probe(gates=(0,))
    b = batcher(probe, 256)
    first = ids(256)
    fut = b.submit(first)
    assert probe.entered[0].wait(30)
    # Full buckets queued behind the launch keep it in flight; they expire
    # before the worker takes them, so it leaves with the batch in flight.
    late = [b.submit(ids(256, 256 * k), timeout_s=0.0) for k in range(1, 4)]
    closer = threading.Thread(target=b.close)
    closer.start()
    probe.gates[0].set()
    closer.join(30)
    assert not closer.is_alive()
    np.testing.assert_array_equal(fut.result(timeout=1), answer(first))
    for f in late:
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=1)
    assert probe.at_call == [0] and probe.unforced == 0


def test_detach_with_a_batch_in_flight_resolves_every_future():
    probe = Probe(gates=(0, 1))
    b = batcher(probe, 256)
    blocks = [ids(256, 256 * k) for k in range(8)]
    try:
        futs = submit_blocked(b, probe, blocks)
        assert probe.entered[1].wait(30)
        # The second batch is launching behind the first; detach now.
        detacher = threading.Thread(target=b.detach_worker)
        detacher.start()
        while not b._detached:
            time.sleep(0.001)
        probe.gates[1].set()
        detacher.join(30)
        assert not detacher.is_alive()
        # Both launched batches were landed before the worker left.
        for f, x in zip(futs[:2], blocks[:2]):
            assert f.done()
            np.testing.assert_array_equal(f.result(), answer(x))
        assert not any(f.done() for f in futs[2:])
        assert probe.at_call == [0, 1] and probe.unforced == 0
        # The caller serves the rest itself, blocking.
        while True:
            batch = b.gather()
            if not batch:
                break
            b.serve(batch)
        for f, x in zip(futs[2:], blocks[2:]):
            np.testing.assert_array_equal(f.result(timeout=1), answer(x))
    finally:
        b.close()
    assert max(probe.at_call) == 1 and probe.unforced == 0


# ---------------------------------------------------------------------------
# staging buffers
# ---------------------------------------------------------------------------
def test_staging_buffer_is_not_written_while_its_batch_is_unforced():
    probe = Probe(gates=(0,))
    b = batcher(probe, 8)
    blocks = [ids(2, 2 * k) for k in range(41)]
    try:
        futs = submit_blocked(b, probe, blocks)
        for f, x in zip(futs, blocks):
            np.testing.assert_array_equal(f.result(timeout=30), answer(x))
    finally:
        b.close()
    # Batches of four 2-row requests, assembled into the two staging
    # buffers in turn while the batch before is in flight.
    assert sum(probe.at_call) >= 8
    assert b.n_zero_copy_assemblies >= 10 and b.n_staging_allocs == 2
    assert probe.dirty == 0 and probe.unforced == 0
