"""Tests for the ``repro.serve`` subsystem + the serving CLI.

* scheduler: bucket ladder, dynamic micro-batching, error delivery, close;
* router: endpoint registration, stats surface, lm routing;
* artifact cache: (fingerprint, Target) dedupe, LRU eviction;
* batch invariance: a row's prediction is identical whether it arrives in a
  batch of 1, zero-padded to a bucket, or mixed into a scheduler micro-batch
  (seeded sweeps via the hypothesis shim) — the property that makes
  micro-batch padding sound;
* ragged pallas batches through the compiled artifact (regression for the
  old ``b % block_batch == 0`` hard assert);
* ``launch/serve.py`` CLI smoke test, in-process.
"""

import threading
import time

import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.compile import Target, compile, fingerprint_params
from repro.models import (train_decision_tree, train_kernel_svm,
                          train_linear_svm, train_logistic, train_mlp)
from repro.serve import (ArtifactCache, BatchingPolicy, InferenceService,
                         MicroBatcher, ModelRouter)
from repro.serve.reliability import DeadlineExceeded

KINDS = ("tree", "logistic", "mlp", "svm-linear", "svm-poly", "svm-rbf")


@pytest.fixture(scope="module")
def blobs_module():
    rng = np.random.RandomState(0)
    n, f, c = 600, 12, 3
    means = rng.randn(c, f) * 4.0
    y = rng.randint(0, c, n).astype(np.int32)
    x = (means[y] + rng.randn(n, f)).astype(np.float32)
    return x[:400], y[:400], x[400:], y[400:], c


@pytest.fixture(scope="module")
def trained(blobs_module):
    xtr, ytr, _, _, c = blobs_module
    return {
        "tree": train_decision_tree(xtr, ytr, c, max_depth=6),
        "logistic": train_logistic(xtr, ytr, c, epochs=15),
        "mlp": train_mlp(xtr, ytr, c, hidden=(16,), epochs=10),
        "svm-linear": train_linear_svm(xtr, ytr, c, epochs=15),
        "svm-rbf": train_kernel_svm(xtr, ytr, c, kernel="rbf",
                                    n_prototypes=40, epochs=10),
        "svm-poly": train_kernel_svm(xtr, ytr, c, kernel="poly",
                                     n_prototypes=40, epochs=10),
    }


@pytest.fixture(scope="module")
def artifacts(trained):
    """One xla fxp16 artifact per kind — the serving configuration."""
    return {k: compile(trained[k], Target(number_format="fxp16", backend="xla"))
            for k in KINDS}


# ---------------------------------------------------------------------------
# BatchingPolicy
# ---------------------------------------------------------------------------
def test_policy_bucket_ladder():
    p = BatchingPolicy(max_batch=64)
    assert p.buckets() == (1, 2, 4, 8, 16, 32, 64)
    assert p.bucket_for(1) == 1
    assert p.bucket_for(3) == 4
    assert p.bucket_for(33) == 64
    assert p.bucket_for(64) == 64
    # non-power-of-two cap becomes the top bucket
    assert BatchingPolicy(max_batch=48).buckets() == (1, 2, 4, 8, 16, 32, 48)
    assert BatchingPolicy(max_batch=48).bucket_for(40) == 48


def test_policy_validation_and_clamp():
    with pytest.raises(ValueError):
        BatchingPolicy(max_batch=0)
    with pytest.raises(ValueError):
        BatchingPolicy(max_wait_ms=-1)
    assert BatchingPolicy(max_batch=64).clamped(16).max_batch == 16
    assert BatchingPolicy(max_batch=8).clamped(None).max_batch == 8
    assert BatchingPolicy(max_batch=8).clamped(16).max_batch == 8


# ---------------------------------------------------------------------------
# MicroBatcher
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("waiting", [True, False],
                         ids=["worker", "coalescer"])
def test_gather_applies_one_set_of_rules_for_both_callers(waiting):
    """The worker's gather (behind the request it blocked for, waiting up
    to its ``max_wait_ms``) and the coalescer's (no wait) form the same
    batch: an expired request is resolved and skipped, the request that
    would overflow ``max_batch`` heads the next batch, and a close
    sentinel stays queued for the final drain."""
    mb = MicroBatcher(lambda x: x[:, 0], BatchingPolicy(
        max_batch=4, max_wait_ms=60_000, eager_when_idle=False,
        warmup=False))
    mb.detach_worker()
    rows = [np.full((n, 2), i, np.float32) for i, n in enumerate((1, 1, 2, 2))]
    head, expired, mate, over = (
        mb.submit(x, timeout_s=0.0 if i == 1 else None)
        for i, x in enumerate(rows))
    mb._queue.put(None)  # what close() leaves behind its last request

    def gather(taker):
        # The worker's gather starts behind the live request it blocked
        # for; the coalescer's takes its first request itself.
        if not waiting:
            return mb.gather(taker)
        first = mb._carry or mb._queue.get_nowait()
        mb._carry = None
        return mb.gather(taker, first)

    batch = gather(1)
    assert [r.future for r in batch] == [head, mate]
    with pytest.raises(DeadlineExceeded):
        expired.result(timeout=0)
    assert mb._carry.future is over
    assert mb.n_expired == 1
    batch = gather(2)
    assert [r.future for r in batch] == [over] and mb._carry is None
    assert mb._queue.get_nowait() is None and mb._queue.empty()
    mb.serve(batch)
    np.testing.assert_array_equal(over.result(timeout=0), [3, 3])
    mb.close()


def test_microbatcher_matches_direct_predict(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    art = artifacts["tree"]
    want = art.predict(xte[:100])
    with MicroBatcher(art.predict, BatchingPolicy(max_batch=32)) as mb:
        futs = [mb.submit(xte[i]) for i in range(100)]
        got = np.array([f.result(timeout=60)[0] for f in futs])
    np.testing.assert_array_equal(got, want)


def test_microbatcher_multirow_requests(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    art = artifacts["logistic"]
    want = art.predict(xte[:60])
    with MicroBatcher(art.predict, BatchingPolicy(max_batch=16)) as mb:
        futs = [mb.submit(xte[i:i + 12]) for i in range(0, 60, 12)]
        got = np.concatenate([f.result(timeout=60) for f in futs])
    np.testing.assert_array_equal(got, want)


def test_microbatcher_actually_batches(blobs_module):
    """Many queued single-row requests must coalesce into few predict calls."""
    _, _, xte, _, _ = blobs_module
    calls = []

    def predict(x):
        calls.append(x.shape[0])
        time.sleep(0.002)  # let the queue fill behind the first dispatch
        return np.zeros(x.shape[0], np.int32)

    with MicroBatcher(predict, BatchingPolicy(max_batch=64, max_wait_ms=50,
                                              warmup=False)) as mb:
        futs = [mb.submit(xte[i]) for i in range(128)]
        for f in futs:
            f.result(timeout=60)
    assert sum(calls) >= 128  # all rows served (plus any bucket padding)
    assert len(calls) <= 20, f"expected coalescing, got {len(calls)} calls"


def test_microbatcher_hold_mode_fills_batches(blobs_module):
    """With eager_when_idle off, the worker holds the first request for
    max_wait_ms, so near-simultaneous submissions land in one batch."""
    _, _, xte, _, _ = blobs_module
    calls = []

    def predict(x):
        calls.append(x.shape[0])
        return np.zeros(x.shape[0], np.int32)

    with MicroBatcher(predict, BatchingPolicy(max_batch=8, max_wait_ms=250,
                                              eager_when_idle=False,
                                              warmup=False)) as mb:
        futs = [mb.submit(xte[i]) for i in range(3)]
        for f in futs:
            assert f.result(timeout=60).shape == (1,)
    assert len(calls) == 1 and calls[0] == 4  # one batch, bucket_for(3) == 4


def test_microbatcher_eager_serves_lone_request_quickly(artifacts, blobs_module):
    """Default policy: a lone request is not taxed the full max_wait_ms."""
    _, _, xte, _, _ = blobs_module
    art = artifacts["tree"]
    with MicroBatcher(art.predict,
                      BatchingPolicy(max_batch=64, max_wait_ms=5000)) as mb:
        mb.submit(xte[0]).result(timeout=60)  # warmup happens here
        t0 = time.perf_counter()
        out = mb.submit(xte[1]).result(timeout=60)
        elapsed = time.perf_counter() - t0
    np.testing.assert_array_equal(out, art.predict(xte[1:2]))
    assert elapsed < 2.5, f"lone request waited {elapsed:.3f}s (idle hold?)"


def test_microbatcher_oversize_request_rejected(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    with MicroBatcher(artifacts["tree"].predict,
                      BatchingPolicy(max_batch=8)) as mb:
        with pytest.raises(ValueError, match="max_batch"):
            mb.submit(xte[:9])


def test_microbatcher_delivers_predict_errors(blobs_module):
    _, _, xte, _, _ = blobs_module

    def predict(x):
        raise RuntimeError("kernel exploded")

    with MicroBatcher(predict, BatchingPolicy(warmup=False)) as mb:
        fut = mb.submit(xte[0])
        with pytest.raises(RuntimeError, match="kernel exploded"):
            fut.result(timeout=60)
        # the worker survives a failing batch
        fut2 = mb.submit(xte[1])
        with pytest.raises(RuntimeError, match="kernel exploded"):
            fut2.result(timeout=60)


def test_microbatcher_close_drains_and_rejects(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    mb = MicroBatcher(artifacts["tree"].predict, BatchingPolicy(max_batch=8))
    futs = [mb.submit(xte[i]) for i in range(20)]
    mb.close()
    for f in futs:
        assert f.result(timeout=60).shape == (1,)
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit(xte[0])
    mb.close()  # idempotent


# ---------------------------------------------------------------------------
# ModelRouter / InferenceService
# ---------------------------------------------------------------------------
def test_router_stats_surface(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    router = ModelRouter()
    router.register("a", artifacts["tree"])
    router.register("b", artifacts["mlp"])
    try:
        with pytest.raises(KeyError, match="already registered"):
            router.register("a", artifacts["tree"])
        with pytest.raises(KeyError, match="no endpoint"):
            router.predict("missing", xte[:1])
        assert router.names() == ["a", "b"]
        router.predict("a", xte[:10])
        router.predict("a", xte[:3])
        snap = router.stats()["a"]
        assert snap["requests"] == 2
        assert snap["rows"] == 13
        assert snap["batches"] >= 1
        assert snap["qps"] > 0
        assert snap["p95_ms"] >= snap["p50_ms"] >= 0
        assert 0 < snap["batch_fill"] <= 1
        assert router.stats()["b"]["requests"] == 0
    finally:
        router.close()


def test_endpoint_predict_chunks_oversize_blocks(artifacts, blobs_module):
    """The sync predict path splits row blocks larger than max_batch across
    submissions instead of rejecting them (README contract)."""
    _, _, xte, _, _ = blobs_module
    art = artifacts["tree"]
    svc = InferenceService()
    svc.register("t", artifact=art, policy=BatchingPolicy(max_batch=32))
    try:
        got = svc.predict("t", xte[:100])  # 100 rows > max_batch 32
        np.testing.assert_array_equal(got, art.predict(xte[:100]))
    finally:
        svc.close()


def test_service_register_validation(trained):
    svc = InferenceService()
    try:
        with pytest.raises(TypeError, match="either model"):
            svc.register("x")
    finally:
        svc.close()


def test_service_concurrent_producers(artifacts, blobs_module):
    """Submissions racing from several threads all resolve correctly."""
    _, _, xte, _, _ = blobs_module
    art = artifacts["tree"]
    want = art.predict(xte[:96])
    svc = InferenceService()
    svc.register("t", artifact=art,
                 policy=BatchingPolicy(max_batch=32, max_wait_ms=5))
    results = {}

    def producer(lo, hi):
        futs = [(i, svc.submit("t", xte[i])) for i in range(lo, hi)]
        for i, f in futs:
            results[i] = f.result(timeout=60)[0]

    try:
        threads = [threading.Thread(target=producer, args=(lo, lo + 24))
                   for lo in range(0, 96, 24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = np.array([results[i] for i in range(96)])
        np.testing.assert_array_equal(got, want)
    finally:
        svc.close()


def test_fixed_batch_artifact_is_clamped(trained, blobs_module):
    """A fixed-batch artifact's ceiling caps the scheduler's buckets, so the
    scheduler never submits a batch the artifact would reject."""
    _, _, xte, _, _ = blobs_module
    art = compile(trained["mlp"], Target(number_format="fxp16",
                                         batch_policy="fixed", batch_size=16))
    assert art.max_supported_batch == 16
    svc = InferenceService()
    ep = svc.register("fixed", artifact=art,
                      policy=BatchingPolicy(max_batch=64))
    try:
        assert ep.policy.max_batch == 16
        futs = [svc.submit("fixed", xte[i]) for i in range(40)]
        got = np.array([f.result(timeout=60)[0] for f in futs])
        want = compile(trained["mlp"],
                       Target(number_format="fxp16")).predict(xte[:40])
        np.testing.assert_array_equal(got, want)
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# ArtifactCache + fingerprinting
# ---------------------------------------------------------------------------
def test_fingerprint_is_content_keyed(trained):
    a = compile(trained["tree"], Target(number_format="fxp16"))
    b = compile(trained["tree"], Target(number_format="fxp16", backend="xla"))
    assert a.fingerprint and a.fingerprint == b.fingerprint
    assert a.cache_key != b.cache_key  # Target differs
    c = compile(trained["mlp"], Target(number_format="fxp16"))
    assert c.fingerprint != a.fingerprint


def test_cache_dedupes_recompiles(trained):
    cache = ArtifactCache()
    t = Target(number_format="fxp16", backend="xla")
    a = cache.get_or_compile(trained["tree"], t)
    b = cache.get_or_compile(trained["tree"], t)
    assert a is b
    assert cache.stats() == {"entries": 1, "hits": 1, "misses": 1,
                             "capacity": None}
    c = cache.get_or_compile(trained["tree"], t.replace(number_format="fxp32"))
    assert c is not a
    assert cache.stats()["entries"] == 2


def test_cache_lru_eviction(trained):
    cache = ArtifactCache(capacity=2)
    t = Target(number_format="fxp16")
    a = cache.get_or_compile(trained["tree"], t)
    cache.get_or_compile(trained["mlp"], t)
    cache.get_or_compile(trained["tree"], t)  # refresh tree
    cache.get_or_compile(trained["logistic"], t)  # evicts mlp
    assert len(cache) == 2
    assert cache.get_or_compile(trained["tree"], t) is a  # still cached
    cache.get_or_compile(trained["mlp"], t)  # recompiles: it was evicted
    assert cache.stats()["misses"] == 4  # tree, mlp, logistic, mlp-again


def test_service_shares_cache_across_endpoints(trained):
    svc = InferenceService()
    try:
        t = Target(number_format="fxp16", backend="xla")
        ep1 = svc.register("main", trained["tree"], t)
        ep2 = svc.register("canary", trained["tree"], t)
        assert ep1.artifact is ep2.artifact
        assert svc.stats()["_cache"]["hits"] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# batch invariance (the property that makes micro-batch padding sound)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 48), i=st.integers(0, 199), seed=st.integers(0, 2**31 - 1))
def test_batch_invariance(artifacts, blobs_module, kind, n, i, seed):
    """A row's prediction must not depend on its batch context: batch of 1 ==
    member of a random batch == zero-padded to a bucket."""
    _, _, xte, _, _ = blobs_module
    art = artifacts[kind]
    rng = np.random.RandomState(seed)
    rows = xte[rng.randint(0, xte.shape[0], n)]
    pos = int(rng.randint(0, n))
    rows[pos] = xte[i % xte.shape[0]]

    alone = art.predict(rows[pos:pos + 1])[0]
    batched = art.predict(rows)[pos]
    bucket = BatchingPolicy(max_batch=64).bucket_for(n)
    padded = np.concatenate(
        [rows, np.zeros((bucket - n,) + rows.shape[1:], rows.dtype)])
    in_bucket = art.predict(padded)[pos]
    assert alone == batched == in_bucket


@pytest.mark.parametrize("kind", KINDS)
def test_batch_invariance_through_scheduler(artifacts, blobs_module, kind):
    """Scheduler micro-batching returns exactly the batch-1 predictions."""
    _, _, xte, _, _ = blobs_module
    art = artifacts[kind]
    want = art.predict(xte[:64])
    with MicroBatcher(art.predict,
                      BatchingPolicy(max_batch=16, max_wait_ms=5)) as mb:
        futs = [mb.submit(xte[i]) for i in range(64)]
        got = np.array([f.result(timeout=120)[0] for f in futs])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# ragged pallas batches (regression: b % block_batch == 0 hard assert)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("batch", [1, 3, 37, 130, 257])
def test_pallas_tree_artifact_ragged_batch(trained, blobs_module, batch):
    _, _, xte, _, _ = blobs_module
    rows = np.resize(xte, (batch, xte.shape[1]))
    ref = compile(trained["tree"], Target(number_format="fxp16")).predict(rows)
    pal = compile(trained["tree"], Target(number_format="fxp16",
                                          backend="pallas")).predict(rows)
    np.testing.assert_array_equal(ref, pal)


# ---------------------------------------------------------------------------
# stats edge cases: endpoints with fewer than 2 completed requests must
# report well-defined percentiles and batch fill, not artifacts of
# percentile-interpolating or dividing near-empty histories.
# ---------------------------------------------------------------------------
def test_stats_idle_endpoint_is_well_defined():
    import warnings

    from repro.serve.router import EndpointStats

    stats = EndpointStats()
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no RuntimeWarnings from numpy
        snap = stats.snapshot()
    assert snap["requests"] == 0 and snap["batches"] == 0
    assert snap["p50_ms"] == 0.0 and snap["p95_ms"] == 0.0
    assert snap["batch_fill"] == 1.0  # no padding wasted yet, not "0% full"
    assert snap["mean_batch_rows"] == 0.0
    assert all(np.isfinite(v) for v in snap.values())


def test_stats_single_request_reports_its_latency(artifacts, blobs_module):
    """With one completed request, p50 == p95 == that request's latency
    (there is nothing to interpolate between)."""
    import warnings

    _, _, xte, _, _ = blobs_module
    svc = InferenceService()
    svc.register("one", artifact=artifacts["tree"])
    try:
        svc.predict("one", xte[0])
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            snap = svc.stats()["one"]
    finally:
        svc.close()
    assert snap["requests"] == 1
    assert snap["p50_ms"] == snap["p95_ms"] > 0.0
    assert 0 < snap["batch_fill"] <= 1.0
    assert all(np.isfinite(v) for v in snap.values())


def test_stats_two_requests_percentiles_ordered(artifacts, blobs_module):
    _, _, xte, _, _ = blobs_module
    svc = InferenceService()
    svc.register("two", artifact=artifacts["tree"])
    try:
        svc.predict("two", xte[0])
        svc.predict("two", xte[1])
        snap = svc.stats()["two"]
    finally:
        svc.close()
    assert snap["requests"] == 2
    assert snap["p99_ms"] >= snap["p95_ms"] >= snap["p50_ms"] > 0.0


def test_small_sample_percentiles_are_nearest_rank():
    """Below 3 samples every percentile is an OBSERVED latency: no
    interpolation manufacturing values between (or past) real requests."""
    from repro.serve.router import _percentiles

    assert _percentiles(np.array([])) == [0.0, 0.0, 0.0]
    assert _percentiles(np.array([7.0])) == [7.0, 7.0, 7.0]
    two = np.array([1.0, 9.0])
    assert _percentiles(two) == [1.0, 9.0, 9.0]
    # the tail percentiles report the window max, never past it
    assert max(_percentiles(two)) == 9.0
    # >= 3 samples: the interpolating percentile path
    assert _percentiles(np.array([1.0, 2.0, 3.0]), (50,)) == [2.0]


def test_stats_p99_tracks_tail_latency():
    from repro.serve.router import EndpointStats

    stats = EndpointStats()
    # 99 fast requests + 1 slow one: p99 must see the tail, p50 must not
    for latency_s in [0.001] * 99 + [1.0]:
        stats.record_batch(1, 1, 1, [latency_s])
    snap = stats.snapshot()
    assert snap["p50_ms"] == pytest.approx(1.0)
    assert snap["p99_ms"] > 5.0 > snap["p95_ms"]
    assert snap["degraded_batches"] == 0
    assert snap["degraded_fraction"] == 0.0


def test_stats_degraded_batch_accounting():
    from repro.serve.router import EndpointStats

    stats = EndpointStats()
    stats.record_batch(2, 2, 2, [0.01, 0.01],
                       meta={"degraded": False, "number_format": "auto16"})
    stats.record_batch(2, 6, 8, [0.01, 0.01],
                       meta={"degraded": True, "number_format": "auto8"})
    snap = stats.snapshot()
    assert snap["degraded_batches"] == 1
    assert snap["degraded_rows"] == 6
    assert snap["degraded_fraction"] == pytest.approx(6 / 8)


# ---------------------------------------------------------------------------
# launch/serve.py CLI smoke test (previously untested)
# ---------------------------------------------------------------------------
def test_serve_cli_smoke(capsys):
    from repro.launch import serve as serve_cli

    serve_cli.main(["--arch", "qwen2-0.5b", "--batch", "2", "--tokens", "3",
                    "--stats"])
    out = capsys.readouterr().out
    assert "ms/token" in out
    assert "endpoint qwen2-0.5b" in out


def test_serve_cli_classifier_mode(capsys):
    from repro.launch import serve as serve_cli

    serve_cli.main(["--classifier", "tree", "--requests", "64", "--stats"])
    out = capsys.readouterr().out
    assert "rows/s" in out and "replicas=1" in out


def test_serve_cli_rejects_ambiguous_mode():
    from repro.launch import serve as serve_cli

    with pytest.raises(SystemExit):
        serve_cli.main(["--arch", "qwen2-0.5b", "--classifier", "tree"])
    with pytest.raises(SystemExit):
        serve_cli.main([])
