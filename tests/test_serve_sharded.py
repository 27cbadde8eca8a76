"""Multi-device data-parallel serving: replica-aware artifacts + scheduler.

Runs meaningfully at any device count: mesh size 1 everywhere (the
degenerate mesh must behave exactly like single-device serving), larger
sizes when the host has the devices (the CI job runs the whole file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Golden-anchored
bit-identity for sharded predictions lives in ``test_golden_vectors.py``.
"""

import numpy as np
import pytest

import jax

from repro.compile import Target, compile
from repro.kernels import tune
from repro.serve import ArtifactCache, BatchingPolicy, InferenceService
from repro.sharding import rules as shrules

NDEV = jax.device_count()


def needs_devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})")


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
    from repro.models import train_decision_tree, train_mlp

    xtr, ytr, _, _, c = blobs_module
    return {
        "tree": train_decision_tree(xtr, ytr, c, max_depth=6),
        "mlp": train_mlp(xtr, ytr, c, hidden=(16,), epochs=10),
    }


# ---------------------------------------------------------------------------
# sharding rules: the serving-mesh helpers
# ---------------------------------------------------------------------------
def test_make_serving_mesh_shape():
    mesh = shrules.make_serving_mesh()
    assert mesh.axis_names == ("data",)
    assert shrules.dp_size(mesh) == NDEV
    assert shrules.batch_spec(mesh) == jax.sharding.PartitionSpec("data")
    with pytest.raises(ValueError, match="only"):
        shrules.make_serving_mesh(NDEV + 1)


def test_dp_size_counts_batch_axes_only():
    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape
            self.axis_names = tuple(shape)

    assert shrules.dp_size(FakeMesh({"data": 4, "model": 2})) == 4
    assert shrules.dp_size(FakeMesh({"pod": 2, "data": 4, "model": 2})) == 8
    assert shrules.dp_size(FakeMesh({"model": 4})) == 1


def test_replica_bucket_padding():
    # (n, replicas) -> (pow2 per-replica shard, total)
    assert shrules.replica_bucket(1, 1) == (1, 1)
    assert shrules.replica_bucket(5, 1) == (8, 8)
    assert shrules.replica_bucket(8, 8) == (1, 8)
    assert shrules.replica_bucket(9, 8) == (2, 16)
    assert shrules.replica_bucket(100, 8) == (16, 128)
    assert shrules.replica_bucket(3, 8) == (1, 8)  # n < replicas
    assert shrules.replica_bucket(512, 8) == (64, 512)


def test_is_host_emulated():
    assert shrules.is_host_emulated(shrules.make_serving_mesh()) == \
        (jax.devices()[0].platform == "cpu")


# ---------------------------------------------------------------------------
# replica-aware BatchingPolicy
# ---------------------------------------------------------------------------
def test_replica_bucket_ladder():
    p = BatchingPolicy(max_batch=64, replicas=8)
    assert p.buckets() == (8, 16, 32, 64)
    assert p.bucket_for(1) == 8
    assert p.bucket_for(9) == 16
    assert p.bucket_for(64) == 64
    # replicas=1 keeps the historical ladder exactly
    assert BatchingPolicy(max_batch=64).buckets() == (1, 2, 4, 8, 16, 32, 64)
    # replicas above max_batch degrade to the cap (predict pads internally)
    assert BatchingPolicy(max_batch=4, replicas=8).buckets() == (4,)
    with pytest.raises(ValueError):
        BatchingPolicy(replicas=0)


def test_with_replicas_and_clamp_compose():
    p = BatchingPolicy(max_batch=256).clamped(64).with_replicas(8)
    assert p.max_batch == 64 and p.replicas == 8
    assert p.with_replicas(8) is p  # no-op fast path


def test_with_replicas_aligns_top_bucket():
    """Non-pow2 replica counts: the top bucket rounds up to replicas x pow2
    so a full dispatch is never silently re-padded inside the artifact."""
    p = BatchingPolicy(max_batch=64).with_replicas(6)
    assert p.max_batch == 96  # 6 * pow2ceil(ceil(64/6)) = 6 * 16
    assert p.buckets() == (6, 12, 24, 48, 96)
    for bucket in p.buckets():
        assert shrules.replica_bucket(bucket, 6)[1] == bucket
    # fixed-ceiling callers opt out: the cap must never be exceeded
    q = BatchingPolicy(max_batch=72).with_replicas(6, align_top=False)
    assert q.max_batch == 72


def test_specialize_mesh_rejects_respecialization(trained):
    art = compile(trained["tree"], Target(number_format="fxp16"))
    sharded = art.specialize_mesh(shrules.make_serving_mesh(1))
    with pytest.raises(ValueError, match="already specialized"):
        sharded.specialize_mesh(shrules.make_serving_mesh(1))


# ---------------------------------------------------------------------------
# specialize_mesh semantics
# ---------------------------------------------------------------------------
def test_specialize_mesh_degenerate_single_device(trained, blobs_module):
    """A 1-replica mesh artifact predicts exactly like the plain artifact."""
    _, _, xte, _, _ = blobs_module
    art = compile(trained["tree"], Target(number_format="fxp16", backend="xla"))
    mesh = shrules.make_serving_mesh(1)
    for strategy in ("fused", "spmd"):
        sharded = art.specialize_mesh(mesh, strategy)
        assert sharded.replicas == 1
        assert sharded.mesh_strategy == strategy
        np.testing.assert_array_equal(sharded.predict(xte), art.predict(xte))


def test_specialize_mesh_strategies_agree(trained, blobs_module):
    """fused and spmd produce identical bytes on whatever mesh exists."""
    _, _, xte, _, _ = blobs_module
    mesh = shrules.make_serving_mesh()
    for kind in ("tree", "mlp"):
        art = compile(trained[kind], Target(number_format="fxp16",
                                            backend="xla"))
        fused = art.specialize_mesh(mesh, "fused")
        spmd = art.specialize_mesh(mesh, "spmd")
        np.testing.assert_array_equal(fused.predict(xte[:97]),
                                      spmd.predict(xte[:97]))


def test_specialize_mesh_stats_exclude_padding(trained, blobs_module):
    """predict_with_stats on ragged batches must not leak phantom pad-row
    overflow/underflow counts (same contract as the fixed-batch wrapper)."""
    _, _, xte, _, _ = blobs_module
    art = compile(trained["mlp"], Target(number_format="fxp16", backend="xla"))
    sharded = art.specialize_mesh(shrules.make_serving_mesh())
    for n in (1, 7, 33):
        _, want = art.predict_with_stats(xte[:n])
        _, got = sharded.predict_with_stats(xte[:n])
        assert got == want, f"n={n}: {got} != {want}"


def test_specialize_mesh_rejects_lm():
    from golden import regenerate as G

    art = compile(G.make_lm_model(), Target())
    with pytest.raises(TypeError, match="classifier"):
        art.specialize_mesh(shrules.make_serving_mesh(1))


def test_specialize_mesh_rejects_unknown_strategy(trained):
    art = compile(trained["tree"], Target())
    with pytest.raises(ValueError, match="strategy"):
        art.specialize_mesh(shrules.make_serving_mesh(1), "warp")


def test_fixed_batch_mesh_capacity_scales(trained, blobs_module):
    _, _, xte, _, _ = blobs_module
    art = compile(trained["mlp"], Target(number_format="fxp16",
                                         batch_policy="fixed", batch_size=8))
    mesh = shrules.make_serving_mesh()
    sharded = art.specialize_mesh(mesh)
    assert sharded.max_supported_batch == 8 * NDEV
    want = compile(trained["mlp"],
                   Target(number_format="fxp16")).predict(xte[:8 * NDEV])
    np.testing.assert_array_equal(sharded.predict(xte[:8 * NDEV]), want)
    with pytest.raises(ValueError, match="mesh capacity"):
        sharded.predict(xte[:8 * NDEV + 1])


def test_mesh_pretune_walks_replica_ladder(trained, blobs_module, tmp_path,
                                           monkeypatch):
    """pretune on a mesh artifact warms per-replica shard shapes: the tune
    cache gains device-keyed entries for the pow2 shard ladder."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc.json"))
    tune.clear_memory_cache()
    _, _, xte, _, _ = blobs_module
    art = compile(trained["mlp"], Target(number_format="fxp16",
                                         backend="pallas"))
    sharded = art.specialize_mesh(shrules.make_serving_mesh(), "fused")
    sharded.pretune(xte[0])
    snap = tune.cache_snapshot()
    model_keys = [k for k in snap if k.startswith("model-mlp|")]
    assert model_keys, "pretune populated no tuner entries"
    dev_key = tune.device_key()
    assert all(k.endswith(dev_key) for k in snap), (
        f"tune entries not device-keyed: {sorted(snap)}")
    tune.clear_memory_cache()


# ---------------------------------------------------------------------------
# service + scheduler integration
# ---------------------------------------------------------------------------
def test_service_mesh_endpoint_parity(trained, blobs_module):
    """Micro-batched traffic through a mesh endpoint returns byte-identical
    predictions to the plain artifact, across ragged request sizes."""
    _, _, xte, _, _ = blobs_module
    art = compile(trained["tree"], Target(number_format="fxp16", backend="xla"))
    want = art.predict(xte[:150])
    svc = InferenceService()
    try:
        ep = svc.register("t", trained["tree"],
                          Target(number_format="fxp16", backend="xla"),
                          mesh=shrules.make_serving_mesh(),
                          policy=BatchingPolicy(max_batch=32 * NDEV,
                                                max_wait_ms=5))
        assert ep.policy.replicas == NDEV
        futs, off = [], 0
        for size in (1, 3, 8, 5, 2) * 8:  # 152 rows in ragged requests
            if off + size > 150:
                break
            futs.append((off, size, svc.submit("t", xte[off:off + size])))
            off += size
        for o, s, f in futs:
            np.testing.assert_array_equal(f.result(timeout=120), want[o:o + s])
    finally:
        svc.close()


def test_cache_keys_mesh_and_single_separately(trained):
    cache = ArtifactCache()
    t = Target(number_format="fxp16", backend="xla")
    mesh = shrules.make_serving_mesh()
    single = cache.get_or_compile(trained["tree"], t)
    sharded = cache.get_or_compile(trained["tree"], t, mesh=mesh)
    assert single is not sharded
    assert cache.stats() == {"entries": 2, "hits": 0, "misses": 2,
                             "capacity": None}
    # same mesh layout again: a hit, not a recompile
    assert cache.get_or_compile(trained["tree"], t, mesh=mesh) is sharded
    assert cache.stats()["hits"] == 1
    assert single.mesh_key is None
    assert sharded.mesh_key is not None and sharded.cache_key != single.cache_key


def test_register_rejects_mismatched_mesh(trained):
    """A pre-specialized artifact registered with a *different* mesh/strategy
    must error loudly, not silently serve the wrong replica layout."""
    svc = InferenceService()
    try:
        art = compile(trained["tree"], Target(number_format="fxp16",
                                              backend="xla"))
        sharded = art.specialize_mesh(shrules.make_serving_mesh(1), "fused")
        with pytest.raises(ValueError, match="already specialized"):
            svc.register("x", artifact=sharded,
                         mesh=shrules.make_serving_mesh(1),
                         mesh_strategy="spmd")
        # a matching mesh is accepted as-is
        ep = svc.register("y", artifact=sharded,
                          mesh=shrules.make_serving_mesh(1),
                          mesh_strategy="fused")
        assert ep.artifact is sharded
    finally:
        svc.close()


def test_service_register_with_mesh_dedupes(trained):
    svc = InferenceService()
    try:
        t = Target(number_format="fxp16", backend="xla")
        mesh = shrules.make_serving_mesh()
        a = svc.register("main", trained["tree"], t, mesh=mesh)
        b = svc.register("canary", trained["tree"], t, mesh=mesh)
        assert a.artifact is b.artifact
        assert svc.stats()["_cache"]["hits"] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# multi-device-only coverage (the 8-device CI job)
# ---------------------------------------------------------------------------
@needs_devices(2)
def test_cache_distinguishes_disjoint_device_meshes(trained):
    """Two same-shape meshes over DISJOINT device sets (splitting a host's
    devices between endpoints) must not alias to one cached artifact — the
    second endpoint would silently serve on the first mesh's devices."""
    devs = jax.devices()
    m1 = shrules.make_serving_mesh(devices=devs[:1])
    m2 = shrules.make_serving_mesh(devices=devs[1:2])
    cache = ArtifactCache()
    t = Target(number_format="fxp16", backend="xla")
    a = cache.get_or_compile(trained["tree"], t, mesh=m1)
    b = cache.get_or_compile(trained["tree"], t, mesh=m2)
    assert a is not b and a.mesh_key != b.mesh_key
    assert cache.stats()["misses"] == 2


@needs_devices(2)
def test_multi_replica_scheduler_buckets(trained, blobs_module):
    """With R replicas every dispatched bucket is a multiple of R."""
    _, _, xte, _, _ = blobs_module
    art = compile(trained["tree"], Target(number_format="fxp16",
                                          backend="xla"))
    mesh = shrules.make_serving_mesh(2)
    buckets = []
    svc = InferenceService()
    try:
        ep = svc.register("t", artifact=art.specialize_mesh(mesh),
                          policy=BatchingPolicy(max_batch=64, max_wait_ms=5))
        assert ep.policy.replicas == 2  # derived from the artifact
        orig = ep.batcher._on_batch

        def spy(n_req, n_rows, bucket, lats, **kw):
            buckets.append(bucket)
            orig(n_req, n_rows, bucket, lats, **kw)

        ep.batcher._on_batch = spy
        futs = [svc.submit("t", xte[i]) for i in range(40)]
        for f in futs:
            f.result(timeout=120)
    finally:
        svc.close()
    assert buckets and all(b % 2 == 0 for b in buckets), buckets


@needs_devices(8)
def test_eight_device_mesh_end_to_end(trained, blobs_module):
    """The acceptance mesh: 8 replicas, both strategies, scheduler included."""
    _, _, xte, _, _ = blobs_module
    mesh = shrules.make_serving_mesh(8)
    for kind in ("tree", "mlp"):
        art = compile(trained[kind], Target(number_format="fxp16",
                                            backend="xla"))
        want = art.predict(xte)
        for strategy in ("fused", "spmd"):
            sharded = art.specialize_mesh(mesh, strategy)
            assert sharded.replicas == 8
            np.testing.assert_array_equal(sharded.predict(xte), want)
        svc = InferenceService()
        try:
            svc.register(kind, artifact=art.specialize_mesh(mesh),
                         policy=BatchingPolicy(max_batch=512))
            np.testing.assert_array_equal(svc.predict(kind, xte), want)
        finally:
            svc.close()
