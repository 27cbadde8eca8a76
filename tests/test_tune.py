"""Block-size autotuner (kernels/tune.py) + the ops.py caching satellites:
cache hits (memory + disk), pow2 batch bucketing, padding-waste bounds, the
tree jit-cache bucketing fix, and the packed-tree weak cache."""

import gc
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, tune
from repro.models.decision_tree import train_decision_tree


@pytest.fixture()
def isolated_cache(tmp_path, monkeypatch):
    """Point the tuner at a private disk cache and start cold."""
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv("REPRO_TUNE_CACHE", path)
    tune.clear_memory_cache()
    yield path
    tune.clear_memory_cache()


# ---------------------------------------------------------------------------
# shape bucketing
# ---------------------------------------------------------------------------
def test_pow2ceil():
    assert [tune.pow2ceil(n) for n in (1, 2, 3, 5, 8, 9, 100)] == \
        [1, 2, 4, 8, 8, 16, 128]


def test_batch_bucket_matches_serve_ladder():
    from repro.serve import BatchingPolicy
    policy = BatchingPolicy(max_batch=256)
    for b in (1, 2, 3, 5, 17, 64, 100, 200, 256):
        assert tune.batch_bucket(b, cap=256) == policy.bucket_for(b)
    assert tune.batch_bucket(1000, cap=256) == 256  # capped


def test_pwl_blocks_sized_to_input():
    # The historical fixed grid padded *everything* to 256*512 = 131072
    # elements; a batch-1 MLP hidden activation (~16 values) must now pad to
    # at most one 128-lane row.
    rows, cols = tune.pwl_blocks(16)
    assert rows * cols == 128
    # and the padded grid never exceeds ~2x the input (+ one lane row).
    for n in (1, 100, 512, 4095, 4096, 10_000, 131_072, 1_000_000):
        rows, cols = tune.pwl_blocks(n)
        n_rows = -(-n // cols)
        padded = -(-n_rows // rows) * rows * cols
        assert padded >= n
        assert padded <= 2 * n + 128 * 512


def test_pwl_activation_waste_regression():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 16).astype(np.float32))
    got = np.asarray(ops.pwl_activation(x, "pwl4"))
    from repro.kernels import ref as R
    np.testing.assert_allclose(got, np.asarray(R.pwl_activation_ref(x, "pwl4")),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# autotuner cache behavior
# ---------------------------------------------------------------------------
def test_matmul_blocks_memory_cache_hit(isolated_cache, monkeypatch):
    calls = []
    real_choose = tune._choose

    def counting_choose(*args, **kwargs):
        calls.append(args)
        return real_choose(*args, **kwargs)

    monkeypatch.setattr(tune, "_choose", counting_choose)
    first = tune.matmul_blocks("layer", 64, 256, 32, 16)
    again = tune.matmul_blocks("layer", 64, 256, 32, 16)
    assert first == again
    assert len(calls) == 1  # second lookup is a pure cache hit


def test_matmul_blocks_pow2_bucket_shares_entry(isolated_cache, monkeypatch):
    calls = []
    real_choose = tune._choose
    monkeypatch.setattr(tune, "_choose",
                        lambda *a, **k: (calls.append(a), real_choose(*a, **k))[1])
    # 5, 6, 8 all land in the M=8 bucket: one tuning, one cache entry.
    blocks = {tune.matmul_blocks("layer", m, 128, 16, 16) for m in (5, 6, 8)}
    assert len(blocks) == 1
    assert len(calls) == 1
    # a different bucket tunes separately
    tune.matmul_blocks("layer", 64, 128, 16, 16)
    assert len(calls) == 2


def test_matmul_blocks_disk_persistence(isolated_cache, monkeypatch):
    path = isolated_cache
    blocks = tune.matmul_blocks("qmatmul", 128, 300, 64, 16)
    with open(path) as f:
        raw = json.load(f)
    assert list(raw.values()) == [list(blocks)]
    # A fresh process (simulated: cold memory) must serve the persisted
    # entry without re-tuning.
    tune.clear_memory_cache()
    monkeypatch.setattr(tune, "_choose",
                        lambda *a, **k: pytest.fail("retuned despite disk cache"))
    assert tune.matmul_blocks("qmatmul", 128, 300, 64, 16) == blocks


def test_disk_cache_save_unions_with_other_writers(isolated_cache):
    # This process loads the (empty) cache and tunes key A; a sibling
    # process then persists a foreign key; tuning key B here must re-merge
    # at save time — union on disk, not last-writer-wins clobbering.
    tune.matmul_blocks("qmatmul", 32, 64, 8, 16)
    with open(isolated_cache) as f:
        after_a = json.load(f)
    foreign_key = "layer|8x16x4|w16|other-device"
    after_a[foreign_key] = [8, 4, 16]
    with open(isolated_cache, "w") as f:
        json.dump(after_a, f)
    tune.matmul_blocks("layer", 64, 128, 32, 16)  # triggers another save
    with open(isolated_cache) as f:
        raw = json.load(f)
    assert foreign_key in raw
    assert len(raw) == 3


def test_corrupt_disk_cache_is_ignored(isolated_cache):
    with open(isolated_cache, "w") as f:
        f.write("{not json")
    tune.clear_memory_cache()
    bm, bn, bk = tune.matmul_blocks("layer", 8, 16, 4, 16)  # must not raise
    assert bm >= 1 and bn >= 1 and bk >= 1


def test_candidates_respect_vmem_and_bounds():
    for on_tpu in (False, True):
        cands = tune.candidates(64, 300, 40, 16, on_tpu)
        assert cands
        for bm, bn, bk in cands:
            assert (bm * bk + bk * bn) * 2 + bm * bn * 6 <= 8 * 1024 * 1024
            assert bm <= 128 and bn <= 256 and bk <= 512
            if on_tpu:  # Mosaic tiling floors for int16
                assert bm >= 16 and bn >= 128 and bk >= 128


def test_tuned_blocks_shrink_small_problems(isolated_cache):
    # The whole point: a batch-8 x 16 -> 32 layer must not tune to the
    # historical 128x256x128 padding (off-TPU cost model minimizes waste).
    bm, bn, bk = tune.matmul_blocks("layer", 8, 16, 32, 16)
    assert bm <= 8 and bk <= 16 and bn <= 32


# ---------------------------------------------------------------------------
# tree kernel: pow2-bucketed block_batch -> bounded jit trace set
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_tree():
    rng = np.random.RandomState(0)
    xt = rng.randn(400, 8).astype(np.float32)
    yt = (xt[:, 0] > 0).astype(np.int32) + (xt[:, 2] > 0.3).astype(np.int32)
    return train_decision_tree(xt, yt, 3, max_depth=6)


def test_tree_predict_bucketed_batches_share_trace(small_tree):
    from repro.kernels.tree_ensemble import tree_ensemble_pallas

    rng = np.random.RandomState(1)
    base = tree_ensemble_pallas._cache_size()
    # Warm the 8-bucket, then every batch in (5..8] must reuse its trace.
    ops.tree_predict(small_tree.tree, jnp.asarray(rng.randn(8, 8), jnp.float32))
    warm = tree_ensemble_pallas._cache_size()
    assert warm >= base
    for b in (5, 6, 7, 8):
        got = np.asarray(ops.tree_predict(
            small_tree.tree, jnp.asarray(rng.randn(b, 8), jnp.float32)))
        assert got.shape == (b,)
    assert tree_ensemble_pallas._cache_size() == warm  # no per-B recompiles


def test_tree_predict_correct_across_buckets(small_tree):
    from repro.kernels import ref as R

    rng = np.random.RandomState(2)
    for b in (1, 3, 8, 37, 100, 300):
        x = jnp.asarray(rng.randn(b, 8).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(ops.tree_predict(small_tree.tree, x)),
            np.asarray(R.tree_ensemble_ref(small_tree.tree, x)))


# ---------------------------------------------------------------------------
# packed-tree cache: no mutation, reuse, weak eviction
# ---------------------------------------------------------------------------
def test_packed_tree_cache_does_not_mutate_model(small_tree):
    x = jnp.asarray(np.random.RandomState(3).randn(4, 8), jnp.float32)
    ops.tree_predict(small_tree.tree, x)
    assert not hasattr(small_tree.tree, "_packed_kernel")


def test_packed_tree_cache_reuses_operands(small_tree):
    first = ops._packed_operands(small_tree.tree)
    second = ops._packed_operands(small_tree.tree)
    assert all(a is b for a, b in zip(first, second))


def test_packed_tree_cache_survives_trace_first_call(small_tree):
    """Regression: when the FIRST tree_predict for a model happens inside a
    jit/shard_map trace (mesh-specialized artifacts do this), the cache must
    not capture tracers — later calls under new traces used to die with
    UnexpectedTracerError."""
    import jax

    ops._PACKED_TREES.pop(id(small_tree.tree), None)  # force a cold cache
    rng = np.random.RandomState(5)
    jitted = jax.jit(lambda x: ops.tree_predict(small_tree.tree, x))
    first = np.asarray(jitted(jnp.asarray(rng.randn(4, 8), jnp.float32)))
    # a different batch shape forces a second, fresh trace over the cache
    second = np.asarray(ops.tree_predict(
        small_tree.tree, jnp.asarray(rng.randn(16, 8), jnp.float32)))
    assert first.shape == (4,) and second.shape == (16,)
    # and the eager path memoizes device-resident operands (no per-dispatch
    # host-to-device upload of the packed tree)
    entry = ops._PACKED_TREES[id(small_tree.tree)][1]
    assert "dev" in entry


def test_packed_tree_cache_evicts_on_gc():
    rng = np.random.RandomState(4)
    xt = rng.randn(200, 5).astype(np.float32)
    model = train_decision_tree(xt, (xt[:, 0] > 0).astype(np.int32), 2,
                                max_depth=3)
    tree = model.tree
    ops._packed_operands(tree)
    key = id(tree)
    assert key in ops._PACKED_TREES
    del model, tree
    gc.collect()
    assert key not in ops._PACKED_TREES


# ---------------------------------------------------------------------------
# artifact pretune fills the caches
# ---------------------------------------------------------------------------
def test_artifact_pretune_populates_tune_cache(isolated_cache, blobs):
    from repro.compile import Target, compile
    from repro.models import train_mlp

    xtr, ytr, xte, _, c = blobs
    model = train_mlp(xtr, ytr, c, hidden=(16,), epochs=3)
    art = compile(model, Target(number_format="fxp16", backend="pallas"))
    art.pretune(xte[0], batches=(1, 8))
    snap = tune.cache_snapshot()
    # the whole-model kernel's batch block, tuned once per bucket
    model_keys = [k for k in snap if k.startswith("model-mlp|")]
    assert len(model_keys) >= 2
    assert os.path.exists(isolated_cache)
    # and serving-sized predictions still agree with the reference backend
    ref = compile(model, Target(number_format="fxp16", backend="ref"))
    np.testing.assert_array_equal(art.predict(xte[:8]), ref.predict(xte[:8]))
