"""The classifier path's kernels compile for a TPU v5e, at D6 widths.

Nothing here runs on a chip: the TPU compiler, installed with JAX, compiles
for a *described* ``v5e:2x2`` topology (one device of it).  That catches
what interpret mode cannot — Mosaic refusing an operand type, a block
shape or an op — at no chip time.  Every case goes through the ``ops``
wrapper the serving path calls, with :func:`repro.kernels.tune.on_tpu`
answering True, so the operands are lane-padded and blocked exactly as on
the chip; the compiled program must hold a Mosaic kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fixedpoint import FXP8, FXP16
from repro.kernels import fxp_model, ops, tune

# D6 (HAR): 561 features, 6 classes; the benchmark's MLP hidden width and
# kernel-SVM prototype count (benchmarks/common.py).
F, C, H, S = 561, 6, 64, 300
BUCKETS = (1, 8, 64, 256)
FORMATS = {8: FXP8, 16: FXP16}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def chip(monkeypatch, one_chip):
    """The kernel layer's platform decision answers "TPU"; returns a
    compile function for one described chip."""
    monkeypatch.setattr(tune, "on_tpu", lambda: True)

    def compile_for_chip(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel was compiled"

    return compile_for_chip


def _ints(shape, dtype):
    return jnp.zeros(shape, dtype)


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("bits", [8, 16])
def test_mlp_megakernel_compiles(chip, bits, bucket):
    fmt = FORMATS[bits]
    ws = (_ints((F, H), fmt.dtype), _ints((H, C), fmt.dtype))
    bs = (_ints((H,), fmt.dtype), _ints((C,), fmt.dtype))
    sched = ((fmt.frac_bits, fmt, "pwl4"), (fmt.frac_bits, fmt, "none"))
    dims = (F, H, C)
    bm = tune.model_block_m(
        "mlp", bucket, dims, bits,
        vmem_bytes=lambda b: fxp_model.mlp_vmem_bytes(dims, bits, b))
    assert fxp_model.mlp_fits_vmem(dims, bits, bm)
    chip(lambda x: ops.fxp_mlp_model(x, ws, bs, sched, bm=bm),
         ((bucket, F), fmt.dtype))


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("kind", ["poly", "rbf"])
def test_svm_megakernel_compiles(chip, kind, bits, bucket):
    fmt = FORMATS[bits]
    sv, dual = _ints((S, F), fmt.dtype), _ints((S, C), fmt.dtype)
    icept = _ints((C,), fmt.dtype)
    bm = tune.model_block_m(
        f"svm-{kind}", bucket, (F, S, C), bits,
        vmem_bytes=lambda b: fxp_model.svm_vmem_bytes(S, F, C, bits, b))
    assert fxp_model.svm_fits_vmem(S, F, C, bits, bm)
    chip(lambda x: ops.fxp_svm_model(x, sv, dual, icept, kind, fmt, fmt,
                                     3, 1, 3, fmt.frac_bits, bm=bm),
         ((bucket, F), fmt.dtype))


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("op", ["layer", "qmatmul"])
def test_layer_kernels_compile(chip, op, bits, bucket):
    """The fused layer (logistic, linear SVM, per-layer fallback) and the
    chained SVM's qmatmul, at the tuner's chip blocks."""
    fmt = FORMATS[bits]
    w, b = _ints((F, C), fmt.dtype), _ints((C,), fmt.dtype)
    blocks = tune.matmul_blocks(op, bucket, F, C, bits)
    if op == "layer":
        fn = functools.partial(ops.fxp_layer, w=w, bias=b, fmt=fmt,
                               activation="exact", blocks=blocks)
    else:
        fn = functools.partial(ops.fxp_qmatmul, b=w, fmt=fmt, blocks=blocks)
    chip(lambda a: fn(a), ((bucket, F), fmt.dtype))


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "mixed"])
def test_mlp_fleet_compiles(chip, uniform, bits, bucket):
    """8 stacked MLPs at D1 width (42 features): the uniform batched dot
    and the mixed-schedule ``lax.switch``, with (E, 1, N) bias blocks."""
    fmt, e, f1, c1 = FORMATS[bits], 8, 42, 2
    ws = (_ints((e, f1, H), fmt.dtype), _ints((e, H, c1), fmt.dtype))
    bs = (_ints((e, H), fmt.dtype), _ints((e, c1), fmt.dtype))
    base = ((fmt.frac_bits, fmt, "pwl4"), (fmt.frac_bits, fmt, "none"))
    other = ((fmt.frac_bits + 1, fmt, "exact"), (fmt.frac_bits, fmt, "none"))
    scheds = tuple(base if uniform or i % 2 else other for i in range(e))
    dims = (f1, H, c1)
    be, bm = tune.fleet_blocks(
        "mlp", e, bucket, dims, bits, uniform=uniform,
        vmem_bytes=lambda eb, b: fxp_model.mlp_fleet_vmem_bytes(
            eb, dims, bits, b))
    chip(lambda x: ops.fxp_mlp_fleet(x, ws, bs, scheds, be=be, bm=bm),
         ((e, bucket, f1), fmt.dtype))


@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "mixed"])
def test_svm_fleet_compiles(chip, uniform, bits, bucket):
    """8 stacked rbf SVMs at D6 widths: the batched decision function and
    the mixed-parameter ``lax.switch``, with (E, 1, C) intercept blocks."""
    fmt, e = FORMATS[bits], 8
    sv, dual = _ints((e, S, F), fmt.dtype), _ints((e, S, C), fmt.dtype)
    icept = _ints((e, C), fmt.dtype)
    base = (fmt, fmt, 3, 1, 3, fmt.frac_bits)
    other = (fmt, fmt, 5, 1, 3, fmt.frac_bits)
    params = tuple(base if uniform or i % 2 else other for i in range(e))
    be, bm = tune.fleet_blocks(
        "svm-rbf", e, bucket, (F, S, C), bits, uniform=uniform,
        vmem_bytes=lambda eb, b: fxp_model.svm_fleet_vmem_bytes(
            eb, S, F, C, bits, b))
    chip(lambda x: ops.fxp_svm_fleet(x, sv, dual, icept, "rbf", params,
                                     be=be, bm=bm),
         ((e, bucket, F), fmt.dtype))


@pytest.mark.parametrize("bucket", BUCKETS)
def test_pwl_activation_compiles(chip, bucket):
    """The float MLP's PWL sigmoid (flt Targets on pallas)."""
    chip(lambda h: ops.pwl_activation(h, "pwl4"), ((bucket, H), jnp.float32))


@pytest.fixture(scope="module")
def d6_tree():
    """The benchmark's tree: D6, max_depth=12 (benchmarks/common.py)."""
    from repro.data import load_dataset
    from repro.models import train_decision_tree

    ds = load_dataset("D6")
    return train_decision_tree(ds.x_train, ds.y_train, ds.n_classes,
                               max_depth=12).tree


@pytest.mark.parametrize("bucket", BUCKETS)
def test_tree_kernel_compiles_at_depth_12(chip, d6_tree, bucket):
    from repro.kernels.tree_ensemble import tree_vmem_bytes

    sel, _, path, _, _ = ops._packed_operands(d6_tree)
    assert d6_tree.max_depth == 12 and sel.shape[0] == F
    # The path matrices sit whole in VMEM, inside the scoped budget.
    assert tree_vmem_bytes(F, path.shape[0], path.shape[1],
                           bucket) <= tune._VMEM_BUDGET
    chip(lambda x: ops.tree_predict(d6_tree, x), ((bucket, F), jnp.float32))


def test_tree_predictions_unchanged_by_packing(d6_tree):
    """The packed single-path-matrix form picks the same leaves as the
    reference on the D6 tree (interpret mode, on the CPU)."""
    from repro.data import load_dataset
    from repro.kernels import ref as R

    x = jnp.asarray(load_dataset("D6").x_test[:300])
    np.testing.assert_array_equal(np.asarray(ops.tree_predict(d6_tree, x)),
                                  np.asarray(R.tree_ensemble_ref(d6_tree, x)))
