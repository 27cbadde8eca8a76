"""Sharding rules + roofline machinery tests (run on a tiny host mesh)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.roofline.analysis import collective_bytes_from_hlo  # noqa: E402
from repro.sharding.rules import Rules  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    # single real CPU device: 1x1 mesh still exercises the rule logic
    from repro.launch.mesh import make_mesh

    return make_mesh((1, 1), ("data", "model"))


def test_divisibility_guard(mesh):
    r = Rules(mesh)
    # axis size 1 divides everything -> always resolves
    assert r.resolve("model", 16) == "model"
    assert r.resolve("batch", 8) in ("data", ("data",))


def test_spec_shapes(mesh):
    r = Rules(mesh)
    spec = r.spec(("batch", None, "model"), (8, 4, 16))
    assert isinstance(spec, P) and len(spec) == 3


def test_unknown_logical_raises(mesh):
    with pytest.raises(KeyError):
        Rules(mesh).resolve("bogus", 8)


class FakeMesh:
    """Minimal mesh stand-in to test non-divisible fallback without devices."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_non_divisible_falls_back_replicated():
    r = Rules(FakeMesh({"data": 16, "model": 16}))
    assert r.resolve("model", 14) is None  # qwen2's 14 heads
    assert r.resolve("model", 32) == "model"
    assert r.resolve("batch", 256) == "data"  # single DP axis -> plain name
    assert r.resolve("batch", 250) is None


def test_multipod_batch_axes():
    r = Rules(FakeMesh({"pod": 2, "data": 16, "model": 16}))
    assert r.resolve("batch", 256) == ("pod", "data")
    assert r.resolve("batch", 16) == "data"  # not divisible by 32 -> in-pod
    assert r.resolve("expert", 256) == ("data", "model")
    assert r.resolve("expert", 8) is None or r.resolve("expert", 8) != "model"


# ---------------------------------------------------------------------------
# collective-bytes HLO parser
# ---------------------------------------------------------------------------
def test_collective_parser_counts_shapes():
    hlo = """
  %ar = bf16[16,1024] all-reduce(bf16[16,1024] %x), replica_groups={}
  %ag.1 = f32[512]{0} all-gather(f32[128]{0} %y), dimensions={0}
  %noise = f32[2,2] add(f32[2,2] %a, f32[2,2] %b)
  %rs = (s8[64,64], s8[64,64]) reduce-scatter(...), dimensions={0}
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-reduce"] == 16 * 1024 * 2
    assert out["all-gather"] == 512 * 4
    assert out["reduce-scatter"] == 2 * 64 * 64
    assert out["total"] == out["all-reduce"] + out["all-gather"] + out["reduce-scatter"]


def test_collective_parser_ignores_non_collectives():
    hlo = "%m = f32[128,128] dot(f32[128,128] %a, f32[128,128] %b)"
    assert collective_bytes_from_hlo(hlo)["total"] == 0


# ---------------------------------------------------------------------------
# analytic model cross-check vs HLO on an unscanned config
# ---------------------------------------------------------------------------
def test_analytic_flops_cross_check_unscanned():
    """On a no-remat 1-layer model (nothing scanned over layers), analytic
    forward FLOPs should land within ~40% of XLA's count."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.lm import model as M
    from repro.roofline.analytic import analytic_cost

    cfg = dataclasses.replace(
        get_config("qwen2-0.5b").reduced(), n_layers=1, remat=False,
        vocab_size=512, attn_chunk=4096)
    B, S = 2, 128
    shape = ShapeSpec("probe", S, B, "prefill")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((B, S), jnp.int32)}
    compiled = jax.jit(lambda p, b: M.forward(p, b, cfg)).lower(params, batch).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jaxlibs return [dict], newer a dict
        ca = ca[0]
    hlo_flops = ca["flops"]
    an = analytic_cost(cfg, shape, chips=1, tp=1, dp_in_pod=1, microbatches=1)
    ratio = an.detail["flops_fwd"] / hlo_flops
    assert 0.6 < ratio < 1.4, f"analytic/hlo = {ratio}"
