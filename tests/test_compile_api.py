"""Tests for the unified ``repro.compile`` artifact API.

Covers the acceptance surface of the compiler redesign:

* keyword-form ``compile(model, number_format=...)`` == Target-form for all
  model kinds x number formats (x tree layouts) — bit-identical predictions;
* ``backend='xla'`` == ``backend='ref'``; ``backend='pallas'`` agrees on the
  tree and MLP fixed-point paths (interpret mode off-TPU);
* ``CompiledArtifact.save``/``load`` round-trips to identical predictions
  and memory reports;
* batch policy, Target validation, registry dispatch, and the ``lm``
  lowering (gate sigmoid threaded through the config, no module global).

(The ``repro.core.convert`` deprecation shim this file used to compare
against is deleted; ``tests/test_convert.py`` keeps the paper-level
pipeline assertions on the compile API.)
"""

import os

import numpy as np
import pytest

from repro.compile import (CompiledArtifact, Target, compile, load,
                           lowering_kinds, model_kind)
from repro.models import (train_decision_tree, train_kernel_svm,
                          train_linear_svm, train_logistic, train_mlp)


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


NAMES = ["tree", "logistic", "mlp", "svm-linear", "svm-rbf", "svm-poly"]


# ---------------------------------------------------------------------------
# keyword form == Target form for every kind x format (x layout)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["flt", "fxp32", "fxp16"])
@pytest.mark.parametrize("name", NAMES)
def test_keyword_form_equals_target_form(trained, blobs_module, name, fmt):
    """``compile(model, number_format=...)`` (the migration spelling of the
    deleted ``convert()`` shim) builds the identical artifact."""
    _, _, xte, _, _ = blobs_module
    model = trained[name]
    layouts = ("iterative", "ifelse", "oblivious") if name == "tree" else ("iterative",)
    for layout in layouts:
        kw = compile(model, number_format=fmt, tree_layout=layout)
        art = compile(model, Target(number_format=fmt, tree_layout=layout))
        np.testing.assert_array_equal(kw.predict(xte), art.predict(xte))
        assert kw.memory_bytes() == art.memory_report()
        assert kw.cache_key == art.cache_key


def test_target_and_kwargs_are_exclusive(trained):
    with pytest.raises(TypeError, match="not both"):
        compile(trained["logistic"], Target(), number_format="flt")


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["flt", "fxp32", "fxp16"])
@pytest.mark.parametrize("name", NAMES)
def test_xla_backend_matches_ref(trained, blobs_module, name, fmt):
    _, _, xte, _, _ = blobs_module
    ref = compile(trained[name], Target(number_format=fmt, backend="ref"))
    xla = compile(trained[name], Target(number_format=fmt, backend="xla"))
    np.testing.assert_array_equal(ref.predict(xte), xla.predict(xte))


@pytest.mark.parametrize("name,fmt", [
    ("tree", "fxp32"), ("tree", "fxp16"), ("tree", "flt"),
    ("mlp", "fxp16"), ("mlp", "fxp8"),
    ("logistic", "fxp16"),
])
def test_pallas_backend_agrees(trained, blobs_module, name, fmt):
    """Acceptance: pallas artifacts agree with ref on tree and MLP fxp paths
    (interpret mode executes the real kernel bodies off-TPU)."""
    _, _, xte, _, _ = blobs_module
    ref = compile(trained[name], Target(number_format=fmt, backend="ref"))
    pal = compile(trained[name], Target(number_format=fmt, backend="pallas"))
    agreement = (ref.predict(xte) == pal.predict(xte)).mean()
    assert agreement >= 0.99, f"{name}/{fmt}: pallas agreement {agreement}"


# ---------------------------------------------------------------------------
# save / load round trip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["tree", "mlp", "svm-rbf"])
def test_save_load_roundtrip(tmp_path, trained, blobs_module, name):
    _, _, xte, _, _ = blobs_module
    art = compile(trained[name], Target(number_format="fxp16", backend="xla",
                                        sigmoid="pwl4", tree_layout="ifelse"))
    path = os.path.join(tmp_path, f"{name}.embml")
    art.save(path, metadata={"note": "roundtrip"})
    art2 = load(path)
    assert isinstance(art2, CompiledArtifact)
    assert art2.kind == art.kind
    assert art2.target == art.target
    np.testing.assert_array_equal(art.predict(xte), art2.predict(xte))
    assert art.memory_report() == art2.memory_report()


def test_load_rejects_non_archive(tmp_path):
    from repro.train.checkpoint import save_pytree

    path = os.path.join(tmp_path, "not_artifact.ckpt")
    save_pytree(path, {"a": np.zeros(3)})
    with pytest.raises(ValueError, match="archive"):
        load(path)


# ---------------------------------------------------------------------------
# batch policy + validation + registry
# ---------------------------------------------------------------------------
def test_fixed_batch_policy_pads_and_rejects(trained, blobs_module):
    _, _, xte, _, _ = blobs_module
    dyn = compile(trained["mlp"], Target(number_format="fxp16"))
    fixed = compile(trained["mlp"], Target(number_format="fxp16",
                                           batch_policy="fixed", batch_size=64))
    np.testing.assert_array_equal(dyn.predict(xte[:10]), fixed.predict(xte[:10]))
    with pytest.raises(ValueError, match="fixed batch_size"):
        fixed.predict(xte[:100])


def test_fixed_batch_stats_exclude_padding(trained, blobs_module):
    """Overflow/underflow accounting (§V-A) must not count the phantom
    zero-padded rows a fixed-batch artifact appends."""
    _, _, xte, _, _ = blobs_module
    dyn = compile(trained["mlp"], Target(number_format="fxp16"))
    fixed = compile(trained["mlp"], Target(number_format="fxp16",
                                           batch_policy="fixed", batch_size=64))
    _, want = dyn.predict_with_stats(xte[:10])
    _, got = fixed.predict_with_stats(xte[:10])
    assert got == want


def test_target_validation():
    with pytest.raises(KeyError):
        Target(number_format="fxp7")
    with pytest.raises(KeyError):
        Target(backend="cuda")
    with pytest.raises(KeyError):
        Target(sigmoid="relu6")
    with pytest.raises(KeyError):
        Target(tree_layout="recursive")
    with pytest.raises(ValueError):
        Target(batch_policy="fixed")  # needs batch_size


def test_registry_dispatch(trained):
    assert model_kind(trained["tree"]) == "tree"
    assert model_kind(trained["svm-rbf"]) == "svm-rbf"
    assert set(lowering_kinds()) >= {"tree", "logistic", "mlp", "svm-linear",
                                     "svm-poly", "svm-rbf", "lm"}
    with pytest.raises(TypeError, match="compile_kind"):
        model_kind(object())


def test_stats_surface(trained, blobs_module):
    _, _, xte, _, _ = blobs_module
    art = compile(trained["mlp"], Target(number_format="fxp16"))
    _, stats = art.predict_with_stats(xte)
    assert stats["total"] > 0
    assert 0 <= stats["overflow_rate"] <= 1
    assert 0 <= stats["underflow_rate"] <= 1


# ---------------------------------------------------------------------------
# lm lowering: quantized serving over the same Target, no module global
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_model():
    import dataclasses

    import jax

    from repro.compile import LMModel
    from repro.configs import get_config
    from repro.lm import model as M

    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
                              d_head=32, d_ff=128, vocab_size=256)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return LMModel(cfg, params)


def test_lm_gate_sigmoid_global_is_gone():
    from repro.lm import model as M

    assert not hasattr(M, "GATE_SIGMOID")


def test_lm_lowering_serves(lm_model):
    art = compile(lm_model, Target(number_format="fxp8", weight_scale="qnm",
                                   kv_cache="int8", sigmoid="pwl4"))
    assert art.kind == "lm"
    cfg = art.extras["cfg"]
    assert cfg.gate_sigmoid == "pwl4"
    assert cfg.kv_cache_dtype == "int8"
    tok = np.array([3, 7], np.int32)
    seqs = art.extras["generate"](tok, 3)
    assert seqs.shape == (2, 4)
    nxt = art.predict(tok)
    assert nxt.shape == (2,)
    # weight-only quantization shrinks the artifact vs the float compile
    flt = compile(lm_model, Target(number_format="flt"))
    assert art.extras["quantized_bytes"] > 0
    assert art.memory_report()["flash"] < flt.memory_report()["flash"]


def test_lm_rejects_unsupported_format(lm_model):
    with pytest.raises(ValueError, match="weight-only"):
        compile(lm_model, Target(number_format="fxp32"))


def test_lm_config_gate_sigmoid_survives_default_target(lm_model):
    """A gate_sigmoid set on the ArchConfig is preserved when the Target
    leaves sigmoid at its default; a non-default Target wins."""
    import dataclasses

    from repro.compile import LMModel

    cfg = dataclasses.replace(lm_model.cfg, gate_sigmoid="pwl2")
    model = LMModel(cfg, lm_model.params)
    kept = compile(model, Target(number_format="flt"))
    assert kept.extras["cfg"].gate_sigmoid == "pwl2"
    overridden = compile(model, Target(number_format="flt", sigmoid="pwl4"))
    assert overridden.extras["cfg"].gate_sigmoid == "pwl4"


def test_discard_params_frees_but_blocks_save(tmp_path, trained, blobs_module):
    _, _, xte, _, _ = blobs_module
    art = compile(trained["logistic"], Target(number_format="fxp16"))
    before = art.predict(xte)
    art.discard_params()
    np.testing.assert_array_equal(art.predict(xte), before)
    with pytest.raises(ValueError, match="discard_params"):
        art.save(os.path.join(tmp_path, "nope.embml"))


# ---------------------------------------------------------------------------
# 32-bit containers on pallas have no exact TPU program
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["logistic", "mlp", "svm-linear", "svm-rbf"])
def test_pallas_fxp32_refused_on_tpu(trained, name, monkeypatch):
    """On a TPU, a 32-bit pallas Target fails at compile time and names
    itself — it never runs somewhere else quietly.  Off the TPU the same
    Target compiles (interpret mode holds the int64 intermediates)."""
    from repro.kernels import tune

    target = Target(number_format="fxp32", backend="pallas")
    assert compile(trained[name], target).target == target
    monkeypatch.setattr(tune, "on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="number_format='fxp32'"):
        compile(trained[name], target)
    # trees compare floats in their kernel: no int64 inside, still served
    assert compile(trained["tree"], target).kind == "tree"


# ---------------------------------------------------------------------------
# the entry points' persistent compile cache
# ---------------------------------------------------------------------------
def test_compile_cache_lands_only_in_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and nowhere in the checkout."""
    import subprocess
    import sys

    from repro.jax_cache import CHECKOUT

    local = os.path.join(CHECKOUT, ".jax_cache")
    before = set(os.listdir(local)) if os.path.isdir(local) else set()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(CHECKOUT, "src"))
    code = ("from repro.jax_cache import enable_compile_cache as e; "
            "print(e()); import jax, jax.numpy as jnp; "
            "jax.jit(lambda x: x * 3 - 1)(jnp.ones(5)).block_until_ready()")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(n.startswith("jit_") for n in os.listdir(tmp_path))
    after = set(os.listdir(local)) if os.path.isdir(local) else set()
    assert after == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from repro.jax_cache import CHECKOUT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        want = os.path.join(CHECKOUT, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
