"""Elastic re-sharding: a checkpoint written under one mesh restores and
steps under a different mesh (DP/TP degree change across restarts).

Runs in a subprocess so the 8 placeholder host devices never leak into the
other tests' single-device view (jax locks the device count on first init).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.lm import model as M
    from repro.sharding.rules import Rules
    from repro.train.checkpoint import CheckpointManager
    from repro.train.optim import adamw, apply_updates

    cfg = dataclasses.replace(
        get_config("qwen2-0.5b").reduced(), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_head=16, d_ff=128, vocab_size=256, remat=False,
        dtype="float32")
    ckpt_dir = os.environ["ELASTIC_CKPT_DIR"]
    batch = {"tokens": jnp.ones((8, 16), jnp.int32)}
    opt = adamw(1e-3)

    def one_step(mesh, params, opt_state):
        rules = Rules(mesh)
        pspecs = M.param_specs(cfg, rules)
        params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), NamedSharding(mesh, s)),
            params, pspecs)
        opt_state = jax.device_put(opt_state)

        @jax.jit
        def step(p, s, b):
            loss, g = jax.value_and_grad(lambda q: M.loss_fn(q, b, cfg, rules))(p)
            u, s = opt.update(g, s, p)
            return apply_updates(p, u), s, loss

        with mesh:
            return step(params, opt_state, batch)

    mgr = CheckpointManager(ckpt_dir, keep=2)
    phase = os.environ["ELASTIC_PHASE"]
    if phase == "save":
        mesh = make_mesh((4, 2), ("data", "model"))   # DP4 x TP2
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        params, opt_state, loss = one_step(mesh, params, opt.init(params))
        mgr.save(1, {"params": jax.tree.map(np.asarray, params)},
                 metadata={"loss": float(loss)})
        print("SAVED", float(loss))
    else:
        mesh = make_mesh((2, 4), ("data", "model"))   # DP2 x TP4 (re-shard)
        like = {"params": M.init_params(cfg, jax.random.PRNGKey(0))}
        step_idx, tree, meta = mgr.restore(like)
        params, opt_state, loss = one_step(mesh, tree["params"],
                                           opt.init(tree["params"]))
        assert jnp.isfinite(loss)
        print("RESTORED", step_idx, float(loss))
""")


@pytest.mark.slow
def test_checkpoint_reshards_across_meshes(tmp_path):
    env = dict(os.environ, PYTHONPATH="src", ELASTIC_CKPT_DIR=str(tmp_path))
    for phase in ("save", "restore"):
        env["ELASTIC_PHASE"] = phase
        out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                             capture_output=True, text=True, timeout=480,
                             cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode == 0, out.stderr[-2000:]
        assert ("SAVED" if phase == "save" else "RESTORED") in out.stdout
