import os
os.environ["XLA_FLAGS"] = (os.environ.get("EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY other import (jax locks the device count
on first init).  For each cell this driver:

  1. builds the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  2. constructs abstract params / optimizer state / inputs
     (ShapeDtypeStruct stand-ins — nothing is allocated),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
     .compile()`` — train_step for train cells, serve_step for decode cells,
     forward for prefill cells,
  4. prints ``memory_analysis()`` (proves it fits) and ``cost_analysis()``
     (FLOPs/bytes for §Roofline), parses collective bytes from the
     partitioned HLO, and
  5. writes a JSON record under benchmarks/results/ for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
Options: --quantized (weight-only int8 serving artifact), --out-dir.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.lm import model as model_lib
from repro.roofline.analysis import (HW, collective_bytes_from_hlo,
                                     model_flops, roofline_terms)
from repro.sharding.rules import Rules
from repro.train.trainer import TrainConfig, make_train_step

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


def _sds_shardings(tree, mesh, spec_tree):
    return jax.tree.map(
        lambda sds, spec: NamedSharding(mesh, spec), tree, spec_tree)


def _batch_specs(cfg: ArchConfig, shape: ShapeSpec, rules: Rules, inputs: Dict):
    """PartitionSpec per input leaf: batch dim on DP axes, model dims on TP."""
    def rule(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        spec = [None] * nd
        if leaf.shape[0] == shape.global_batch and shape.global_batch > 1:
            ax = rules.resolve("batch", leaf.shape[0])
            spec[0] = ax
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(inputs)
    specs = [rule(jax.tree_util.keystr(p), l) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def moments_dtype_for(cfg: ArchConfig) -> str:
    # >100B params: bf16 moments (capacity analysis in EXPERIMENTS.md)
    return "bfloat16" if cfg.param_count() > 100e9 else "float32"


def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, quantized: bool = False,
               fsdp: bool = True, microbatches: int = 4):
    """Returns (fn, example_args(abstract), in_shardings, donate) for a cell."""
    rules = Rules(mesh)
    inputs = model_lib.input_specs(cfg, shape)

    if shape.kind == "train":
        tcfg = TrainConfig(moments_dtype=moments_dtype_for(cfg),
                           microbatches=microbatches)
        from repro.train.trainer import make_optimizer
        opt = make_optimizer(tcfg)
        step = make_train_step(cfg, tcfg, opt, rules=rules)
        aparams = model_lib.abstract_params(cfg)
        aopt = jax.eval_shape(opt.init, aparams)
        pspecs = model_lib.param_specs(cfg, rules, fsdp=fsdp)
        # optimizer moments mirror param specs; step counter replicated
        from repro.train.optim import OptState
        mu_specs = pspecs if aopt.mu is not None else None
        nu_specs = pspecs if aopt.nu is not None else None
        opt_spec_tree = OptState(P(), mu_specs, nu_specs)
        bspecs = _batch_specs(cfg, shape, rules, inputs)
        in_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
            jax.tree.map(lambda s: NamedSharding(mesh, s), opt_spec_tree,
                         is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs),
        )
        args = (aparams, aopt, inputs)
        return step, args, in_shardings, (0, 1)

    if shape.kind == "prefill":
        def fwd(params, batch):
            return model_lib.loss_fn(params, batch, cfg, rules) if cfg.encoder_only \
                else model_lib.forward(params, batch, cfg, rules)
        aparams = model_lib.abstract_params(cfg)
        if quantized:
            from repro.core.quantize import QuantSpec, quantize_lm_params
            aparams = jax.eval_shape(
                lambda p: quantize_lm_params(p, QuantSpec()), aparams)
        pspecs = model_lib.param_specs(cfg, rules, fsdp=fsdp, tree=aparams)
        bspecs = _batch_specs(cfg, shape, rules, inputs)
        in_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
            jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs),
        )
        return fwd, (aparams, inputs), in_shardings, ()

    # decode
    def serve(params, cache, batch):
        return model_lib.serve_step(params, cache, batch, cfg, rules)

    aparams = model_lib.abstract_params(cfg)
    if quantized:
        from repro.core.quantize import QuantSpec, quantize_lm_params
        aparams = jax.eval_shape(
            lambda p: quantize_lm_params(p, QuantSpec()), aparams)
    pspecs = model_lib.param_specs(cfg, rules, fsdp=fsdp, tree=aparams)
    cspecs = model_lib.cache_specs(cfg, rules, shape.global_batch, shape.seq_len)
    inputs2 = dict(inputs)
    acache = inputs2.pop("cache")
    bspecs = _batch_specs(cfg, shape, rules, inputs2)
    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
        jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs),
        jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs),
    )
    return serve, (aparams, acache, inputs2), in_shardings, (1,)


def run_cell(arch: str, shape_name: str, mesh_name: str, quantized: bool = False,
             fsdp: bool = True, out_dir: Optional[str] = None,
             verbose: bool = True, microbatches: int = 4,
             kv_int8: bool = False, expert_sharding=None,
             moe_chunk: int = 0) -> Dict:
    import dataclasses as _dc
    cfg = get_config(arch)
    if kv_int8:
        cfg = _dc.replace(cfg, kv_cache_dtype="int8")
    if expert_sharding is not None and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe,
                                               expert_sharding=expert_sharding))
    if moe_chunk:
        cfg = _dc.replace(cfg, moe_prefill_chunk=moe_chunk)
    shape = SHAPES[shape_name]
    status = cfg.runnable_shapes()[shape_name]
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "quantized": quantized, "kv_int8": kv_int8, "status": status,
                 "microbatches": microbatches}
    if status != "run":
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: {status}")
        return rec

    if mesh_name.startswith("dp"):
        # custom single-pod mesh 'dp<D>tp<T>' e.g. dp64tp4 (perf iterations)
        dpn, tpn = mesh_name[2:].split("tp")
        mesh = make_mesh((int(dpn), int(tpn)), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=mesh_name == "multipod")
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    fn, args, in_shardings, donate = build_cell(cfg, shape, mesh, quantized,
                                                 fsdp, microbatches)

    with mesh:
        jitted = jax.jit(fn, in_shardings=in_shardings,
                         donate_argnums=donate or None)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    hlo_flops_dev = float(cost.get("flops", 0.0)) if cost else 0.0
    hlo_bytes_dev = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops(cfg.param_count(active_only=True), tokens,
                     "train" if shape.kind == "train" else "fwd")
    # Primary roofline source: the analytic model — HLO cost_analysis counts
    # scan bodies ONCE (see repro/roofline/analytic.py docstring), so raw HLO
    # numbers are recorded separately as hlo_*.
    from repro.roofline.analytic import analytic_cost
    an = analytic_cost(
        cfg, shape, chips=chips, tp=mesh.shape.get("model", 1),
        dp_in_pod=mesh.shape.get("data", 1), pods=mesh.shape.get("pod", 1),
        microbatches=microbatches if shape.kind == "train" else 1,
        quantized=quantized, kv_quantized=kv_int8)
    rep = roofline_terms(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        flops_dev=an.flops_global / chips, bytes_dev=an.hbm_bytes_global / chips,
        coll_bytes_dev=an.coll_bytes_dev,
        model_flops_global=mf,
        bytes_per_device=getattr(mem, "temp_size_in_bytes", None) if mem else None,
        note="analytic primary; hlo_* raw (scan bodies counted once by XLA)")

    mem_fields = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                mem_fields[f] = int(v)

    rec.update({
        "chips": chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "cost_analysis": {k: float(v) for k, v in (cost or {}).items()
                          if isinstance(v, (int, float))},
        "hlo_flops_dev": hlo_flops_dev,
        "hlo_bytes_dev": hlo_bytes_dev,
        "memory_analysis": mem_fields,
        "collective_bytes": coll,
        "analytic": an.to_dict(),
        "roofline": rep.to_dict(),
    })
    if verbose:
        ms = mem_fields.get("temp_size_in_bytes", 0)
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}"
              f"{' (int8)' if quantized else ''}: OK "
              f"compile={t_compile:.1f}s "
              f"an_flops/dev={an.flops_global / chips:.3e} "
              f"an_bytes/dev={an.hbm_bytes_global / chips:.3e} "
              f"an_coll/dev={an.coll_bytes_dev:.3e} "
              f"dominant={rep.dominant} temp/dev={ms / 1e9:.2f}GB")
        print(f"  memory_analysis: {mem_fields}")
        print(f"  hlo cost_analysis (scan-undercount): flops={hlo_flops_dev:.4e} "
              f"bytes={hlo_bytes_dev:.4e} coll={coll['total']:.3e}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = ("_int8" if quantized else "") + ("_kv8" if kv_int8 else "") \
            + (f"_mb{microbatches}" if microbatches != 4 and shape.kind == "train" else "") \
            + (f"_moechunk{moe_chunk}" if moe_chunk else "")
        path = os.path.join(out_dir,
                            f"dryrun_{arch}_{shape_name}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod",
                    help="pod | multipod | dp<D>tp<T> (e.g. dp64tp4)")
    ap.add_argument("--all", action="store_true", help="every runnable cell")
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--expert-sharding", default=None, choices=["ep", "ep2d", "tp"])
    ap.add_argument("--moe-chunk", type=int, default=0)
    ap.add_argument("--out-dir", default=os.path.abspath(DEFAULT_OUT))
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = []
    for a, s in cells:
        try:
            run_cell(a, s, args.mesh, quantized=args.quantized,
                     fsdp=not args.no_fsdp, out_dir=args.out_dir,
                     microbatches=args.microbatches, kv_int8=args.kv_int8,
                     expert_sharding=args.expert_sharding,
                     moe_chunk=args.moe_chunk)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures.append((a, s, repr(e)))
            print(f"[dryrun] {a} x {s} x {args.mesh}: FAILED {e}")
            traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        sys.exit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
