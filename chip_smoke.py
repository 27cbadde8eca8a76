#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip, through the user's entry
points, at the paper's widths.

    python3 chip_smoke.py             # phases (a)-(d) on one chip
    python3 chip_smoke.py --chips 4   # only the 4-replica mesh phase

Phases, in one process, in order:

(a) classifiers — D6 (561 features, 6 classes, generated from its seed);
    all six kinds trained with the benchmark settings, served from
    ``Target(backend="pallas")`` endpoints at fxp16 and auto8 through
    ``InferenceService.submit``/``predict`` at batch 1, 37 and 64.  Every
    prediction must equal the ``ref`` backend's, compiled from the same
    model and the same frozen QuantPlan and run on the host CPU device.
    Every endpoint's program must hold a compiled TPU kernel (no interpret
    mode), and MLPs/SVMs must take the whole-model kernel route.
(b) fleet — 8 MLP endpoints at D1 width (42 features, auto16) coalesced by
    ``enable_fleet()`` under concurrent requests: at least one stacked
    dispatch, no fallback, every row equal to its member's own prediction.
(c) CLI — ``repro.launch.serve.main`` in-process with the pallas backend.
(d) LM decode — qwen2-0.5b at its published widths, random weights from a
    seed, 8 greedy tokens at batch 2 through ``svc.generate``; logits finite.

``--chips 4`` runs only this: the D6 MLP and tree served over
``make_serving_mesh(4)`` with ``strategy="spmd"``, compared row for row
with the same artifacts on one device.

Each phase prints its agreement counts, compile seconds and tuning
seconds.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  A run that
finds no TPU, or fails any check, exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
KINDS = ("tree", "logistic", "mlp", "svm-linear", "svm-poly", "svm-rbf")
WHOLE_MODEL_KINDS = ("mlp", "svm-poly", "svm-rbf")
FORMATS = ("fxp16", "auto8")
BATCHES = (1, 37, 64)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# compile-time accounting
# --------------------------------------------------------------------------
_COMPILE_S = [0.0]


def _on_event(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


@contextlib.contextmanager
def phase(name: str):
    from repro.kernels import tune

    c0, t0, w0 = _COMPILE_S[0], tune.tuning_seconds(), time.perf_counter()
    info: dict = {}
    yield info
    extra = " ".join(f"{k}={v}" for k, v in info.items())
    print(f"phase {name}: {extra} compile_s={_COMPILE_S[0] - c0:.3f} "
          f"tuning_s={tune.tuning_seconds() - t0:.3f} "
          f"wall_s={time.perf_counter() - w0:.3f}", flush=True)


# --------------------------------------------------------------------------
# oracle and kernel checks
# --------------------------------------------------------------------------
def ref_oracle(art, rows):
    """The ref backend, same params and frozen plan, on the host CPU."""
    import jax
    import numpy as np

    from repro.compile import compile_from_params

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = compile_from_params(art.kind, art.params,
                                  art.target.replace(backend="ref"),
                                  plan=art.quant_plan)
        return np.asarray(ref.predict(np.asarray(rows)))


def check_tpu_kernel(name: str, art, rows) -> None:
    """The served program holds a Mosaic kernel, not an interpreted one."""
    import jax

    text = jax.jit(art._program.predict).lower(rows).as_text()
    check("tpu_custom_call" in text,
          f"{name}: no TPU kernel in the lowered program (interpret mode?)")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_classifiers(dataset: str = "D6") -> None:
    import numpy as np

    from benchmarks.common import train_one
    from repro.compile import Target
    from repro.data import load_dataset
    from repro.kernels import tune
    from repro.serve import BatchingPolicy, InferenceService

    with phase("a-classifiers") as info:
        ds = load_dataset(dataset)
        # The benchmark's own trainers and settings, uncut.
        models = {kind: train_one(dataset, kind) for kind in KINDS}
        need = sum(BATCHES)
        rows = np.asarray(ds.x_test[:need], np.float32)
        svc = InferenceService()
        agree = total = 0
        try:
            for kind, model in models.items():
                for fmt in FORMATS:
                    name = f"{kind}/{fmt}"
                    target = Target(number_format=fmt, backend="pallas")
                    # No bucket-ladder warmup: only the buckets the three
                    # batches use are compiled (the CLI phase warms one).
                    ep = svc.register(
                        name, model, target,
                        policy=BatchingPolicy(max_batch=max(BATCHES),
                                              warmup=False),
                        calibration=(ds.x_train if target.is_calibrated
                                     else None))
                    art = ep.artifact
                    if kind in WHOLE_MODEL_KINDS:
                        check(art.kernel_strategy == "megakernel",
                              f"{name} took the {art.kernel_strategy} route")
                    check_tpu_kernel(name, art, rows[:max(BATCHES)])
                    # One request per batch size, in turn, so each is its
                    # own dispatch: submit for all but the last, which
                    # goes through the sync predict.
                    edges = np.cumsum((0,) + BATCHES)
                    parts = [rows[a:b] for a, b in zip(edges, edges[1:])]
                    got = [np.asarray(svc.submit(name, p).result(timeout=600))
                           for p in parts[:-1]]
                    got.append(np.asarray(svc.predict(name, parts[-1])))
                    got = np.concatenate(got)
                    want = ref_oracle(art, rows)
                    n_ok = int(np.sum(got == want))
                    print(f"  {name}: {n_ok}/{need} rows equal the ref "
                          f"oracle, route={art.kernel_strategy}", flush=True)
                    check(n_ok == need, f"{name}: {need - n_ok} rows differ "
                          f"from the ref oracle")
                    agree += n_ok
                    total += need
        finally:
            svc.close()
        dropped = tune.dropped_candidates()
        for line in dropped[:5]:
            print(f"  dropped tuning candidate: {line}")
        info.update(dataset=dataset, features=rows.shape[1],
                    endpoints=len(models) * len(FORMATS),
                    agree=f"{agree}/{total}",
                    dropped_candidates=len(dropped))


def phase_fleet(members: int = 8, train_rows: int = 4096) -> None:
    import numpy as np

    from repro.compile import Target
    from repro.data import load_dataset
    from repro.models import train_mlp
    from repro.serve import BatchingPolicy, InferenceService

    with phase("b-fleet") as info:
        ds = load_dataset("D1")
        epochs, requests = 2, 6
        print(f"cut: fleet members train on {train_rows} of "
              f"{len(ds.x_train)} rows for {epochs} epochs (benchmark "
              f"setting: all rows, 8 epochs)")
        x, y = ds.x_train[:train_rows], ds.y_train[:train_rows]
        models = [train_mlp(x, y, ds.n_classes, hidden=(64,), epochs=epochs,
                            seed=e) for e in range(members)]
        target = Target(number_format="auto16", backend="pallas")
        svc = InferenceService()
        try:
            names = [f"fleet-{e}" for e in range(members)]
            for name, model in zip(names, models):
                svc.register(name, model, target,
                             policy=BatchingPolicy(max_batch=64,
                                                   max_wait_ms=5.0,
                                                   warmup=False),
                             calibration=x)
            formed = svc.enable_fleet(names)
            check(len(formed) == 1, f"expected one fleet, got {formed}")
            rng = np.random.RandomState(0)
            batches = {n: [np.asarray(ds.x_test[rng.randint(0, 1000, b)],
                                      np.float32)
                           for b in rng.randint(1, 33, requests)]
                       for n in names}
            futs = {n: [] for n in names}
            start = threading.Barrier(members)

            def client(name):
                start.wait()
                for b in batches[name]:
                    futs[name].append(svc.submit(name, b))

            threads = [threading.Thread(target=client, args=(n,))
                       for n in names]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            agree = total = 0
            for name in names:
                # The member's own (solo) prediction of all its rows at
                # once: rows are independent, so one dispatch suffices.
                got = np.concatenate([np.asarray(f.result(timeout=600))
                                      for f in futs[name]])
                want = np.asarray(svc.endpoint(name).artifact.predict(
                    np.concatenate(batches[name])))
                agree += int(np.sum(got == want))
                total += len(want)
            snap = svc.stats()["_fleets"][0]
        finally:
            svc.close()
        print(f"  fleet snapshot: {json.dumps(snap, sort_keys=True)}")
        check(agree == total, f"fleet: {total - agree} rows differ from "
              f"their member's own prediction")
        check(snap["stacked_dispatches"] >= 1, "fleet: no stacked dispatch")
        check(snap["stack_fallbacks"] == 0,
              f"fleet: {snap['stack_fallbacks']} stacked-dispatch fallbacks")
        check(snap["warmup_failures"] == 0,
              f"fleet: {snap['warmup_failures']} warmup failures")
        info.update(members=members, agree=f"{agree}/{total}",
                    stacked_dispatches=snap["stacked_dispatches"],
                    fallbacks=snap["stack_fallbacks"])


def phase_cli(requests: int = 512) -> None:
    from repro.launch import serve

    with phase("c-cli") as info:
        argv = ["--classifier", "mlp", "--backend", "pallas", "--format",
                "auto16", "--requests", str(requests)]
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            serve.main(argv)
        text = out.getvalue()
        print("  " + text.strip().replace("\n", "\n  "))
        m = re.search(r"accuracy ([0-9.]+)", text)
        check(m is not None, "cli: no accuracy line")
        check("auto16/pallas" in text, "cli: endpoint is not auto16/pallas")
        # The CLI's synthetic blobs are cleanly separable.
        check(float(m.group(1)) >= 0.9, f"cli: accuracy {m.group(1)}")
        info.update(argv=" ".join(argv), accuracy=m.group(1))


def phase_lm(full: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile import LMModel, Target
    from repro.configs import get_config
    from repro.lm import model as M
    from repro.serve import InferenceService

    with phase("d-lm") as info:
        arch, batch, tokens = "qwen2-0.5b", 2, 8
        cfg = get_config(arch)
        if not full:
            cfg = cfg.reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree_util.tree_leaves(params))
        svc = InferenceService()
        try:
            svc.register(arch, LMModel(cfg, params), Target())
            tok = np.random.RandomState(0).randint(
                1, cfg.vocab_size, (batch,)).astype(np.int32)
            seqs = np.asarray(svc.generate(arch, tok, tokens))
            art = svc.endpoint(arch).artifact
            step, init = art.extras["serve_step"], art.extras["init_cache"]
            cache = init(batch, tokens + 4)
            logits = None
            for t in range(tokens):
                logits, cache = step(art.extras["params"], cache,
                                     {"token": jnp.asarray(seqs[:, t])})
        finally:
            svc.close()
        logits = np.asarray(logits, np.float32)
        check(seqs.shape == (batch, tokens + 1), f"lm: seqs {seqs.shape}")
        check(bool(np.all((seqs >= 0) & (seqs < cfg.vocab_size))),
              "lm: token out of vocabulary")
        check(logits.shape == (batch, cfg.vocab_size),
              f"lm: logits {logits.shape}")
        check(bool(np.all(np.isfinite(logits))), "lm: non-finite logits")
        # The greedy tokens the service produced are the logits' argmax.
        check(bool(np.all(np.argmax(logits, -1) == seqs[:, tokens])),
              "lm: generate disagrees with the step's argmax")
        info.update(arch=arch, d_model=cfg.d_model, layers=cfg.n_layers,
                    params=n_params, tokens=f"{batch}x{tokens}")


def phase_mesh(dataset: str = "D6") -> None:
    import numpy as np

    from benchmarks.common import train_one
    from repro.compile import Target
    from repro.data import load_dataset
    from repro.serve import InferenceService
    from repro.sharding.rules import make_serving_mesh

    with phase("mesh") as info:
        n_devices, batches = 4, (1, 37, 64, 256)
        ds = load_dataset(dataset)
        mesh = make_serving_mesh(n_devices)
        rows = np.asarray(ds.x_test[:max(batches)], np.float32)
        svc = InferenceService()
        agree = total = 0
        try:
            for kind in ("mlp", "tree"):
                model = train_one(dataset, kind)
                target = Target(number_format="fxp16", backend="pallas")
                one = svc.register(f"{kind}/1", model, target).artifact
                ep = svc.register(f"{kind}/mesh", model, target, mesh=mesh,
                                  mesh_strategy="spmd")
                art = ep.artifact
                check(art.mesh_strategy == "spmd" and art.replicas == n_devices,
                      f"{kind}: {art.replicas} replicas, {art.mesh_strategy}")
                for b in batches:
                    got = np.asarray(svc.predict(f"{kind}/mesh", rows[:b]))
                    want = np.asarray(one.predict(rows[:b]))
                    n_ok = int(np.sum(got == want))
                    print(f"  {kind} batch {b}: {n_ok}/{b} rows equal the "
                          f"single-device prediction", flush=True)
                    check(n_ok == b, f"{kind} mesh batch {b}: {b - n_ok} "
                          f"rows differ")
                    agree += n_ok
                    total += b
        finally:
            svc.close()
        info.update(replicas=n_devices, strategy="spmd",
                    agree=f"{agree}/{total}")


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the 4-replica spmd mesh phase")
    args = ap.parse_args(argv)

    # The oracle runs on the host CPU device next to the chip.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "tpu" in platforms and "cpu" not in platforms:
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from repro.jax_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not next to this script "
              f"({ROOT}/src): {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"FAIL: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; this smoke has no fallback",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"FAIL: --chips {args.chips} but JAX found {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    try:
        if args.chips == 4:
            phase_mesh()
        else:
            phase_classifiers()
            phase_fleet()
            phase_cli()
            phase_lm()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"total compile_s={_COMPILE_S[0]:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
