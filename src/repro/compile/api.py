"""The staged compiler entry point: ``compile(model, Target(...))``.

Pipeline (replaces the closure monolith in ``core/convert.py``):

    extract_params -> calibrate -> quantize -> lower -> specialize/jit

Each registered lowering (see :mod:`repro.compile.registry`) implements the
model-specific stages for one kind.  ``calibrate`` only runs for calibrated
(``auto*``) Targets: the lowering replays its program in float over the
caller-supplied ``calibration`` batch and the planner freezes a per-tensor
:class:`repro.quant.QuantPlan`, which the quantize/lower stages then resolve
tensor formats through (fixed formats skip the stage; plan is None).
``specialize`` is shared: it applies the Target's backend (eager reference /
``jax.jit`` / Pallas programs are already built by ``lower``) and batch
policy, producing the final callable wrapped into a
:class:`repro.compile.artifact.CompiledArtifact`.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.core.fixedpoint import FxpStats

from .artifact import CompiledArtifact
from .fingerprint import fingerprint_params
from .registry import Lowered, get_lowering, model_kind
from .target import Target

__all__ = ["compile", "compile_from_params", "specialize_mesh",
           "resolve_mesh_strategy"]


def resolve_mesh_strategy(mesh: Any, strategy: str = "auto") -> str:
    """Resolve ``'auto'`` to the concrete mesh execution strategy.

    ``fused`` on host-emulated (all-CPU) meshes — every "device" shares one
    physical host, so per-replica dispatch is pure overhead — and ``spmd``
    (one shard_map-partitioned program) on real accelerator meshes.  The
    single place this policy lives; the artifact cache and specialize_mesh
    both key off it.
    """
    if strategy == "auto":
        from repro.sharding import rules as shrules

        return "fused" if shrules.is_host_emulated(mesh) else "spmd"
    return strategy


def _subtract_phantom_rows(stats: FxpStats, k: int, pad_row_cache: list,
                           probe: Callable) -> FxpStats:
    """Remove ``k`` phantom zero-pad rows' contribution from ``stats``.

    Every stats counter is an elementwise count, so rows are independent and
    an all-zeros batch yields exactly N copies of one phantom row's events
    (zero rows are *not* silent — biases make them nonzero downstream).
    ``probe()`` runs such a batch once, returning ``(n_rows, FxpStats)``;
    the per-row contribution is memoized in ``pad_row_cache`` (a one-slot
    list owned by the calling wrapper).  Shared by the fixed-batch wrapper
    and the mesh-replica wrapper — one definition of the correction rule.
    """
    if not pad_row_cache:
        n, zstats = probe()
        pad_row_cache.append(FxpStats(
            *(np.asarray(v) // n
              for v in (zstats.overflow, zstats.underflow, zstats.total))))
    per = pad_row_cache[0]
    return FxpStats(np.asarray(stats.overflow) - k * per.overflow,
                    np.asarray(stats.underflow) - k * per.underflow,
                    np.asarray(stats.total) - k * per.total)


def _flat_jit(predict: Callable) -> Callable:
    """``jax.jit(predict)`` whose input crosses to the device as one flat
    buffer.

    A TPU keeps a 2-D 32-bit array in (8, 128) tiles, and lays a ``(rows,
    features)`` program input out transposed, so the runtime transposes
    each host array into tiles on the host; a 1-D array's tiles are runs of
    its memory, so it crosses as a straight copy.  The program takes the
    rows flat with their shape as a static argument and restores it with a
    reshape on the device, then runs ``predict`` unchanged: the same
    arithmetic, outputs and stats.  The jit cache is keyed by the shape, so
    it holds one executable per bucket, as a 2-D jit would.

    Host input (anything ``np.asarray`` takes) is passed as
    ``x.reshape(-1)``: a view of any contiguous array, a copy of any other.
    A ``jax.Array`` is reshaped where it lives, eagerly, outside the
    program: one more dispatch and a 2-D -> 1-D relayout on the device,
    which the program then undoes.  The serving paths hand it host arrays.
    """
    program = jax.jit(lambda flat, shape: predict(flat.reshape(shape)),
                      static_argnums=1)

    def run(x):
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        return program(x.reshape(-1), x.shape)

    return run


def _specialize(program: Lowered, target: Target, kind: str = "") -> Callable:
    """Stage 4: backend jit + batch policy.

    * ``ref`` runs the program eagerly (op-by-op oracle semantics, easiest to
      debug); ``xla``/``pallas`` wrap the whole program in ``jax.jit``, its
      input taken flat.
    * ``emit`` serves through the generated C: the lowering's ``emit_spec``
      is templated into a freestanding translation unit, built once with the
      system ``cc`` on first predict (lazy — emission itself needs no
      toolchain), inputs are quantized host-side with the exact traced
      rounding, and the compiled binary produces the labels.  Stats cover
      input quantization only (the C program has no stats plumbing).
    * ``fixed`` batch policy pads every call up to ``batch_size`` (one traced
      shape, the embedded static-allocation posture) and rejects larger
      batches; padded rows are sliced off the output.
    """
    predict = program.predict
    if target.backend in ("xla", "pallas") and program.jittable:
        predict = _flat_jit(predict)
    elif target.backend == "emit":
        from repro import emit as emit_mod

        spec = (program.extras or {}).get("emit_spec")
        if spec is None:
            if not target.is_quantized:
                raise TypeError(
                    "the 'emit' backend serves quantized targets only — "
                    "float models have no fixed-point program to emit "
                    "(use number_format='fxp*'/'auto*')")
            raise TypeError(
                f"the '{kind or 'requested'}' lowering does not support the "
                f"'emit' backend (no emit_spec); C emission covers the "
                f"classifier lowerings (tree/logistic/mlp/svm-*)")
        runner_cell: list = []

        def predict(x):
            if not runner_cell:
                src = emit_mod.emit_c(spec, kind=kind,
                                      target_name=target.number_format)
                runner_cell.append(emit_mod.CRunner(
                    src, emit_mod.input_format(spec)))
            return runner_cell[0].predict(x)

    if target.batch_policy == "fixed":
        inner = predict
        batch_size = target.batch_size
        pad_row_stats: list = []

        def predict(x):
            x = np.asarray(x)
            n = x.shape[0]
            if n > batch_size:
                raise ValueError(
                    f"batch {n} exceeds the artifact's fixed batch_size "
                    f"{batch_size}; recompile with a larger Target.batch_size")
            if n == batch_size:
                return inner(x)
            pad = [(0, batch_size - n)] + [(0, 0)] * (x.ndim - 1)
            out, stats = inner(np.pad(x, pad))
            if not target.is_quantized:
                return out[:n], stats  # float stats are structurally zero
            stats = _subtract_phantom_rows(
                stats, batch_size - n, pad_row_stats,
                lambda: (batch_size, inner(np.zeros(
                    (batch_size,) + x.shape[1:], x.dtype))[1]))
            return out[:n], stats

    return predict


def compile_from_params(kind: str, params: Any, target: Target,
                        calibration: Any = None,
                        plan: Any = None) -> CompiledArtifact:
    """Run the calibrate/quantize/lower/specialize stages on already-extracted
    params.

    This is the shared tail of :func:`compile` and of
    :func:`repro.compile.artifact.load` (archives store extracted params).
    For calibrated targets either a ``calibration`` batch (a plan is derived
    from it) or an already-frozen ``plan`` (the archive-load and cache paths,
    which must reproduce the original artifact bit-for-bit without the
    original batch) must be supplied.
    """
    from repro.quant import make_plan

    lowering = get_lowering(kind)
    if target.is_calibrated:
        if plan is None:
            plan = make_plan(lowering, params, target, calibration)
    else:
        plan = None  # fixed/float targets ignore stray plans
    qparams = lowering.quantize(params, target, plan)
    program = lowering.lower(qparams, target, plan)
    predict = _specialize(program, target, kind=kind)
    return CompiledArtifact(kind=kind, target=target, params=params,
                            _predict=predict, flash_bytes=program.flash_bytes,
                            sram_bytes=program.sram_bytes,
                            extras=program.extras,
                            fingerprint=fingerprint_params(kind, params),
                            _program=program, quant_plan=plan)


def specialize_mesh(artifact: CompiledArtifact, mesh: Any,
                    strategy: str = "auto") -> CompiledArtifact:
    """Stage 5 (optional): replica-aware data-parallel predict over a mesh.

    Returns a new artifact whose predict shards the batch axis across the
    mesh's data-parallel replicas (see :mod:`repro.sharding.rules`), with
    *replica-aware padding*: every replica always sees the same power-of-two
    shard, so each device serves from the same tuned block-size entry and
    warm jit trace as single-device serving — which is also why the sharded
    predictions are bit-identical to single-device ones (row independence;
    the parity suite is the oracle).

    Execution strategy:

    * ``spmd``  — one ``shard_map``-partitioned program; each device runs the
      lowered predict on its shard, overflow/underflow stats are ``psum``-ed.
      The real-mesh path (TPU/GPU pods).
    * ``fused`` — the replica shards execute as one fused host-level batch on
      the artifact's own specialized predict.  Chosen automatically for
      host-emulated meshes (``--xla_force_host_platform_device_count``),
      where all "devices" share one physical host and per-replica dispatch
      is pure overhead; bit-identical to ``spmd`` by row independence.
    * ``auto``  — ``fused`` on host-emulated meshes, ``spmd`` otherwise.

    The ``fused`` path additionally tracks per-replica health
    (:class:`repro.sharding.ReplicaHealthTracker`, surfaced as
    ``artifact.replica_health``): a replica whose shard dispatch keeps
    faulting is evicted and its shards fail over to the survivors — still
    bit-identical, because rows are independent and every replica runs the
    same specialized program — then periodically probed for re-admission.
    While every replica is healthy and no ``mesh.replica`` fault rules are
    installed, dispatch takes the original untracked fast path.
    """
    import dataclasses as _dc

    from repro.sharding import ReplicaHealthTracker
    from repro.sharding import rules as shrules

    if artifact.kind == "lm":
        raise TypeError(
            "specialize_mesh supports classifier artifacts only; LM decode "
            "shards via the model-parallel LM stack, not batch replicas")
    if artifact.target.backend == "emit":
        raise TypeError(
            "specialize_mesh does not apply to the 'emit' backend: the C "
            "binary serves on the host, not a device mesh (spmd would "
            "silently fall back to the traced program) — specialize a "
            "ref/xla/pallas artifact instead")
    if artifact.mesh is not None:
        raise ValueError(
            f"artifact is already specialized for mesh {artifact.mesh_key}; "
            f"nesting mesh wrappers would double-pad every batch — "
            f"specialize the base (single-device) artifact instead")
    program = artifact._program
    if program is None:
        raise ValueError(
            "artifact carries no lowered program (legacy pickle?); recompile "
            "via repro.compile.compile or load() to specialize a mesh")
    if strategy not in ("auto", "spmd", "fused"):
        raise ValueError("strategy must be 'auto', 'spmd' or 'fused'")
    strategy = resolve_mesh_strategy(mesh, strategy)
    replicas = shrules.dp_size(mesh)
    target = artifact.target
    fixed_shard = target.batch_size if target.batch_policy == "fixed" else None

    if strategy == "spmd":
        if not program.jittable:
            raise TypeError(
                f"'{artifact.kind}' program is not jittable; spmd mesh "
                f"specialization needs a traceable predict")
        from jax.sharding import PartitionSpec as P

        axes = shrules.batch_axes(mesh)
        spec = shrules.batch_spec(mesh)

        def _shard_fn(xs):
            out, stats = program.predict(xs)
            if axes:  # no batch axes -> single replica, nothing to reduce
                stats = jax.tree_util.tree_map(
                    lambda s: jax.lax.psum(s, axes), stats)
            return out, stats

        inner = jax.jit(jax.shard_map(_shard_fn, mesh=mesh,
                                      in_specs=(spec,),
                                      out_specs=(spec, P()),
                                      check_vma=False))
    else:
        inner = artifact._predict  # already specialized (jit + batch policy)

    tracker = ReplicaHealthTracker(replicas) if strategy == "fused" else None

    def _mesh_faults():
        """The installed fault injector, iff it has ``mesh.replica`` rules
        (lazy import: repro.serve depends on repro.compile, not vice versa)."""
        try:
            from repro.serve import faults
        except Exception:
            return None
        return faults.current() if faults.active_for("mesh.replica") else None

    def _replica_dispatch(shard_x, slot, injector):
        """Run one shard on the healthiest available replica (nominal
        replica first), reporting outcomes to the tracker.  Raises the last
        failure only when every candidate replica refused the shard."""
        last = None
        for replica in tracker.candidates(slot):
            try:
                if injector is not None:
                    injector.fire("mesh.replica", name=str(replica),
                                  batch=shard_x)
                o, s = inner(shard_x)
            except Exception as e:
                tracker.record_failure(replica)
                last = e
                continue
            tracker.record_success(replica)
            return o, s
        raise last

    # Replica-aware padding must not leak phantom overflow/underflow counts
    # into predict_with_stats — shares the fixed-batch wrapper's correction.
    pad_row_stats: list = []

    def predict(x):
        x = np.asarray(x)
        n = x.shape[0]
        shard, total = shrules.replica_bucket(n, replicas)
        if fixed_shard is not None:
            if n > fixed_shard * replicas:
                raise ValueError(
                    f"batch {n} exceeds the mesh capacity "
                    f"{fixed_shard * replicas} ({replicas} replicas x fixed "
                    f"batch_size {fixed_shard}); recompile or grow the mesh")
            shard, total = fixed_shard, fixed_shard * replicas
        if total > n:
            pad = [(0, total - n)] + [(0, 0)] * (x.ndim - 1)
            x = np.pad(x, pad)
        injector = _mesh_faults() if strategy == "fused" else None
        tracked = tracker is not None and (injector is not None
                                           or not tracker.all_healthy())
        if strategy == "fused" and (fixed_shard is not None or tracked):
            outs, stats = [], None
            for r in range(replicas):
                shard_x = x[r * shard:(r + 1) * shard]
                if tracked:
                    o, s = _replica_dispatch(shard_x, r, injector)
                else:
                    o, s = inner(shard_x)
                outs.append(np.asarray(o))
                stats = s if stats is None else stats.merge(s)
            out = np.concatenate(outs, axis=0)
        else:
            out, stats = inner(x)
        if total == n or not target.is_quantized:
            return out[:n], stats
        stats = _subtract_phantom_rows(
            stats, total - n, pad_row_stats,
            lambda: (total,
                     predict(np.zeros((total,) + x.shape[1:], x.dtype))[1]))
        return out[:n], stats

    return _dc.replace(artifact, _predict=predict, mesh=mesh,
                       replicas=replicas, mesh_strategy=strategy,
                       replica_health=tracker)


def compile(model: Any, target: Optional[Target] = None,
            calibration: Any = None, **kwargs) -> CompiledArtifact:
    """Compile a trained model into an embedded inference artifact.

    ``target`` may be omitted and given as keyword fields instead:
    ``compile(model, number_format="fxp16", backend="pallas")``.

    ``calibration`` is a sample input batch, required by calibrated
    (``auto*``) number formats: the compiler observes per-tensor ranges on
    it and freezes a :class:`repro.quant.QuantPlan` onto the artifact.
    """
    tgt = target if target is not None else Target(**kwargs)
    if target is not None and kwargs:
        raise TypeError("pass either a Target or keyword fields, not both")
    kind = model_kind(model)
    lowering = get_lowering(kind)
    params = lowering.extract_params(model)
    return compile_from_params(kind, params, tgt, calibration=calibration)
