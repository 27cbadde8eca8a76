"""The compiled artifact — the paper's "output file" analogue.

A :class:`CompiledArtifact` is the frozen, self-contained result of
:func:`repro.compile.compile`: extracted parameters + a specialized predict
program + the memory model.  ``save(path)`` writes a single-file archive
(compressed msgpack: kind + Target + parameter tree + the frozen QuantPlan
for calibrated targets) and ``load(path)`` re-runs the lowering pipeline on
the stored parameters, so an archive round-trips to an artifact that
predicts identically — including across machines that pick a different
kernel execution mode (interpret vs TPU), and without needing the original
calibration batch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro import spans
from repro.core.fixedpoint import FxpStats
from repro.train.checkpoint import (LEAF_KEY as _LEAF_KEY,
                                    atomic_write_bytes, compress_bytes,
                                    decode_leaf, decompress_bytes,
                                    encode_leaf)

from .target import Target

__all__ = ["CompiledArtifact", "ArtifactIntegrityError", "load",
           "mesh_descriptor"]


class ArtifactIntegrityError(ValueError):
    """The archive's bytes do not match what was saved (member checksum
    mismatch, undecodable container, truncation).  Raised *before* any
    corrupted member is deserialized: a flipped bit in stored weights must
    fail loudly at load, never become a silently-wrong classifier."""


def mesh_descriptor(mesh: Optional[Any], strategy: Optional[str]) -> Optional[Tuple]:
    """Hashable (axes, device ids, strategy) descriptor of a mesh
    specialization — the cache-key component for mesh-specialized artifacts.

    Device identity is part of the key: two same-shaped meshes over
    *disjoint* device sets (splitting a host's devices between endpoints)
    must not alias to one artifact, or the second endpoint would silently
    serve on the first mesh's devices.  ``None`` for single-device
    artifacts."""
    if mesh is None:
        return None
    devs = list(mesh.devices.flat)
    return (tuple((a, int(mesh.shape[a])) for a in mesh.axis_names),
            devs[0].platform if devs else "cpu",
            tuple(int(d.id) for d in devs), strategy)

_ARCHIVE_FORMAT = "repro-compiled-artifact"
# v2: optional ``quant_plan`` payload (calibrated per-tensor formats).
# v3: members stored as individually-packed blobs with per-member sha256
# verified on load.  v1/v2 archives still load (without integrity checks —
# they carry none).
_ARCHIVE_VERSION = 3
# The v3 member blobs, in the order they are hashed into the archive.
_ARCHIVE_MEMBERS = ("kind", "target", "params", "quant_plan", "metadata")


# --------------------------------------------------------------------------
# parameter-tree (de)serialization: nested dicts/lists of arrays + scalars,
# leaves in the shared checkpoint codec.
# --------------------------------------------------------------------------
def _encode(x: Any) -> Any:
    if isinstance(x, dict):
        return {str(k): _encode(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return {_LEAF_KEY: "list", "items": [_encode(v) for v in x]}
    return encode_leaf(x)


def _decode(d: Any) -> Any:
    if not isinstance(d, dict):
        return d
    kind = d.get(_LEAF_KEY)
    if kind is None:
        return {k: _decode(v) for k, v in d.items()}
    if kind == "list":
        return [_decode(v) for v in d["items"]]
    return decode_leaf(d)


@dataclasses.dataclass
class CompiledArtifact:
    """Frozen inference artifact: parameters + specialized predict program."""

    kind: str  # 'tree' | 'logistic' | 'mlp' | 'svm-*' | 'lm'
    target: Target
    # Extracted (float) parameters — the archive payload; None after
    # discard_params().
    params: Optional[Dict[str, Any]]
    _predict: Callable[..., Tuple[jax.Array, FxpStats]] = dataclasses.field(repr=False)
    flash_bytes: int = 0  # read-only parameter memory (paper: flash / HBM)
    sram_bytes: int = 0  # activation scratch (paper: SRAM / VMEM working set)
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict, repr=False)
    # sha256 of the extracted parameter tree (survives discard_params);
    # (fingerprint, target, mesh_key) keys the serving-layer artifact cache.
    fingerprint: str = ""
    # The lowered program (repro.compile.registry.Lowered) the predict was
    # specialized from; specialize_mesh re-specializes it for a device mesh.
    _program: Optional[Any] = dataclasses.field(default=None, repr=False)
    # Mesh specialization (None / 1 / None for single-device artifacts).
    mesh: Optional[Any] = dataclasses.field(default=None, repr=False)
    replicas: int = 1
    mesh_strategy: Optional[str] = None
    # Calibrated per-tensor formats (repro.quant.QuantPlan); None for fixed
    # and float targets.  Rides in the archive and keys the serving cache.
    quant_plan: Optional[Any] = dataclasses.field(default=None, repr=False)
    # Replica health tracker (repro.sharding.ReplicaHealthTracker) for
    # mesh-specialized artifacts on the fused dispatch path; None elsewhere.
    # Surfaced into /v1/stats by the serving router.
    replica_health: Optional[Any] = dataclasses.field(default=None, repr=False)

    @property
    def mesh_key(self) -> Optional[Tuple]:
        """Hashable mesh descriptor for cache keying (None = single-device)."""
        return mesh_descriptor(self.mesh, self.mesh_strategy)

    @property
    def plan_key(self) -> Optional[Tuple]:
        """Hashable QuantPlan descriptor (None = no calibrated plan).

        Part of ``cache_key``: one model compiled for one calibrated Target
        under two *different* calibration batches may legitimately yield two
        different plans — and therefore two different programs — so the plan
        identity must key the serving cache alongside Target and mesh.
        """
        return None if self.quant_plan is None else self.quant_plan.descriptor()

    @property
    def kernel_strategy(self) -> Optional[str]:
        """How the pallas backend dispatched this model's forward pass:
        ``"megakernel"`` (the whole model in one ``pallas_call``),
        ``"per-layer"`` (the fused-layer fallback when the packed weights
        exceed the VMEM budget), or None (backends/lowerings where the
        distinction does not exist)."""
        return self.extras.get("kernel_strategy")

    @property
    def cache_key(self) -> Tuple[str, Target, Optional[Tuple],
                                 Optional[Tuple], Optional[str]]:
        # kernel_strategy is part of the key: the megakernel/per-layer
        # routing depends on ambient state beyond the Target (the VMEM
        # budget override), so two artifacts of one model compiled under
        # different budgets must not alias in the serving cache.
        return (self.fingerprint, self.target, self.mesh_key, self.plan_key,
                self.kernel_strategy)

    @property
    def max_supported_batch(self) -> Optional[int]:
        """Largest batch one predict call accepts (None = unbounded).

        The micro-batching scheduler clamps its bucket ladder to this, so a
        ``batch_policy='fixed'`` artifact is never fed a batch it would
        reject.  A mesh-specialized artifact serves one fixed batch *per
        replica*, so its ceiling scales with the replica count.
        """
        if self.target.batch_policy == "fixed":
            return self.target.batch_size * max(1, self.replicas)
        return None

    def specialize_mesh(self, mesh: Any, strategy: str = "auto") -> "CompiledArtifact":
        """Replica-aware data-parallel artifact over ``mesh`` (new artifact;
        see :func:`repro.compile.api.specialize_mesh` for the strategies)."""
        from .api import specialize_mesh as _specialize_mesh

        return _specialize_mesh(self, mesh, strategy)

    # -- inference -----------------------------------------------------------
    def predict_device(self, x: np.ndarray) -> Any:
        """One dispatch; returns the outputs unforced: a device array that
        JAX may still be computing (or a host array, where the program
        makes one).  The caller forces it with ``np.asarray``."""
        with spans.span("repro.predict.call", rows=len(x)):
            out, _ = self._predict(x)
        return out

    def predict(self, x: np.ndarray) -> np.ndarray:
        out = self.predict_device(x)
        with spans.span("repro.predict.sync", rows=len(x)):
            return np.asarray(out, np.int32)

    def predict_with_stats(self, x: np.ndarray) -> Tuple[np.ndarray, Dict[str, float]]:
        out, stats = self._predict(x)
        total = max(int(stats.total), 1)
        return np.asarray(out, np.int32), {
            "overflow": int(stats.overflow),
            "underflow": int(stats.underflow),
            "total": int(stats.total),
            "overflow_rate": float(int(stats.overflow) / total),
            "underflow_rate": float(int(stats.underflow) / total),
        }

    def pretune(self, example: np.ndarray,
                batches: Optional[Tuple[int, ...]] = None) -> "CompiledArtifact":
        """Warm the kernel block-size tuner and the jit trace cache for the
        serving bucket ladder, ahead of traffic.

        Runs ``predict`` on zero inputs shaped like ``example`` (one row) at
        each batch size in ``batches`` — default: the power-of-two ladder up
        to ``max_supported_batch`` (or 64).  Each call populates the
        autotuner's shape-keyed entry (persisted to the on-disk JSON cache,
        see ``repro.kernels.tune``, device-keyed) and the corresponding jit
        trace, so the first real request in every bucket hits warm caches.
        For megakernel-routed artifacts (``kernel_strategy ==
        "megakernel"``) this warms the whole-model batch-block entries and
        the single-dispatch traces over the same ladder — the serving
        buckets hit the one-``pallas_call`` path warm from the first
        request.

        A mesh-specialized artifact walks the *mesh-level* ladder — replicas
        x the per-replica power-of-two shard ladder (up to the per-replica
        cap) — so every device's shard shape is tuned and every mesh bucket's
        program is traced before traffic.  Returns self.
        """
        row = np.asarray(example)
        if row.ndim > 1:
            row = row[0]
        if batches is None:
            r = max(1, self.replicas)
            top = self.max_supported_batch or 64 * r
            ladder, b = [], r
            while b < top:
                ladder.append(b)
                b *= 2
            batches = tuple(ladder) + (top,)
        for b in batches:
            self.predict(np.zeros((int(b),) + row.shape, row.dtype))
        return self

    # -- C emission ----------------------------------------------------------
    def emit_c(self) -> str:
        """The freestanding C99 translation unit for this artifact.

        Available for any quantized classifier artifact regardless of its
        execution backend (the emit spec rides on the lowered program);
        raises :class:`repro.emit.EmitError` for float targets and the
        ``lm`` lowering.  Emission is pure templating — no C compiler is
        needed (that's only for :meth:`report`'s measured sizes and the
        ``emit`` backend's replay harness).
        """
        from repro import emit as emit_mod

        return emit_mod.emit_artifact_c(self)

    # -- memory model --------------------------------------------------------
    def memory_report(self) -> Dict[str, int]:
        return {"flash": self.flash_bytes, "sram": self.sram_bytes,
                "total": self.flash_bytes + self.sram_bytes}

    def memory_bytes(self) -> Dict[str, int]:
        """Legacy alias for :meth:`memory_report` (EmbeddedModel API)."""
        return self.memory_report()

    def report(self, x: Optional[np.ndarray] = None,
               y: Optional[np.ndarray] = None,
               measure_c: Any = "auto") -> Dict[str, Any]:
        """Paper-style resource report for this artifact.

        Always includes the memory model and the per-tensor number formats
        (the QuantPlan table for calibrated targets, with
        ``chain_frac_bits`` for a calibrated RBF SVM's distance, exponent
        and kernel value; the single global format otherwise).  ``model_bytes`` is computed from the *actual
        quantized tensors* (per-tensor container widths), not a float-size
        estimate.  Given an evaluation batch ``x``, adds the observed
        saturation/underflow counts (paper §V-A); given labels ``y`` as
        well, adds accuracy and the delta vs a float recompile of the same
        parameters (paper Tables V-VII) — that comparison needs the
        retained parameter tree, so it is skipped after
        :meth:`discard_params`.

        ``measure_c`` controls the *measured* footprint (paper Tables
        IV-VI): compile the generated C freestanding and report its real
        ``.text``/``.rodata``/``.data`` section sizes as ``c_sections``
        (with ``model_bytes_measured = flash``).  ``"auto"`` measures for
        ``emit``-backend artifacts when a toolchain exists and silently
        skips otherwise; ``True`` forces measurement (raising without a C
        compiler or for un-emittable artifacts); ``False`` disables it.
        """
        rep: Dict[str, Any] = {
            "kind": self.kind,
            "number_format": self.target.number_format,
            "backend": self.target.backend,
            "model_bytes": self.flash_bytes,
            "sram_bytes": self.sram_bytes,
        }
        want_measure = (measure_c is True
                        or (measure_c == "auto"
                            and self.target.backend == "emit"))
        if want_measure:
            try:
                from repro import emit as emit_mod

                rep["c_sections"] = emit_mod.measure_artifact(self)
                rep["model_bytes_measured"] = rep["c_sections"]["flash"]
            except Exception:
                if measure_c is True:
                    raise
                # auto mode: no toolchain / un-emittable — estimate only.
        if self.quant_plan is not None:
            rep["formats"] = {
                path: repr(self.quant_plan.fmt(path))
                for path in self.quant_plan.paths()}
            rep["calibration_ranges"] = dict(self.quant_plan.ranges)
            if "chain_frac_bits" in self.extras:
                # fractional bits of values that are no plan path, such as
                # the RBF SVM's int32 squared distance
                rep["chain_frac_bits"] = dict(self.extras["chain_frac_bits"])
        elif self.target.is_quantized:
            rep["formats"] = {"*": repr(self.target.fmt)}
        else:
            rep["formats"] = {}
        if x is not None:
            out, stats = self.predict_with_stats(x)
            rep["saturation"] = stats
            if y is not None:
                y = np.asarray(y)
                rep["accuracy"] = float((out == y).mean())
                if self.params is not None and self.target.is_quantized:
                    from .api import compile_from_params

                    flt = compile_from_params(
                        self.kind, self.params,
                        self.target.replace(number_format="flt",
                                            backend="ref"))
                    rep["accuracy_float"] = float(
                        (flt.predict(x) == y).mean())
                    rep["accuracy_delta"] = (rep["accuracy"]
                                             - rep["accuracy_float"])
        return rep

    def discard_params(self) -> "CompiledArtifact":
        """Drop the retained (unquantized) parameter tree to free memory.

        The specialized predict program keeps working (it closes over the
        lowered representation), but :meth:`save` becomes unavailable.
        Useful for long-lived quantized LM artifacts, where the float tree
        would otherwise stay resident alongside the quantized one.
        """
        self.params = None
        return self

    # -- persistence ---------------------------------------------------------
    def save(self, path: str, metadata: Optional[Dict] = None,
             include_c: bool = False) -> None:
        """Write the self-contained archive (paper Fig. 1 'output file').

        ``include_c=True`` additionally embeds the generated freestanding C
        source in the checksummed ``metadata`` member (key ``"emit_c"``) —
        the shippable MCU source travels with the archive that produced it.
        Quantized classifier artifacts only.
        """
        import time

        import msgpack

        if self.params is None:
            raise ValueError(
                "cannot save: parameters were dropped via discard_params(); "
                "recompile the model to obtain a saveable artifact")
        import hashlib

        meta = dict(metadata or {})
        if include_c:
            meta["emit_c"] = self.emit_c()
        members = {
            "kind": self.kind,
            "target": dataclasses.asdict(self.target),
            "params": _encode(self.params),
            # The frozen plan (not the calibration batch): load() must
            # reproduce this artifact bit-for-bit without re-calibrating.
            "quant_plan": (None if self.quant_plan is None
                           else self.quant_plan.to_dict()),
            "metadata": meta,
        }
        # v3: every member is its own msgpack blob, checksummed so load()
        # can prove the bytes it is about to deserialize are the bytes that
        # were saved — weights that rotted in flash fail loudly, not subtly.
        blobs = {name: msgpack.packb(members[name], use_bin_type=True)
                 for name in _ARCHIVE_MEMBERS}
        payload = {
            "format": _ARCHIVE_FORMAT,
            "version": _ARCHIVE_VERSION,
            "members": blobs,
            "integrity": {
                "algo": "sha256",
                "members": {name: hashlib.sha256(blob).hexdigest()
                            for name, blob in blobs.items()},
            },
            "saved_at": time.time(),
        }
        atomic_write_bytes(
            path, compress_bytes(msgpack.packb(payload, use_bin_type=True)))


def _filter_archive_bytes(data: bytes, path: str) -> bytes:
    """Fault-injection hook (``artifact.load`` byte-filter site): the chaos
    harness corrupts archives here to prove the integrity check catches it.
    Lazy import — repro.serve imports repro.compile, not vice versa."""
    try:
        from repro.serve import faults
    except Exception:
        return data
    return faults.filter_bytes("artifact.load", data, name=path)


def load(path: str) -> CompiledArtifact:
    """Load an archive and recompile it into a live artifact.

    The stored parameters are re-run through the quantize/lower/specialize
    stages of the recorded Target, so the loaded artifact predicts
    identically to the one that was saved.

    v3 archives are integrity-checked first: every member blob's sha256
    must match the stored digest before it is deserialized.  Any mismatch
    — or an archive too mangled to decode at all — raises
    :class:`ArtifactIntegrityError`; corrupted weights never load.
    """
    import hashlib

    import msgpack

    from .api import compile_from_params

    with open(path, "rb") as f:
        data = _filter_archive_bytes(f.read(), path)
    try:
        payload = msgpack.unpackb(decompress_bytes(data), raw=False,
                                  strict_map_key=False)
        if not isinstance(payload, dict):
            raise ValueError("archive container is not a map")
    except ArtifactIntegrityError:
        raise
    except Exception as e:
        raise ArtifactIntegrityError(
            f"{path}: archive is not decodable ({e!r}); the file is "
            f"corrupt or truncated") from e
    if payload.get("format") != _ARCHIVE_FORMAT:
        raise ValueError(f"{path} is not a {_ARCHIVE_FORMAT} archive")
    version = payload.get("version", 0)
    if version > _ARCHIVE_VERSION:
        raise ValueError(f"archive version {version} is newer than "
                         f"this reader ({_ARCHIVE_VERSION})")
    if version >= 3:
        blobs = payload.get("members") or {}
        digests = (payload.get("integrity") or {}).get("members") or {}
        fields = {}
        for name in _ARCHIVE_MEMBERS:
            blob = blobs.get(name)
            want = digests.get(name)
            if not isinstance(blob, (bytes, bytearray)) or want is None:
                raise ArtifactIntegrityError(
                    f"{path}: archive member '{name}' is missing or "
                    f"unchecksummed")
            got = hashlib.sha256(blob).hexdigest()
            if got != want:
                raise ArtifactIntegrityError(
                    f"{path}: sha256 mismatch on member '{name}' "
                    f"(stored {want[:12]}…, computed {got[:12]}…); refusing "
                    f"to deserialize a corrupt archive")
            try:
                fields[name] = msgpack.unpackb(bytes(blob), raw=False,
                                               strict_map_key=False)
            except Exception as e:
                raise ArtifactIntegrityError(
                    f"{path}: member '{name}' passed its checksum but is "
                    f"undecodable ({e!r})") from e
    else:
        fields = payload  # v1/v2: members inline, no integrity section
    target = Target(**fields["target"])
    params = _decode(fields["params"])
    plan = None
    if fields.get("quant_plan") is not None:
        from repro.quant import QuantPlan

        plan = QuantPlan.from_dict(fields["quant_plan"])
    return compile_from_params(fields["kind"], params, target, plan=plan)
