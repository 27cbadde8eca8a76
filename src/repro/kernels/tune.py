"""Block-size autotuner for the Pallas kernels (shape/dtype-keyed, disk-cached).

The kernels historically ran with fixed 128/256 block defaults — MXU-aligned,
but hugely wasteful for the paper-scale problems this repo actually serves
(a batch-8 call on a 16-wide MLP layer was padded to a 128x256x128 matmul).
This module picks (bm, bn, bk) per *problem shape bucket* instead:

* **Key** — ``kind|MxKxN|w<bits>|<device>`` where M is rounded up to its
  power-of-two bucket (matching the serving layer's pow2 batch buckets in
  ``serve/batching.py``), so every warm serving bucket shares one cache entry
  and one jit trace.
* **Selection** — on TPU, candidates are swept with a caller-provided
  ``runner`` (wall-time of the real kernel on zero inputs of the padded
  shape; timing is shape- not value-dependent) and the fastest wins.  A
  candidate the compiler refuses is dropped and counted
  (:func:`dropped_candidates`), never silently.  Off TPU (interpret mode —
  CI, laptops) timing is meaningless, so a deterministic cost model picks
  the candidate minimizing padded MACs plus a small per-grid-step overhead
  charge.
* **Cache** — two layers: a process-wide dict, and an on-disk JSON file
  (``$REPRO_TUNE_CACHE`` or ``.repro_cache/tune_cache.json`` in the
  checkout) written atomically on every new entry, so tuning survives
  process restarts and a serving fleet can ship a pre-tuned cache.  Delete
  the file (or point the env var elsewhere) to invalidate;
  ``CompiledArtifact.pretune`` fills it ahead of traffic.

Candidates respect TPU tiling floors (sublane x lane = {8,16,32} x 128 by
container width) when tuning for a real TPU; interpret mode may shrink
blocks all the way to the problem size, since only padded-work waste
matters there.

:func:`on_tpu` is the kernel layer's one platform decision: it picks
Mosaic compilation over interpret mode, lane-padded operands, the TPU tile
floors above and timed tuning.  Everything else takes it as an argument.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.jax_cache import CHECKOUT

__all__ = ["matmul_blocks", "model_block_m", "fleet_blocks", "batch_bucket",
           "pwl_blocks", "pow2ceil", "cache_path", "clear_memory_cache",
           "cache_snapshot", "device_key", "on_tpu", "dropped_candidates",
           "tuning_seconds"]

Blocks = Tuple[int, int, int]
Runner = Callable[[Blocks], float]

# Per-grid-step overhead charge (in MAC-equivalents) for the off-TPU cost
# model: breaks ties toward fewer, larger grid steps.
_STEP_COST = 4096
# VMEM budget for one grid step's working set (a + b + int32 acc + out).
_VMEM_BUDGET = 8 * 1024 * 1024

# Minimum sublane tile per container width on real TPU (lane is always 128).
_TPU_SUBLANE = {32: 8, 16: 16, 8: 32}

_lock = threading.RLock()
_memory: Dict[str, Blocks] = {}
_disk_loaded_from: Optional[str] = None
# Candidates a timed sweep dropped because the compiler (or the run) refused
# them, as "key blocks: error" lines, and the seconds spent sweeping.
_dropped: List[str] = []
_sweep_s = [0.0]


def on_tpu() -> bool:
    """True when the kernels compile for the TPU with Mosaic.

    The single platform decision of the kernel layer: interpret mode,
    lane padding, the tuner's tile floors and timed sweeps all follow it.
    Helpers that shape operands take the answer as an argument, so a test
    can build exactly the shapes the chip compiles.
    """
    return jax.default_backend() == "tpu"


def dropped_candidates() -> List[str]:
    """Every candidate a timed sweep dropped in this process, with why."""
    with _lock:
        return list(_dropped)


def tuning_seconds() -> float:
    """Wall seconds this process spent in timed tuning sweeps."""
    return _sweep_s[0]


# --------------------------------------------------------------------------
# shape bucketing
# --------------------------------------------------------------------------
def pow2ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(0, (int(n) - 1).bit_length())


def device_key(device=None) -> str:
    """Cache-key component naming the hardware a tuning entry was measured on.

    ``platform:device_kind`` (e.g. ``cpu:cpu``, ``tpu:TPU_v4``) — block
    timings transfer between devices of the same kind but not across
    hardware generations, so a mesh of mixed fleets (or a pre-tuned cache
    shipped to a different pod) never serves a foreign device's blocks.
    ``device`` defaults to the default jax device — the one the kernels
    dispatch (and the tuner times) on.
    """
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or device.platform
    return f"{device.platform}:{kind}".replace(" ", "_")


def batch_bucket(b: int, cap: int = 256) -> int:
    """Round a batch up to its power-of-two bucket, capped.

    Matches ``serve/batching.py``'s pow2 bucket ladder so a kernel blocked
    on the bucketed batch is only ever traced once per warm bucket.
    """
    return min(int(cap), pow2ceil(max(1, int(b))))


def pwl_blocks(n_elements: int) -> Tuple[int, int]:
    """(block_rows, block_cols) for an n-element flattened PWL activation.

    Sized to the input: small calls get one small grid step (a batch-1 MLP
    activation pads to at most one 128-lane row, not the historical fixed
    256x512 = 131k-element grid), large calls get the full 256x512 tile.
    """
    n = max(1, int(n_elements))
    cols = 512 if n >= 4096 else 128
    rows = -(-n // cols)
    return min(256, pow2ceil(rows)), cols


# --------------------------------------------------------------------------
# disk cache
# --------------------------------------------------------------------------
def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNE_CACHE",
        os.path.join(CHECKOUT, ".repro_cache", "tune_cache.json"))


def _merge_disk_into_memory(path: str) -> None:
    """Fold valid on-disk entries into memory (in-memory entries win)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return  # absent or corrupt cache: retune from scratch
    for key, val in raw.items():
        if (isinstance(val, list) and len(val) == 3
                and all(isinstance(v, int) and v > 0 for v in val)):
            _memory.setdefault(key, tuple(val))


def _load_disk() -> None:
    """Merge the on-disk cache into memory (once per distinct path)."""
    global _disk_loaded_from
    path = cache_path()
    if _disk_loaded_from == path:
        return
    _disk_loaded_from = path
    _merge_disk_into_memory(path)


@contextlib.contextmanager
def _save_lock(path: str):
    """Advisory cross-process lock serializing read-merge-replace cycles.

    ``os.replace`` alone makes each write atomic, but the *union* needs the
    whole read-merge-write window exclusive: a sibling process whose entries
    land between our read and our replace would be clobbered.  Posix flock
    on a sidecar file; platforms without fcntl fall back to lock-free
    best-effort (the pre-existing behavior)."""
    try:
        import fcntl
    except ImportError:  # non-posix: keep best-effort semantics
        yield
        return
    with open(f"{path}.lock", "a+") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lockf, fcntl.LOCK_UN)


def _save_disk() -> None:
    """Best-effort atomic rewrite of the disk cache from memory.

    Re-merges the current on-disk content first — under a cross-process
    file lock — so concurrent writers (sibling processes in a serving
    fleet) union their entries instead of clobbering each other
    (last-writer-wins only applies per key, which is harmless — both
    writers tuned the same shape).

    Must be called WITHOUT ``_lock`` held: the flock can block on a slow
    sibling's disk I/O, and warm in-memory lookups must never wait behind
    it.  ``_lock`` is taken only for the brief merge + snapshot.
    """
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with _save_lock(path):
            with _lock:
                _merge_disk_into_memory(path)
                snapshot = dict(_memory)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({k: list(v) for k, v in sorted(snapshot.items())},
                          f, indent=0)
            os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc.: tuning still works, just not persisted


def clear_memory_cache() -> None:
    """Drop the in-process cache (tests; forces a disk reload / retune)."""
    global _disk_loaded_from
    with _lock:
        _memory.clear()
        _disk_loaded_from = None


def cache_snapshot() -> Dict[str, Blocks]:
    with _lock:
        return dict(_memory)


# --------------------------------------------------------------------------
# candidate generation + selection
# --------------------------------------------------------------------------
def _pow2s_upto(cap: int, floor: int) -> List[int]:
    out, v = [], floor
    while v <= cap:
        out.append(v)
        v *= 2
    return out or [floor]


def candidates(m: int, k: int, n: int, bits: int,
               on_tpu: bool) -> List[Blocks]:
    """Feasible (bm, bn, bk) sets for an MxKxN matmul in a ``bits`` container.

    Off TPU blocks may shrink to the (pow2-bucketed) problem dims; on TPU
    they are floored at the Mosaic sublane/lane tile for the dtype.
    """
    ebytes = bits // 8
    if on_tpu:
        bm_floor, lane = _TPU_SUBLANE[bits], 128
    else:
        bm_floor, lane = 1, 1
    bms = _pow2s_upto(min(128, pow2ceil(m)), min(bm_floor, 128))
    bns = _pow2s_upto(min(256, pow2ceil(n)), min(lane, 256))
    bks = _pow2s_upto(min(512, pow2ceil(k)), min(lane, 512))
    out = []
    for bm in bms:
        for bn in bns:
            for bk in bks:
                vmem = (bm * bk + bk * bn) * ebytes + bm * bn * (4 + ebytes)
                if vmem <= _VMEM_BUDGET:
                    out.append((bm, bn, bk))
    return out


def _model_cost(m: int, k: int, n: int, blocks: Blocks) -> float:
    bm, bn, bk = blocks
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    steps = (mp // bm) * (np_ // bn) * (kp // bk)
    return mp * kp * np_ + steps * _STEP_COST


def _sweep(key: str, cands: list, runner: Callable):
    """The fastest candidate by ``runner`` wall time, or None when every
    candidate was refused.  Refusals are recorded, not swallowed.  A lone
    candidate is returned untimed: there is nothing to choose."""
    if len(cands) == 1:
        return cands[0]
    t0 = time.perf_counter()
    best, best_t = None, float("inf")
    for cand in cands:
        try:
            t = runner(cand)
        except Exception as e:
            msg = (str(e).strip().splitlines() or [""])[0][:200]
            with _lock:
                _dropped.append(f"{key} {cand}: {type(e).__name__}: {msg}")
            continue
        if t < best_t:
            best, best_t = cand, t
    with _lock:
        _sweep_s[0] += time.perf_counter() - t0
    return best


def _choose(key: str, m: int, k: int, n: int, bits: int,
            runner: Optional[Runner]) -> Blocks:
    chip = on_tpu()
    cands = candidates(m, k, n, bits, chip)
    if chip and runner is not None:
        best = _sweep(key, cands, runner)
        if best is not None:
            return best
    # Deterministic fallback (and the only path off-TPU).
    return min(cands, key=lambda blk: (_model_cost(m, k, n, blk),
                                       -blk[0] * blk[1] * blk[2]))


# --------------------------------------------------------------------------
# public lookup
# --------------------------------------------------------------------------
def matmul_blocks(kind: str, m: int, k: int, n: int, bits: int,
                  runner: Optional[Runner] = None) -> Blocks:
    """Tuned (bm, bn, bk) for a ``kind`` matmul of logical shape MxKxN.

    M is bucketed to its power of two (serving batch ladder) before keying;
    the first lookup per key tunes and persists, later lookups are a dict
    hit — including across processes via the JSON disk cache.  Entries are
    keyed by the *dispatching* device's hardware kind (see
    :func:`device_key`): replica-sharded serving on a homogeneous mesh
    tunes once per shard shape, and a cache shipped to different hardware
    never serves a foreign generation's blocks.  (There is deliberately no
    way to tune *for* another device than the one the runner measures on —
    a mislabeled timing is worse than a retune.)
    """
    mb = batch_bucket(m, cap=1 << 30)
    key = f"{kind}|{mb}x{int(k)}x{int(n)}|w{int(bits)}|{device_key()}"
    with _lock:
        hit = _memory.get(key)
        if hit is not None:
            return hit
        _load_disk()
        hit = _memory.get(key)
        if hit is not None:
            return hit
    # Tune outside the lock: an on-TPU sweep compiles and times dozens of
    # candidates, and holding the lock through it would stall every other
    # thread's warm dict hit.  A concurrent miss on the same key tunes
    # twice and stores the same (deterministic off-TPU) answer — harmless.
    blocks = _choose(key, mb, int(k), int(n), int(bits), runner)
    with _lock:
        blocks = _memory.setdefault(key, blocks)
    _save_disk()  # outside _lock: the cross-process flock must not stall hits
    return blocks


def model_block_m(kind: str, m: int, dims: Tuple[int, ...], bits: int,
                  vmem_bytes: Optional[Callable[[int], float]] = None,
                  budget: Optional[int] = None,
                  runner: Optional[Callable[[int], float]] = None) -> int:
    """Tuned batch block ``bm`` for a whole-model megakernel dispatch.

    A megakernel's only grid axis is the batch (weights ride whole — see
    ``repro.kernels.fxp_model``), so the tuning problem collapses to one
    knob: how many batch rows per grid step.  Keys like
    :func:`matmul_blocks` (pow2-bucketed M, the model's dim signature, the
    container width, the dispatching device) and shares the same two-layer
    cache, storing ``(bm, 1, 1)`` so the disk format stays uniform.

    ``vmem_bytes(bm)`` (optional) bounds candidates to the VMEM ``budget``;
    on TPU with a ``runner`` the survivors are wall-time swept, otherwise
    the largest feasible block wins — M is already bucketed to a power of
    two, so growing ``bm`` never adds padding, it only removes grid steps.
    """
    mb = batch_bucket(m, cap=1 << 30)
    sig = "x".join(str(int(d)) for d in dims)
    key = f"model-{kind}|{mb}|d{sig}|w{int(bits)}|{device_key()}"
    with _lock:
        hit = _memory.get(key)
        if hit is None:
            _load_disk()
            hit = _memory.get(key)
        if hit is not None:
            return int(hit[0])
    chip = on_tpu()
    floor = _TPU_SUBLANE[int(bits)] if chip else 1
    cap = max(floor, min(128, pow2ceil(mb)))
    cands = _pow2s_upto(cap, floor)
    if vmem_bytes is not None:
        limit = _VMEM_BUDGET if budget is None else budget
        fitting = [bm for bm in cands if vmem_bytes(bm) <= limit]
        cands = fitting or cands[:1]  # callers gate on the fit predicate
    bm = cands[-1]
    if chip and runner is not None:
        bm = _sweep(key, cands, runner) or bm
    with _lock:
        got = _memory.setdefault(key, (int(bm), 1, 1))
    _save_disk()
    return int(got[0])


def fleet_blocks(kind: str, n_models: int, m: int, dims: Tuple[int, ...],
                 bits: int, uniform: bool = True,
                 vmem_bytes: Optional[Callable[[int, int], float]] = None,
                 budget: Optional[int] = None,
                 runner: Optional[Callable[[Tuple[int, int]], float]] = None,
                 ) -> Tuple[int, int]:
    """Tuned (be, bm) for a fleet-stacked megakernel dispatch.

    A fleet dispatch has two grid axes — model blocks of ``be`` stacked
    members and batch blocks of ``bm`` rows — so the tuning problem is a
    2-D sweep bounded by ``vmem_bytes(be, bm) <= budget``.  Heterogeneous
    fleets (``uniform=False``: members froze distinct layer schedules) pin
    ``be = 1`` — the kernel switches per-model static branches by grid
    index and cannot batch the dot across models.  Keys carry the fleet
    size, uniformity, the pow2-bucketed batch, the member dim signature,
    the container width, and the dispatching device; stored as
    ``(be, bm, 1)`` so the disk format stays uniform with the other kinds.

    Off TPU the deterministic cost model minimizes padded work plus a
    per-grid-step charge; on TPU with a ``runner`` the feasible pairs are
    wall-time swept like :func:`matmul_blocks`.
    """
    e = max(1, int(n_models))
    mb = batch_bucket(m, cap=1 << 30)
    sig = "x".join(str(int(d)) for d in dims)
    key = (f"fleet-{kind}|E{e}|u{int(bool(uniform))}|{mb}|d{sig}"
           f"|w{int(bits)}|{device_key()}")
    with _lock:
        hit = _memory.get(key)
        if hit is None:
            _load_disk()
            hit = _memory.get(key)
        if hit is not None:
            return int(hit[0]), int(hit[1])
    chip = on_tpu()
    floor = _TPU_SUBLANE[int(bits)] if chip else 1
    bms = _pow2s_upto(max(floor, min(128, pow2ceil(mb))), floor)
    bes = ([b for b in _pow2s_upto(pow2ceil(e), 1) if b <= e]
           if uniform else [1])
    limit = _VMEM_BUDGET if budget is None else budget
    cands = [(be, bm) for be in bes for bm in bms
             if vmem_bytes is None or vmem_bytes(be, bm) <= limit]
    if not cands:
        cands = [(1, bms[0])]  # callers gate on the fleet fit predicate
    # Per-row MAC weight of one stacked member: the matmul chain over dims.
    row_macs = max(1, sum(i * o for i, o in zip(dims, dims[1:])))

    def _cost(cand: Tuple[int, int]) -> float:
        be, bm = cand
        ep = -(-e // be) * be
        mp = -(-mb // bm) * bm
        steps = (ep // be) * (mp // bm)
        return ep * mp * row_macs + steps * _STEP_COST

    be, bm = min(cands, key=lambda c: (_cost(c), -(c[0] * c[1])))
    if chip and runner is not None:
        be, bm = _sweep(key, cands, runner) or (be, bm)
    with _lock:
        got = _memory.setdefault(key, (int(be), int(bm), 1))
    _save_disk()
    return int(got[0]), int(got[1])
