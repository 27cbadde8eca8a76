"""Fleet coalescing: many endpoints' micro-batches, ONE stacked dispatch.

The :class:`FleetCoalescer` takes over scheduling for a set of endpoints
whose artifacts share a :func:`repro.compile.fleet_signature` — their
:class:`~repro.serve.batching.MicroBatcher` workers are detached and one
coalescer thread drains all their queues.  Each round it gathers every
member's pending micro-batch, writes them into slots of a preallocated
``(E, bucket, F)`` staging buffer (double-buffered, like the per-endpoint
zero-copy path), and launches the fleet's single stacked Pallas dispatch
(:class:`repro.compile.FleetStack`).  Outputs are scattered back to each
member's futures bit-identically to that member's own golden vectors — the
stack's slot-isolation contract.

Per-endpoint semantics are preserved, not flattened:

* **degradation** — a member whose precision governor says "degraded"
  leaves the round and is served by its own dispatch path (the fallback
  artifact), exactly as without coalescing;
* **circuit breaking** — a member with a non-closed breaker serves solo so
  its probe dispatches feed its own breaker; successful stacked rounds
  record success on every riding member's breaker;
* **fault isolation** — a stacked dispatch failure falls back to
  per-member serving (retries, poison bisection and all); one member's
  malformed rows never fail another member's round.  Every such fallback,
  and every warmup trace that failed, is counted in :meth:`snapshot`
  (``stack_fallbacks``, ``warmup_failures``): a stack that never runs must
  not look like a working fleet.

Overlap: the stacked dispatch is launched *asynchronously* (JAX async
dispatch — ``FleetStack.predict_device`` returns an unmaterialized device
array), so round t computes on the device while round t+1's first sweep
runs.  Where round t+1 holds for stragglers, round t is finalized
(materialized, scattered to its futures) at the start of that hold and
only what is left of the hold is slept: the hold, idle time anyway, does
the materialization, and round t's callers do not wait through round
t+1's hold.  A round that does not hold (a full stack, a lone rider)
launches first and finalizes round t after, so the sync overlaps its
compute.

The coalescer runs on the micro-batcher's scheduling core
(:class:`repro.serve.batching.SchedulerCore`): each member's queue is
drained by :meth:`MicroBatcher.gather`, and the staging store, the
:class:`~repro.serve.batching.Launch` record of the round in flight, the
force and the per-rider scatter are the ones the micro-batcher uses.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from repro import spans

from .batching import Launch, SchedulerCore

__all__ = ["FleetCoalescer"]

# How long an idle coalescer sleeps between sweeps unless a submit wakes it.
_IDLE_WAIT_S = 0.05


class FleetCoalescer(SchedulerCore):
    """Single-threaded cross-endpoint scheduler over one FleetStack.

    ``endpoints`` are the member :class:`~repro.serve.router.Endpoint`\\ s
    in *slot order* — ``endpoints[e]``'s artifact must be member ``e`` of
    ``stack``.  Construction detaches each member's internal worker; the
    members' ``submit`` APIs keep working unchanged, served by this thread.
    """

    def __init__(self, stack, endpoints):
        if len(endpoints) != stack.n_models:
            raise ValueError(f"{len(endpoints)} endpoints for a "
                             f"{stack.n_models}-model stack")
        super().__init__()
        self.stack = stack
        self.members = list(endpoints)
        # Fill hold: when a round collects some but not all members, wait
        # this long for stragglers before dispatching — a narrow stack
        # wastes the dispatch the whole design exists to amortize.  The
        # members' own max_wait is the latency budget their callers
        # already accepted, so its minimum adds no new tail.
        self._hold_s = min(ep.policy.max_wait_ms for ep in endpoints) / 1e3
        self._event = threading.Event()
        self._closed = False
        self._warmed = False
        self._inflight: Optional[Launch] = None  # launched, not finalized
        # Round accounting (single-writer: the coalescer thread).
        self.n_rounds = 0              # stacked rounds launched
        self.n_stacked_dispatches = 0  # == n_rounds unless a launch raised
        self.n_stacked_requests = 0
        self.n_solo_batches = 0        # member batches served per-endpoint
        self.n_stack_fallbacks = 0     # stacked rounds re-served per member
        self.n_warmup_failures = 0     # warmup traces that raised
        self.n_hold_finalizes = 0      # rounds finalized in the next hold
        for ep in self.members:
            ep.batcher.detach_worker()
            ep.batcher.on_enqueue = self._event.set
        self._worker = threading.Thread(
            target=self._run, name="fleet-coalescer", daemon=True)
        self._worker.start()

    # -- lifecycle -----------------------------------------------------------
    def close(self, timeout: Optional[float] = None) -> None:
        """Stop the coalescer thread and finalize any in-flight round.

        Members' queues are NOT drained here — closing their batchers
        (``Endpoint.close`` / ``ModelRouter.close``) serves what remains on
        the closing thread, exactly as for a detach-free endpoint.
        """
        if self._closed:
            return
        self._closed = True
        self._event.set()
        self._worker.join(timeout)
        self._finalize_pending()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def snapshot(self) -> dict:
        return {"members": [ep.name for ep in self.members],
                "rounds": self.n_rounds,
                "stacked_dispatches": self.n_stacked_dispatches,
                "stacked_requests": self.n_stacked_requests,
                "solo_batches": self.n_solo_batches,
                "stack_fallbacks": self.n_stack_fallbacks,
                "warmup_failures": self.n_warmup_failures,
                "hold_finalizes": self.n_hold_finalizes,
                "staging_allocs": self.n_staging_allocs,
                "assembly_s": self.assembly_s,
                "device_s": self.device_s}

    # -- round machinery -----------------------------------------------------
    def _warmup(self) -> None:
        """Trace the stacked program over the shared bucket ladder before
        the first live round — and every member's own solo ladder too: a
        member can leave the stack at any moment (degradation engages, a
        breaker trips, a malformed row), and its first solo batch must not
        eat a full ladder of cold traces mid-traffic."""
        shape = (self.stack.n_models, self.stack.n_features)
        for b in self.members[0].policy.buckets():
            try:
                np.asarray(self.stack.predict_device(
                    np.zeros((shape[0], b, shape[1]), np.float32)))
            except Exception:
                # Live rounds surface the error with fallback; counted.
                self.n_warmup_failures += 1
        example = np.zeros((1, shape[1]), np.float32)
        for ep in self.members:
            self.n_warmup_failures += ep.batcher.warm(example)
        self._warmed = True

    def _serve_solo(self, ep, batch: list) -> None:
        """One member's batch through its own dispatch path (degradation,
        breaker feed, retries, bisection — unchanged semantics), which
        resolves every future of it."""
        ep.batcher.serve(batch)
        self.n_solo_batches += 1

    def _round(self) -> bool:
        """Collect/dispatch one coalescing round; True if any work moved."""
        with spans.span("repro.fleet.round") as rnd:
            return self._coalesce(rnd)

    def _coalesce(self, rnd) -> bool:
        """The round under its ``repro.fleet.round`` span ``rnd``."""
        stacked: List[tuple] = []  # (slot, ep, batch, rows)
        solo: List[tuple] = []
        def collect(skip=()):
            with spans.span("repro.fleet.collect"):
                for slot, ep in enumerate(self.members):
                    if slot in skip:
                        continue
                    batch = ep.batcher.gather(rnd.id)
                    if not batch:
                        continue
                    rows = sum(r.x.shape[0] for r in batch)
                    if ep.fleet_route():
                        stacked.append((slot, ep, batch, rows))
                    else:
                        solo.append((ep, batch))

        collect()
        if 2 <= len(stacked) < len(self.members) and self._hold_s > 0:
            # Partial stack: hold briefly for stragglers, then sweep once
            # more.  The window runs from the end of the first sweep; the
            # round in flight is finalized inside it, so its callers are
            # answered now and not after this round's launch.
            deadline = time.perf_counter() + self._hold_s
            if self._finalize_pending(in_hold=True):
                self.n_hold_finalizes += 1
            left = deadline - time.perf_counter()
            if left > 0:
                with spans.span("repro.fleet.hold"):
                    time.sleep(left)
            collect(skip={slot for slot, _, _, _ in stacked})
        if rnd.id:
            requests = (sum(len(b) for _, _, b, _ in stacked)
                        + sum(len(b) for _, b in solo))
            rnd.set(riders=len(stacked), requests=requests)
        if not stacked and not solo:
            # Idle: nothing can overlap with the in-flight round — force it
            # out so its callers are not held hostage to future traffic.
            self._finalize_pending()
            return False
        for ep, batch in solo:
            self._serve_solo(ep, batch)
        if len(stacked) < 2:
            # A lone rider gains nothing from the stack (the E-wide dispatch
            # would compute E-1 idle slots); its own path is strictly better.
            for _, ep, batch, _ in stacked:
                self._serve_solo(ep, batch)
            self._finalize_pending()
            return True
        if not self._warmed:
            self._warmup()
        bucket = max(ep.policy.bucket_for(rows)
                     for _, ep, _, rows in stacked)
        rnd.set(bucket=bucket)
        t0 = self._clock()
        riders: List[tuple] = []
        with spans.span("repro.fleet.assemble"):
            buf = self._staging_buffer(
                (self.stack.n_models, bucket, self.stack.n_features),
                np.float32)
            for slot, ep, batch, rows in stacked:
                try:
                    off = 0
                    for r in batch:
                        n = r.x.shape[0]
                        buf[slot, off:off + n] = r.x
                        off += n
                    buf[slot, rows:bucket] = 0
                except Exception:
                    # Malformed rows (shape/dtype) fail alone on the
                    # member's own path (bisection isolates the poison
                    # request); the slot's half-written data is simply
                    # never scattered.
                    self._serve_solo(ep, batch)
                    continue
                riders.append((slot, ep, batch, rows))
        if not riders:
            self._finalize_pending()
            return True
        t1 = self._clock()
        try:
            with spans.span("repro.fleet.launch"):
                out = self.stack.predict_device(buf)  # async: NOT materialized
        except Exception:
            self.n_stack_fallbacks += 1
            for _, ep, batch, _ in riders:
                self._serve_solo(ep, batch)
            self._finalize_pending()
            return True
        self.assembly_s += t1 - t0
        self.n_rounds += 1
        self.n_stacked_dispatches += 1
        # Pipeline depth 1: hand the new round to the device FIRST, then
        # finalize the previous one (unless the hold already did) — round
        # t's materialization wait runs while round t+1 computes.
        self._finalize_pending(launched=Launch(riders, bucket, buf, t1,
                                               out=out, taker=rnd.id))
        return True

    def _finalize_pending(self, in_hold: bool = False,
                          launched: Optional[Launch] = None) -> bool:
        """Force the round in flight, if any, and scatter each rider's
        outputs to its futures; ``launched`` takes its place.  True if
        there was one, and then every rider's future has resolved.
        ``in_hold``: run inside the next round's straggler hold."""
        fl, self._inflight = self._inflight, launched
        if fl is None:
            return False
        with spans.span("repro.fleet.finalize", round=fl.taker,
                        in_hold=int(in_hold)):
            try:
                y = self._force(fl, self.stack.n_models * fl.bucket)
            except Exception:
                # Deferred device failure: the whole round recomputes on
                # the members' own paths (retry/bisection semantics
                # included).
                self.n_stack_fallbacks += 1
                for _, ep, batch, _ in fl.batch:
                    self._serve_solo(ep, batch)
                return True
            done = self._clock()
            for slot, ep, batch, rows in fl.batch:
                if ep.breaker is not None:
                    ep.breaker.record_success()
                self.n_stacked_requests += len(batch)
                meta = {"coalesced": True, "degraded": False,
                        "number_format": ep.artifact.target.number_format}
                self._scatter(batch, y[slot], rows, fl.bucket, meta, done,
                              ep.stats.record_batch)
        return True

    def _run(self) -> None:
        while True:
            if self._closed:
                self._finalize_pending()
                return
            try:
                moved = self._round()
            except BaseException:  # pragma: no cover - belt and braces
                moved = False
            if not moved:
                self._event.wait(_IDLE_WAIT_S)
                self._event.clear()
