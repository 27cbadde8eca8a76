"""Dynamic micro-batching: the scheduler between request queue and artifact.

Requests (one or a few rows each) are enqueued from any thread; a single
worker drains the queue into micro-batches bounded by ``max_batch`` rows and
``max_wait_ms`` of queueing delay, pads each batch up to a power-of-two
*bucket* so the jitted/pallas predict program only ever sees a small closed
set of batch shapes (one trace per bucket, warmed up eagerly), runs the
artifact once per micro-batch, and scatters the per-row results back to the
callers' futures.

Padding uses zero rows and is sliced off before results are returned —
every lowering is row-independent, so padding can never perturb a real
row's prediction (the batch-invariance property tests assert exactly this).

Fault tolerance (see :mod:`repro.serve.reliability`):

* **deadlines** — ``submit(x, timeout_s=...)`` attaches a deadline; a
  request that expires while queued is resolved with
  :class:`DeadlineExceeded` and *skipped* when batches form — never
  dispatched, never holding up live batchmates.
* **bounded retry** — a dispatch that raises a :class:`TransientError` is
  retried under the endpoint's :class:`RetryPolicy` (exponential backoff +
  jitter over an injectable clock/sleep).
* **poison-batch bisection** — a batch whose dispatch keeps failing is
  split in halves and the halves retried, recursively: the offending
  request(s) fail alone with a structured :class:`DispatchError`
  (``isolated=True``) while their batchmates are served normally —
  bit-identically, because rows are independent and every sub-batch pads
  to a warmed bucket.  A single poison request in a batch of n costs
  O(log n) extra dispatches.
* **worker survival** — no exception (predict, concatenation of
  incompatible rows, a cancelled future) can kill the worker loop: every
  future of the affected batch resolves with a structured error and the
  loop keeps serving.

Pipelining: ``predict`` launches a batch and may return its outputs
unforced (JAX async dispatch); the worker forces them with ``np.asarray``.
Where a whole ``max_batch`` bucket of rows is already queued behind a
launched batch, the worker leaves it unforced, gathers and launches the
next batch, and only then forces and scatters the first: the host's call,
the kernel and the copy back of one batch run while the next batch's
input copies.  Otherwise it forces the batch at once.  At most one batch
is ever unforced.  A pipelined batch whose launch or force raises has
spent its first dispatch attempt and goes on, blocking, down the retry
and bisection path.

One scheduling core serves this worker and the fleet coalescer
(:mod:`repro.serve.fleet`): :meth:`MicroBatcher.gather` is the one gather
loop (the worker waits in it behind the request it blocked for, the
coalescer does not wait), and :class:`SchedulerCore` holds the staging
store, the :class:`Launch` record, the force and the scatter.  Each
scheduler keeps its own rule for leaving a launch unforced.
"""

from __future__ import annotations

import dataclasses
import queue
import random
import threading
import time
import zlib
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro import spans

from .reliability import DeadlineExceeded, DispatchError, RetryPolicy

__all__ = ["BatchingPolicy", "MicroBatcher"]


@dataclasses.dataclass(frozen=True)
class BatchingPolicy:
    """Scheduler knobs for one endpoint.

    * ``max_batch``   — row budget of one micro-batch (and the top bucket).
    * ``max_wait_ms`` — how long the first request of a batch may wait for
      company before the batch is dispatched anyway.
    * ``eager_when_idle`` — dispatch a partial batch immediately when the
      queue runs dry instead of idling out the full ``max_wait_ms``: under
      load the queue stays non-empty and batches fill anyway, while a lone
      sequential client is not taxed the wait on every request.  Disable to
      always hold for ``max_wait_ms`` (maximum fill under slow open-loop
      arrivals, at a latency cost).
    * ``warmup``      — trace every bucket with zero rows before the first
      micro-batch is served (triggered lazily by the first request, which
      supplies the row shape and therefore absorbs the trace latency;
      subsequent requests never hit an untraced bucket).
    * ``replicas``    — data-parallel replica count of the endpoint's
      artifact (set automatically by :class:`repro.serve.router.Endpoint`
      from ``CompiledArtifact.replicas``).  The bucket ladder becomes
      *replica-aware*: every bucket is ``replicas`` x a power-of-two shard,
      so a mesh-specialized artifact always hands each device the same
      tuned pow2 shard the single-device path serves.
    """

    max_batch: int = 64
    max_wait_ms: float = 2.0
    eager_when_idle: bool = True
    warmup: bool = True
    replicas: int = 1

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")

    def buckets(self) -> Tuple[int, ...]:
        """The closed set of batch shapes predict will be called with:
        powers of two (times ``replicas``) up to ``max_batch``."""
        out, b = [], min(self.replicas, self.max_batch)
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return tuple(out)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding ``n`` rows."""
        for b in self.buckets():
            if b >= n:
                return b
        return self.max_batch

    def clamped(self, max_supported: Optional[int]) -> "BatchingPolicy":
        """Respect an artifact's fixed-batch ceiling (see
        ``CompiledArtifact.max_supported_batch``)."""
        if max_supported is None or self.max_batch <= max_supported:
            return self
        return dataclasses.replace(self, max_batch=max_supported)

    def with_replicas(self, replicas: int,
                      align_top: bool = True) -> "BatchingPolicy":
        """Replica-aware variant of this policy (no-op when it matches).

        ``align_top`` rounds ``max_batch`` up to ``replicas * pow2`` so the
        top bucket is exactly a replica-aligned shard set — otherwise a full
        dispatch on a non-power-of-two replica count would be silently
        re-padded inside the mesh artifact (e.g. 64 rows on 6 replicas pad
        to 96: computed shape 96, warmed/traced shape 64, up to ~50% padded
        work on the busiest bucket).  Callers whose artifact has a hard
        batch ceiling (fixed batch policy — already replica-aligned by
        construction) pass ``align_top=False``.
        """
        replicas = max(1, int(replicas))
        if replicas == self.replicas:
            return self
        max_batch = self.max_batch
        if align_top and replicas > 1:
            per = -(-max_batch // replicas)
            max_batch = replicas * (1 << max(0, (per - 1).bit_length()))
        return dataclasses.replace(self, replicas=replicas,
                                   max_batch=max_batch)


@dataclasses.dataclass
class _Request:
    x: np.ndarray  # (n, ...) rows
    future: Future
    t_enqueue: float
    deadline: Optional[float] = None  # absolute, on the batcher's clock
    # (request id, perf_counter at enqueue) while spans are on, else None:
    # the start of the request's ``repro.request.queue`` interval.
    queued: Optional[Tuple[int, float]] = None


@dataclasses.dataclass(slots=True)
class Launch:
    """A batch, or a fleet round, handed to its program whose outputs are
    not forced yet."""
    batch: list  # requests; a round's (slot, endpoint, requests, rows)
    bucket: int
    x: np.ndarray  # the padded input: (bucket, ...); a round's (E, bucket, F)
    t_call: float  # on the owner's clock: the start of its device_s
    rows: int = 0  # real rows of a batch (a round's riders carry their own)
    out: Any = None
    meta: Optional[dict] = None
    taker: int = 0  # span id of the fleet round that launched it


def _taken(req: _Request, taker: int) -> None:
    """Record ``req``'s wait in the queue, ending now, under the span id
    of the batch or fleet round that took it."""
    if req.queued is not None:
        rid, t = req.queued
        spans.interval("repro.request.queue", t, time.perf_counter(),
                       request=rid, taker=taker)


def _fail(fut: Future, exc: BaseException) -> None:
    """Resolve a future with an exception, tolerating cancelled/raced
    futures — resolving a batch must never abort mid-scatter."""
    try:
        fut.set_exception(exc)
    except BaseException:
        pass


# detach_worker() wake-up sentinel: wakes the worker's blocking wait so it
# exits, leaving the batcher open for an external scheduler (the coalescer).
_DETACH = object()


# on_batch(n_requests, n_rows, bucket, per-request latencies in seconds,
#          meta=batch metadata dict or None)
OnBatch = Callable[[int, int, int, Sequence[float]], None]
# on_dispatch(ok: bool, exc) — one call per dispatch *attempt* (the circuit
# breaker's outcome feed; retries and bisection sub-dispatches each count)
OnDispatch = Callable[[bool, Optional[BaseException]], None]


class SchedulerCore:
    """What the micro-batcher and the fleet coalescer share below their
    gather: one clock, the staging store, the force and the scatter.

    Either scheduler leaves at most one launch unforced while it assembles
    the next (the micro-batcher while a full bucket is queued behind it,
    the coalescer until the next round's launch or inside its hold), so
    two staging buffers per shape are enough: with JAX async dispatch the
    unforced launch may still be copying its input to the device, and the
    batch being assembled never writes the buffer it was handed.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock or time.perf_counter
        self._staging: dict = {}  # (shape, dtype) -> [buffer, buffer]
        self.n_staging_allocs = 0  # staging buffers ever allocated
        self.assembly_s = 0.0  # host batch-assembly time
        self.device_s = 0.0  # each launch's call to its forced outputs

    def _staging_buffer(self, shape: tuple, dtype) -> np.ndarray:
        """The next of the two staging buffers of ``shape`` and ``dtype``,
        used alternately and allocated once: the steady state writes rows
        into long-lived buffers, not a fresh pad per dispatch."""
        pair = self._staging.get((shape, dtype))
        if pair is None:
            pair = [np.zeros(shape, dtype), np.zeros(shape, dtype)]
            self._staging[shape, dtype] = pair
            self.n_staging_allocs += 2
        pair.reverse()
        return pair[0]

    def _force(self, fl: Launch, rows: int) -> np.ndarray:
        """Wait for a launch's outputs: the one place either scheduler
        syncs with the device.  ``rows`` (padding included) names the sync
        span's input; device_s runs from the launch's call to here."""
        with spans.span("repro.predict.sync", rows=rows):
            y = np.asarray(fl.out)
        self.device_s += self._clock() - fl.t_call
        return y

    @staticmethod
    def _scatter(batch: list, y, rows: int, bucket: int,
                 meta: Optional[dict], done: float,
                 on_batch: Optional[OnBatch]) -> None:
        """Resolve the requests of a forced batch from its outputs ``y``.

        Stats are recorded BEFORE the futures resolve: a caller woken by
        its result (e.g. an HTTP client that immediately queries
        /v1/stats) must already see the batch that served it counted; the
        meta is stamped before the result for the same reason."""
        if on_batch is not None:
            try:
                on_batch(len(batch), rows, bucket,
                         [done - r.t_enqueue for r in batch], meta=meta)
            except Exception:
                pass  # a stats sink must never take down serving
        off = 0
        for r in batch:
            n = r.x.shape[0]
            if meta is not None:
                r.future.batch_meta = meta
            try:
                r.future.set_result(y[off:off + n])
            except BaseException:
                pass  # cancelled/raced future; keep scattering the rest
            off += n


class MicroBatcher(SchedulerCore):
    """Single-worker dynamic micro-batching loop over one predict callable.

    ``predict(x: (bucket, ...)) -> (bucket, ...) per-row outputs``; any
    exception it raises is delivered to the futures of that micro-batch —
    after retries (transient failures, per ``retry``) and poison isolation
    (persistent failures: the batch is bisected so only the offending
    requests fail).  The worker keeps serving subsequent batches no matter
    what predict does.

    ``predict`` may instead return ``(outputs, meta)`` where ``meta`` is a
    dict describing how the batch was served (e.g. the degraded-precision
    flag): the meta dict is stamped onto every future of the batch as
    ``future.batch_meta`` *before* the result is set, and forwarded to the
    ``on_batch`` stats sink.

    ``clock``/``sleep`` default to ``time.perf_counter``/``time.sleep`` and
    are injectable so deadline and backoff behavior is unit-testable.
    """

    def __init__(self, predict: Callable[[np.ndarray], np.ndarray],
                 policy: Optional[BatchingPolicy] = None,
                 on_batch: Optional[OnBatch] = None,
                 name: str = "endpoint",
                 retry: Optional[RetryPolicy] = None,
                 on_dispatch: Optional[OnDispatch] = None,
                 clock: Optional[Callable[[], float]] = None,
                 sleep: Optional[Callable[[float], None]] = None):
        super().__init__(clock)
        self.predict = predict
        self.policy = policy or BatchingPolicy()
        self.name = name
        self.retry = retry
        self._on_batch = on_batch
        self._on_dispatch = on_dispatch
        self._sleep = sleep or time.sleep
        # Deterministic per-endpoint jitter stream (stable across restarts).
        self._rng = random.Random(zlib.crc32(name.encode()) & 0xFFFFFFFF)
        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._carry: Optional[_Request] = None  # didn't fit the last batch
        self._warmed = False
        self._closed = False
        self._detached = False
        self._submit_lock = threading.Lock()  # orders submit() vs close()
        # Reliability counters (single-writer: the worker thread; readers
        # tolerate torn reads — they are monotone gauges for stats).
        self.n_expired = 0        # requests resolved with DeadlineExceeded
        self.n_retries = 0        # dispatch retries after transient faults
        self.n_dispatch_failures = 0  # failed dispatch attempts
        self.n_failed_requests = 0    # requests resolved with an error
        # The launched batch left unforced behind a queued full bucket
        # (worker only; see _step).
        self._inflight: Optional[Launch] = None
        self.n_zero_copy_assemblies = 0  # batches assembled into staging
        self.n_concat_assemblies = 0    # legacy concatenate fallbacks
        self.n_batch1_fastpath = 0      # lone full-bucket requests, no copy
        # Optional hook fired after every successful submit() enqueue — the
        # fleet coalescer's wake-up signal (no-arg callable, must not raise).
        self.on_enqueue: Optional[Callable[[], None]] = None
        self._worker: Optional[threading.Thread] = threading.Thread(
            target=self._run, name=f"microbatch-{name}", daemon=True)
        self._worker.start()

    # -- client side ---------------------------------------------------------
    def submit(self, x: np.ndarray,
               timeout_s: Optional[float] = None) -> Future:
        """Enqueue rows; the future resolves to the (n,) per-row outputs.

        ``x`` is one row (1-D, resolves to a length-1 array) or an (n, ...)
        row block with ``n <= max_batch``.  ``timeout_s`` attaches a
        deadline: if the request is still queued when it passes, the future
        resolves with :class:`DeadlineExceeded` instead of being computed.
        """
        x = np.asarray(x)
        if x.ndim == 1:
            x = x[None, :]
        if x.shape[0] > self.policy.max_batch:
            raise ValueError(
                f"request of {x.shape[0]} rows exceeds max_batch "
                f"{self.policy.max_batch}; split it across submissions")
        now = self._clock()
        deadline = None if timeout_s is None else now + max(0.0, timeout_s)
        queued = ((spans.new_id(), time.perf_counter()) if spans.enabled()
                  else None)
        fut: Future = Future()
        # The closed check and the enqueue must be atomic vs close(), or a
        # racing submit could land a request in a dead queue after the final
        # drain — a future that never resolves.
        with self._submit_lock:
            if self._closed:
                raise RuntimeError(f"MicroBatcher '{self.name}' is closed")
            self._queue.put(_Request(x, fut, now, deadline, queued))
        cb = self.on_enqueue
        if cb is not None:
            try:
                cb()
            except Exception:
                pass  # a wake-up hook must never fail a submit
        return fut

    def depth(self) -> int:
        """Requests currently queued (including a carried head-of-line
        request) — the admission/degradation load signal."""
        return self._queue.qsize() + (1 if self._carry is not None else 0)

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop the worker; ``drain`` serves queued requests first.

        Every queued future RESOLVES — served while ``timeout`` (seconds of
        total drain budget; None = unbounded) allows, rejected with a
        RuntimeError once the deadline passes or when ``drain`` is False.
        Nothing is silently dropped.
        """
        with self._submit_lock:
            if self._closed:
                return
            self._closed = True
            self._queue.put(None)  # sentinel; no submit can follow it
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        if self._worker is not None:
            self._worker.join(timeout)
        worker_done = self._worker is None or not self._worker.is_alive()
        leftovers = []
        if worker_done and self._carry is not None:
            leftovers.append(self._carry)
            self._carry = None
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is _DETACH:
                continue  # stale detach wake-up; nothing to resolve
            if req is None:
                # Shutdown sentinel.  If the worker overran the join timeout
                # it still needs it to terminate — hand it back and stop
                # stealing from the queue (FIFO order guarantees no request
                # sits behind the first sentinel).
                if not worker_done:
                    self._queue.put(None)
                    break
                continue
            leftovers.append(req)
        for req in leftovers:
            # Serving leftovers requires the worker to be gone (predict is
            # single-caller by contract) and budget to remain.
            if drain and worker_done and (
                    deadline is None or time.perf_counter() < deadline):
                self._serve([req])
            else:
                _fail(req.future, RuntimeError(
                    f"MicroBatcher '{self.name}' closed"
                    + (" (drain deadline exceeded)" if drain else "")))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- worker side ---------------------------------------------------------
    def _expired(self, req: _Request, now: Optional[float] = None) -> bool:
        if req.deadline is None:
            return False
        if now is None:
            now = self._clock()
        return now >= req.deadline

    def _expire(self, req: _Request) -> None:
        self.n_expired += 1
        self.n_failed_requests += 1
        _fail(req.future, DeadlineExceeded(
            f"deadline passed after {self._clock() - req.t_enqueue:.3f}s in "
            f"queue on '{self.name}'"))

    def _first(self) -> Optional[_Request]:
        """Block for the first live request of the next batch.  Requests
        already past their deadline are resolved with
        :class:`DeadlineExceeded` and never join a batch.  Returns None on
        shutdown sentinel or detach.  The batch in flight is landed before
        the worker blocks on an empty queue."""
        first = self._carry
        self._carry = None
        while True:
            if first is None:
                if self._detached:
                    return None
                if self._inflight is not None and self._queue.empty():
                    self._land()
                first = self._queue.get()
                if first is None:
                    return None
            if first is _DETACH:
                return None
            if self._detached:
                self._carry = first  # hand head-of-line to the driver
                return None
            if not self._expired(first):
                return first
            self._expire(first)
            first = None

    def gather(self, taker: int = 0,
               first: Optional[_Request] = None) -> list:
        """Gather the next micro-batch, possibly [].  ``taker`` is the span
        id of the batch or fleet round that takes it, which each request's
        ``repro.request.queue`` interval names.

        The worker passes ``first``, the live request it blocked for, and
        waits up to that request's ``max_wait_ms`` for company unless
        ``eager_when_idle``.  An external scheduler (the fleet coalescer, once
        the worker is detached) passes none: the batch starts at the carried
        request and nothing waits.  Either way a request past its deadline
        is resolved with :class:`DeadlineExceeded` and skipped, one that
        would overflow ``max_batch`` is carried to head the next batch, a
        ``close()`` sentinel ends the batch and stays queued for the final
        drain, and a detach wake-up is skipped."""
        deadline = None
        if first is None:
            first, self._carry = self._carry, None
            if first is not None and self._expired(first):
                self._expire(first)
                first = None
        elif not self.policy.eager_when_idle:
            deadline = first.t_enqueue + self.policy.max_wait_ms / 1e3
        if first is None:
            batch, rows = [], 0
        else:
            _taken(first, taker)
            batch, rows = [first], first.x.shape[0]
        while rows < self.policy.max_batch:
            try:
                if deadline is None:
                    req = self._queue.get_nowait()
                else:
                    wait = deadline - self._clock()
                    req = (self._queue.get(timeout=wait) if wait > 0
                           else self._queue.get_nowait())
            except queue.Empty:
                if deadline is None or wait <= 0:
                    break
                continue
            if req is None:  # shutdown: keep the sentinel for the drain
                self._queue.put(None)
                break
            if req is _DETACH:
                continue  # a wake-up only; the worker leaves after this batch
            if self._expired(req):
                self._expire(req)
                continue
            if rows + req.x.shape[0] > self.policy.max_batch:
                self._carry = req  # head-of-line for the next batch
                break
            _taken(req, taker)
            batch.append(req)
            rows += req.x.shape[0]
        return batch

    # -- external-driver interface (the fleet coalescer) ---------------------
    def detach_worker(self, timeout: float = 5.0) -> None:
        """Retire the internal worker thread WITHOUT closing the batcher.

        Afterward ``submit`` keeps enqueueing but nothing serves the queue
        until an external scheduler does, via :meth:`gather` and
        :meth:`serve` — how the fleet coalescer takes over a member
        endpoint's scheduling while preserving its client-facing API.
        Idempotent; :meth:`close` still drains whatever remains.
        """
        if self._worker is None:
            return
        self._detached = True
        self._queue.put(_DETACH)  # wake a blocked _first
        self._worker.join(timeout)
        if self._worker.is_alive():  # pragma: no cover - defensive
            raise RuntimeError(
                f"MicroBatcher '{self.name}' worker did not detach")
        self._worker = None

    def serve(self, batch: list) -> None:
        """Serve an externally-gathered micro-batch on the caller's thread
        (lazy bucket warmup included) — the coalescer's per-member solo and
        fallback path.  Single-caller, like the worker loop it replaces;
        blocking: every future of the batch is resolved before this
        returns."""
        if not batch:
            return
        with spans.span("repro.batch") as sp:
            self._open(batch, sp)
            self._serve(batch)

    def warm(self, example: np.ndarray) -> int:
        """Trace every bucket once with zero rows shaped like ``example``,
        unless done already or the policy's ``warmup`` is off.  Returns how
        many traces raised (real traffic surfaces the error with
        context)."""
        failed = 0
        if not self._warmed and self.policy.warmup:
            for b in self.policy.buckets():
                try:
                    out = self.predict(
                        np.zeros((b,) + example.shape[1:], example.dtype))
                    np.asarray(out[0] if type(out) is tuple else out)
                except Exception:
                    failed += 1
        self._warmed = True
        return failed

    def _open(self, batch: list, sp) -> None:
        """Describe a collected batch on its ``repro.batch`` span ``sp``
        and warm the buckets before its first dispatch."""
        if sp.id:
            rows = sum(r.x.shape[0] for r in batch)
            sp.set(requests=len(batch), rows=rows,
                   bucket=self.policy.bucket_for(rows))
        if not self._warmed:
            self.warm(batch[0].x)

    def _assemble(self, batch: list, rows: int, bucket: int) -> np.ndarray:
        """Gather ``batch`` into one (bucket, ...) input without per-dispatch
        allocation on the steady-state path.

        * lone full-bucket request — forwarded as-is, zero copies;
        * homogeneous rows — written at offsets into a preallocated staging
          buffer, tail zeroed (the padding contract: zero rows, sliced off);
        * heterogeneous rows (mismatched trailing shape/dtype — a malformed
          submit) — the legacy ``np.concatenate`` path, preserving its error
          surface: the raise propagates to ``_serve``'s poison bisection.
        """
        first = batch[0].x
        if len(batch) == 1 and rows == bucket:
            self.n_batch1_fastpath += 1
            return first
        trailing, dtype = first.shape[1:], first.dtype
        if any(r.x.shape[1:] != trailing or r.x.dtype != dtype
               for r in batch):
            self.n_concat_assemblies += 1
            x = np.concatenate([r.x for r in batch], axis=0)
            if bucket > rows:
                pad = np.zeros((bucket - rows,) + x.shape[1:], x.dtype)
                x = np.concatenate([x, pad], axis=0)
            return x
        buf = self._staging_buffer((bucket,) + trailing, dtype)
        off = 0
        for r in batch:
            n = r.x.shape[0]
            buf[off:off + n] = r.x
            off += n
        if rows < bucket:
            buf[rows:bucket] = 0
        self.n_zero_copy_assemblies += 1
        return buf

    def assembly_stats(self) -> dict:
        """Allocation/timing accounting of the batch-assembly path (the
        zero-copy acceptance hook: steady state must show assemblies growing
        while staging allocations plateau at two per active bucket)."""
        return {"n_staging_allocs": self.n_staging_allocs,
                "n_zero_copy_assemblies": self.n_zero_copy_assemblies,
                "n_concat_assemblies": self.n_concat_assemblies,
                "n_batch1_fastpath": self.n_batch1_fastpath,
                "assembly_s": self.assembly_s,
                "device_s": self.device_s}

    def _assembled(self, batch: list) -> Launch:
        """Assemble ``batch`` into its bucket, ready to launch."""
        rows = sum(r.x.shape[0] for r in batch)
        t0 = self._clock()
        with spans.span("repro.batch.assemble"):
            bucket = self.policy.bucket_for(rows)
            x = self._assemble(batch, rows, bucket)
        t1 = self._clock()
        self.assembly_s += t1 - t0
        return Launch(batch, bucket, x, t1, rows)

    def _call(self, fl: Launch) -> None:
        """Launch an assembled batch: call predict, leave its outputs
        unforced."""
        out = self.predict(fl.x)
        if type(out) is tuple:  # (outputs, batch metadata)
            out, fl.meta = out
        fl.out = out

    def _failed_attempt(self, e: BaseException) -> None:
        self.n_dispatch_failures += 1
        if self._on_dispatch is not None:
            try:
                self._on_dispatch(False, e)
            except Exception:
                pass

    def _dispatch_once(self, batch: list) -> None:
        """One blocking dispatch attempt for ``batch``: assemble into the
        bucket, launch, force, record stats, scatter results.  Raises on
        failure (nothing resolved); on success every future in ``batch``
        resolves."""
        fl = self._assembled(batch)
        with spans.span("repro.batch.dispatch", overlapped=0):
            self._call(fl)
            y = self._force(fl, fl.bucket)
        self._resolve([(fl, y)], ())

    def _try_dispatch(self, batch: list, failed: int = 0,
                      last: Optional[BaseException] = None
                      ) -> Optional[BaseException]:
        """Dispatch with bounded transient retry; returns None on success
        (futures resolved) or the final exception (nothing resolved).
        ``failed`` attempts were already made, the last raising ``last``
        (a pipelined batch's first attempt)."""
        attempts = self.retry.max_attempts if self.retry is not None else 1
        while True:
            if last is not None:
                if (self.retry is None or failed >= attempts
                        or not self.retry.retryable(last)):
                    return last
                self.n_retries += 1
                self._sleep(self.retry.backoff_s(failed - 1, self._rng))
            try:
                self._dispatch_once(batch)
                return None
            except Exception as e:
                last = e
                failed += 1
                self._failed_attempt(e)

    def _live(self, batch: list) -> list:
        """``batch`` less its requests past their deadline, resolved with
        :class:`DeadlineExceeded`."""
        now = self._clock()
        live = []
        for r in batch:
            if self._expired(r, now):
                self._expire(r)
            else:
                live.append(r)
        return live

    def _serve(self, batch: list, isolated: bool = False,
               failed: Optional[BaseException] = None) -> None:
        """Serve ``batch``: expire the stale, dispatch the live, bisect on
        failure so a poison request fails alone.  ``failed``: the batch's
        first attempt, a pipelined one, already raised it.  Every future in
        ``batch`` is resolved by the time this returns; nothing escapes
        (the worker loop must survive any predict/concatenate/future
        misbehavior)."""
        try:
            live = self._live(batch)
            if not live:
                return
            err = (self._try_dispatch(live) if failed is None
                   else self._try_dispatch(live, 1, failed))
            if err is None:
                return
            if len(live) == 1:
                self.n_failed_requests += 1
                final = DispatchError(
                    f"dispatch failed on '{self.name}': {err!r}",
                    cause=err, isolated=isolated)
                final.__cause__ = err
                _fail(live[0].future, final)
                return
            # Poison-batch bisection: retry the halves independently so the
            # offending request(s) fail alone.  Each half re-pads to its own
            # (warmed) bucket; row independence keeps survivors' results
            # bit-identical to any other batch composition.
            mid = len(live) // 2
            self._serve(live[:mid], isolated=True)
            self._serve(live[mid:], isolated=True)
        except BaseException as e:  # belt-and-braces: resolve, don't die
            for r in batch:
                if not r.future.done():
                    self.n_failed_requests += 1
                    _fail(r.future, DispatchError(
                        f"scheduler error on '{self.name}': {e!r}", cause=e))

    def _full_bucket_queued(self, fl: Launch) -> bool:
        """Whether the requests queued now, the carried one first, hold a
        whole ``max_batch`` of rows ahead of any shutdown or detach.

        Only asked when ``fl`` itself was cut by size (a full bucket, or a
        request carried over): a batch whose gather emptied the queue has
        only what arrived during its assembly and call behind it, and the
        scan would cost every such batch its time."""
        if fl.rows < self.policy.max_batch and self._carry is None:
            return False
        need = self.policy.max_batch
        if self._carry is not None:
            need -= self._carry.x.shape[0]
        with self._queue.mutex:
            for req in self._queue.queue:
                if need <= 0:
                    break
                if req is None or req is _DETACH:
                    return False
                need -= req.x.shape[0]
        return need <= 0

    def _step(self, batch: list) -> None:
        """The worker's turn for a collected ``batch``, under its
        ``repro.batch`` span: launch it, force and scatter the batch in
        flight, if any, and force ``batch`` too unless a full bucket is
        queued behind it, in which case it becomes the batch in flight."""
        prev, self._inflight = self._inflight, None
        landed: List[tuple] = []  # (launch, outputs)
        failed: List[tuple] = []  # (batch, exception of its first attempt)
        try:
            live = self._live(batch)
            fl = None
            if live:
                try:
                    fl = self._assembled(live)
                except Exception as e:
                    failed.append((live, e))
            with spans.span("repro.batch.dispatch", overlapped=int(
                    prev is not None and fl is not None)):
                if fl is not None:
                    try:
                        self._call(fl)
                    except Exception as e:
                        failed.append((live, e))
                        fl = None
                for f in (prev, fl):
                    if f is None:
                        continue
                    if (f is fl and not failed
                            and self._full_bucket_queued(fl)):
                        self._inflight = fl
                        continue
                    try:
                        landed.append((f, self._force(f, f.bucket)))
                    except Exception as e:
                        failed.append((f.batch, e))
            self._resolve(landed, failed)
        except Exception as e:  # belt-and-braces: resolve, don't die
            self._inflight = None
            for r in batch + (prev.batch if prev is not None else []):
                if not r.future.done():
                    self.n_failed_requests += 1
                    _fail(r.future, DispatchError(
                        f"scheduler error on '{self.name}': {e!r}", cause=e))

    def _resolve(self, landed: list, failed: list) -> None:
        """Scatter forced batches; send each batch whose first attempt
        failed down the blocking retry and bisection path."""
        with spans.span("repro.batch.scatter"):
            for fl, y in landed:
                if self._on_dispatch is not None:
                    try:
                        self._on_dispatch(True, None)
                    except Exception:
                        pass
                self._scatter(fl.batch, y, fl.rows, fl.bucket, fl.meta,
                              self._clock(), self._on_batch)
        for batch, e in failed:
            self._failed_attempt(e)
            self._serve(batch, failed=e)

    def _land(self) -> None:
        """Force and scatter the batch in flight, if any: nothing live is
        queued behind it, or the worker is leaving."""
        if self._inflight is not None:
            with spans.span("repro.batch"):
                self._step([])

    def _run(self) -> None:
        while True:
            first = self._first()  # the blocking wait: idle, not a span
            if first is None:
                self._land()
                return
            with spans.span("repro.batch") as sp:
                with spans.span("repro.batch.collect"):
                    batch = self.gather(sp.id, first)
                self._open(batch, sp)
                self._step(batch)
