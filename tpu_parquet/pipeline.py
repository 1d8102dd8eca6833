"""Overlapped chunk pipeline: threaded IO + decompress prefetch.

SURVEY.md §7.4.7's stance — "pipelining beats any single kernel" — applied to
the host side of the decode path.  The engine's natural work unit is the
column chunk (one contiguous IO, one decompress+parse, one staged region, one
fused dispatch); until this module, file → row group → chunk executed strictly
sequentially, so the device idled during every chunk's IO and the CPU idled
during every transfer.

Three pieces, shared by the host ``FileReader`` and the batched
``DeviceFileReader``:

- :func:`prefetch_map` — an *ordered* overlapped map: up to ``prefetch``
  items run on a bounded thread pool ahead of the consumer, results are
  yielded in submission order, and errors surface at the failing item's
  position (never out of order, never swallowed).  Decompression releases the
  GIL (zlib via stdlib, snappy via ctypes → the C++ codec), and chunk IO is
  blocking reads, so host threads genuinely overlap.  The item stream is
  pulled lazily in the CONSUMER thread, so work generation (page-pruning
  planning, schema snapshots) keeps its sequential semantics.
- :class:`PipelineStats` — per-stage wall-time counters
  (io / decompress / stage / dispatch / finalize) plus stall time and the
  in-flight high-water mark, surfaced by both readers' ``pipeline_stats()``
  so bench.py can report overlap efficiency (sum of stage time ÷ wall time:
  1.0 is perfectly serial, higher means overlap).
- :class:`SharedReader` — thread-safe positioned reads over one byte source:
  ``os.pread`` on real files (parallel, never touches the shared fd
  position), a lock around seek+read otherwise (BytesIO, sockets wrapped in
  a buffer).

Memory is bounded by :class:`tpu_parquet.alloc.InFlightBudget`: the submitter
acquires each chunk's estimated bytes (compressed + decompressed, from the
footer) BEFORE handing it to the pool and releases them when the consumer
takes the result — backpressure instead of OOM, asserted in tests.  The
budget is only ever awaited in the consumer thread while nothing is in
flight, or skipped in favor of draining the window head, so it cannot
deadlock against itself.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Callable, Iterable, Iterator, Optional, TypeVar

from .alloc import InFlightBudget
from .obs import (LatencyHistogram, current_tracer, note_worker_crash,
                  profiler_span, register_flight_source)

T = TypeVar("T")
R = TypeVar("R")

STAGES = ("io", "decompress", "recompress", "stage", "dispatch", "finalize")

# per-PipelineStats token riding the pipeline_wall trace counter: one trace
# often carries several stats objects (one per file of a scan), and the
# summarizer must sum each pipeline's own wall, not max across all of them
_pstats_ids = itertools.count(1)


class PipelineStats:
    """Per-stage timing for the overlapped decode pipeline (SURVEY.md §5.5).

    Stage meanings (a stage a path never enters simply stays 0):

    - ``io``          chunk byte reads from the source
    - ``decompress``  page decompress + CRC + structure parse + host decode
    - ``recompress``  link recompression: snappy over hot streams so GZIP/
                      ZSTD/uncompressed files still ship compressed
                      (ship.py ROUTE_RECOMPRESS; runs on the same worker
                      threads as decompress when prefetch > 0)
    - ``stage``       host→device staging (buffer assembly + transfer)
    - ``dispatch``    issuing the fused XLA calls
    - ``finalize``    deferred validity syncs

    ``busy_seconds`` is the sum over stages — the serial cost the pipeline is
    hiding; ``overlap_efficiency = busy_seconds / wall_seconds`` reads 1.0
    for a perfectly serial run and >1 when stages genuinely overlap.
    ``stall_seconds`` counts submitter time blocked on the memory budget.
    Thread-safe: workers and the main thread add concurrently.

    Each ``add``/``timed`` also feeds a per-stage log-bucketed
    :class:`~tpu_parquet.obs.LatencyHistogram` (p50/p95 where the sums
    alone can't attribute a stall — see obs.StatsRegistry), and ``timed``
    emits a span on ``tracer`` (the ``TPQ_TRACE`` process tracer by
    default; a disabled tracer costs one ``if``) and a ``tpq.<stage>``
    annotation into any live profiler capture (``obs.profiler_span``);
    so does the budget wait in :func:`prefetch_map` (``tpq.stall``).
    """

    def __init__(self, prefetch: int = 0, budget_bytes: int = 0,
                 tracer=None):
        self.prefetch = int(prefetch)
        self.budget_bytes = int(budget_bytes)
        self.chunks = 0
        self.row_groups = 0
        self.stall_seconds = 0.0
        self.wall_seconds = 0.0
        self.peak_in_flight_bytes = 0
        # live prefetch backlog (items submitted, not yet consumed) — a
        # point-in-time gauge for obs.Sampler, deliberately NOT in as_dict()
        # (its end-of-run value is always 0 and would only add key noise)
        self.queue_depth = 0
        self._stage_seconds = {s: 0.0 for s in STAGES}
        self._stage_hist = {s: LatencyHistogram() for s in STAGES}
        self.tracer = tracer if tracer is not None else current_tracer()
        self._obs_id = next(_pstats_ids)
        self._lock = threading.Lock()
        self._t0: Optional[float] = None
        # a flight dump must show every live pipeline's lane seconds and
        # queue depth at the moment of the wedge (weakly held — see obs)
        register_flight_source(f"pipeline[{self._obs_id}]", self, "sample")

    # -- accumulation ---------------------------------------------------------

    def add(self, stage: str, seconds: float) -> None:
        if stage not in self._stage_seconds:
            raise ValueError(
                f"unknown pipeline stage {stage!r}; valid stages: "
                f"{', '.join(STAGES)}")
        with self._lock:
            self._stage_seconds[stage] += seconds
        self._stage_hist[stage].record(seconds)

    @contextmanager
    def timed(self, stage: str, **span_args):
        t0 = time.perf_counter()
        try:
            with profiler_span(stage):
                yield
        finally:
            t1 = time.perf_counter()
            self.add(stage, t1 - t0)
            tr = self.tracer
            if tr is not None and tr.active:
                tr.complete(stage, t0, t1, **span_args)

    def add_stall(self, seconds: float, t0: Optional[float] = None) -> None:
        with self._lock:
            self.stall_seconds += seconds
        tr = self.tracer
        if tr is not None and tr.active and t0 is not None:
            tr.complete("stall", t0, t0 + seconds)

    def count_chunk(self) -> None:
        with self._lock:
            self.chunks += 1

    def count_row_group(self) -> None:
        with self._lock:
            self.row_groups += 1

    def touch_wall(self) -> None:
        """Extend the wall clock to now (first call starts it)."""
        now = time.perf_counter()
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            self.wall_seconds = now - self._t0
            wall = self.wall_seconds
        tr = self.tracer
        if tr is not None and tr.active and wall:
            # the pipeline's own wall clock rides the trace as a counter so
            # pq_tool trace reports the SAME overlap efficiency as this
            # object (span extents alone include consumer tails the wall
            # clock deliberately excludes)
            tr.counter("pipeline_wall", seconds=round(wall, 6),
                       pipe=self._obs_id)

    def set_queue_depth(self, n: int) -> None:
        with self._lock:
            self.queue_depth = int(n)

    def sample(self) -> dict:
        """Point-in-time counter snapshot for :class:`~tpu_parquet.obs.Sampler`:
        the cumulative per-stage seconds (as counter tracks their slope IS
        live per-lane throughput), the stall total, and the live prefetch
        queue depth (backpressure visible as a curve, not an end total)."""
        with self._lock:
            out = {s: round(v, 6) for s, v in self._stage_seconds.items()}
            out["stall"] = round(self.stall_seconds, 6)
            out["chunks"] = self.chunks
            out["queue_depth"] = self.queue_depth
        return out

    def note_peak(self, budget: InFlightBudget) -> None:
        with self._lock:
            self.peak_in_flight_bytes = max(self.peak_in_flight_bytes,
                                            budget.peak)

    def merge_from(self, other: "PipelineStats") -> None:
        """Fold another pipeline's counters into this one (layering hook:
        a DataLoader accumulates its per-unit readers' pipelines here).
        Stage/stall seconds and item counts add; peaks take the max; the
        wall clock stays this object's own (merged pipelines overlap it)."""
        with other._lock:
            stages = dict(other._stage_seconds)
            chunks, row_groups = other.chunks, other.row_groups
            stall = other.stall_seconds
            peak = other.peak_in_flight_bytes
        with self._lock:
            for s, v in stages.items():
                self._stage_seconds[s] += v
            self.chunks += chunks
            self.row_groups += row_groups
            self.stall_seconds += stall
            self.peak_in_flight_bytes = max(self.peak_in_flight_bytes, peak)
        for s in STAGES:
            self._stage_hist[s].merge_from(other._stage_hist[s])

    # -- reporting ------------------------------------------------------------

    def stage_seconds(self, stage: str) -> float:
        with self._lock:
            return self._stage_seconds[stage]

    @property
    def busy_seconds(self) -> float:
        with self._lock:
            return sum(self._stage_seconds.values())

    @property
    def overlap_efficiency(self) -> float:
        return self.busy_seconds / self.wall_seconds if self.wall_seconds else 0.0

    def as_dict(self) -> dict:
        with self._lock:
            stages = {f"{s}_seconds": round(v, 6)
                      for s, v in self._stage_seconds.items()}
        busy = self.busy_seconds
        return {
            "prefetch": self.prefetch,
            "budget_bytes": self.budget_bytes,
            "chunks": self.chunks,
            "row_groups": self.row_groups,
            **stages,
            "busy_seconds": round(busy, 6),
            "wall_seconds": round(self.wall_seconds, 6),
            "stall_seconds": round(self.stall_seconds, 6),
            "peak_in_flight_bytes": self.peak_in_flight_bytes,
            "overlap_efficiency": round(self.overlap_efficiency, 3),
            # only the stages that saw work: the empty ones carry no
            # information and would triple the artifact's size
            "stage_histograms": {s: h.as_dict()
                                 for s, h in self._stage_hist.items()
                                 if h.count},
        }


class SharedReader:
    """Thread-safe positioned reads over one byte source, via a ByteStore.

    Every read delegates to a :class:`tpu_parquet.iostore.ByteStore` —
    :class:`~tpu_parquet.iostore.LocalStore` by default (``os.pread`` on
    real files: fully parallel, the shared fd's position is never touched,
    so a main thread interleaving its own seek+read — the page-pruning
    planner — stays correct; a lock around seek+read for fd-less sources).
    Passing a :class:`~tpu_parquet.iostore.GenericRangeStore` slots the
    fault-tolerant retry/backoff/deadline core underneath the SAME reader
    and pipeline stack — no decode layer sees the difference.
    ``parallel`` is False on the locked path so callers that ALSO seek the
    raw object outside this class know to stay sequential.
    """

    def __init__(self, f, store=None):
        self._f = f
        if store is None:
            from .iostore import LocalStore

            store = LocalStore(f)
        self.store = store
        # the CURRENT scan's iostore.ScanToken (set by the reader at each
        # scan boundary): rides every pread so a store shared between
        # concurrent requests charges THIS scan's retry budget and honors
        # THIS request's deadline/cancel — never a neighbor's
        self._scan = None

    def set_scan(self, token) -> None:
        self._scan = token

    @property
    def parallel(self) -> bool:
        return self.store.parallel

    def as_file(self) -> "_PReadFile":
        """A minimal file-like (seek/read pairs) whose every read goes
        through ``pread`` — for code written against a raw file that must
        run while worker threads read the same source (the page-pruning
        planner's header walks)."""
        return _PReadFile(self)

    def pread(self, offset: int, size: int) -> bytes:
        if self._scan is not None:
            return self.store.read_range(offset, size, scan=self._scan)
        return self.store.read_range(offset, size)


class _PReadFile:
    """File-like adapter over :class:`SharedReader` — tracks its own
    position, so concurrent holders never fight over the shared fd's."""

    def __init__(self, sr: SharedReader):
        self._sr = sr
        self._pos = 0

    def seek(self, pos: int) -> int:
        self._pos = int(pos)
        return self._pos

    def read(self, size: int) -> bytes:
        b = self._sr.pread(self._pos, size)
        self._pos += len(b)
        return b


def prefetch_map(
    items: Iterable[T],
    fn: Callable[[T], R],
    prefetch: int,
    budget: Optional[InFlightBudget] = None,
    cost: Optional[Callable[[T], int]] = None,
    stats: Optional[PipelineStats] = None,
    cancel=None,
    feed=None,
) -> Iterator[R]:
    """Ordered overlapped map: run ``fn`` over ``items`` on a bounded pool.

    Up to ``prefetch`` items are in flight ahead of the consumer; results
    yield strictly in item order; an item whose ``fn`` raises re-raises at
    its ordered position, after which remaining work is cancelled and the
    pool is joined — no leaked threads, even when the consumer abandons the
    generator early (``break`` triggers the same cleanup via close()).

    ``cost(item)`` bytes are acquired from ``budget`` before submission and
    released when the consumer receives the result (ownership transfers).
    Backpressure never blocks while results are poppable: when the next
    item's bytes don't fit, the window head is drained first; a true blocking
    wait happens only with nothing in flight (the oversize-item case, which
    :class:`InFlightBudget` admits alone).

    ``cancel`` (a :class:`~tpu_parquet.resilience.CancelToken`) is checked
    at every unit boundary — before each submission and each yield — so a
    cancelled or deadline-expired request stops issuing new work, raises
    its TYPED verdict at the consumer, and still runs the full cleanup
    path (window drained, budget released, pool joined: nothing orphaned).

    ``feed`` (a :class:`tpu_parquet.iostore_async.FetchEngine`, or any
    object with ``want_more()``/``max_inflight``) decouples IO depth from
    decode depth: pulling an item is what SUBMITS its IO (the engine-mode
    :class:`~tpu_parquet.iostore.CoalescedFetcher` puts its ranges in
    flight at construction), so while the engine reports free fetch slots,
    items are pulled ahead of the ``prefetch``-deep decode window into a
    ready queue — ``prefetch=K`` bounds DECODE parallelism, in-flight IO
    is bounded by ``TPQ_IO_INFLIGHT`` and the memory budget (ahead-pulls
    charge ``budget`` non-blocking and stop at the first refusal, so
    backpressure still holds).

    ``prefetch <= 0`` degrades to a plain sequential map with zero threads —
    the bit-identical baseline the tests compare against.
    """
    trace = getattr(cancel, "trace", None) if cancel is not None else None
    if prefetch <= 0:
        for item in items:
            if cancel is not None:
                cancel.check()
            if trace is None:
                yield fn(item)
            else:
                with trace.span("decode"):
                    res = fn(item)
                yield res
        return

    def run(item):
        # the worker half of the flight recorder's crash trigger: a dying
        # worker notes itself in the ring (and dumps under TPQ_FLIGHT)
        # BEFORE the future carries the exception back — the consumer may
        # be blocked elsewhere and never surface it promptly
        try:
            if trace is None:
                return fn(item)
            # one request-trace span per decoded unit, on the worker
            # thread (per-thread nesting parents it to the request root)
            with trace.span("decode"):
                return fn(item)
        except BaseException as e:
            note_worker_crash(e)
            raise

    it = iter(items)
    pending: deque = deque()  # (future, charged_cost)
    ready: deque = deque()    # (item, cost): charged + IO submitted, awaiting
    #                           a decode slot (only the feed pulls ahead here)
    carried: Optional[tuple] = None  # (item, cost) awaiting budget headroom
    # the WINDOW is prefetch items deep, but the pool never exceeds the
    # machine's cores: chunk decode is a numpy/ctypes mix that still holds
    # the GIL between releases, and oversubscribed workers convoy on it
    # (measured 0.88x at 4 threads on 2 cores; queued-but-not-running items
    # keep the lookahead without the contention)
    workers = max(1, min(prefetch, os.cpu_count() or 1))
    ex = ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="tpq-prefetch")
    try:
        exhausted = False

        def pull(block_ok: bool) -> bool:
            # move ONE item generator → (budget charge) → ready queue;
            # False when the generator is dry or the budget said not now.
            # A blocking budget wait is allowed only with nothing in
            # flight (block_ok + empty window) — the no-deadlock contract
            nonlocal carried, exhausted
            if carried is None:
                try:
                    item = next(it)
                except StopIteration:
                    exhausted = True
                    return False
                carried = (item, int(cost(item)) if cost is not None else 0)
            item, c = carried
            if budget is not None and c:
                if not budget.try_acquire(c):
                    if not block_ok or pending:
                        return False  # drain the head; its release frees room
                    t0 = time.perf_counter()
                    with profiler_span("stall"):
                        budget.acquire(c, cancel=cancel)
                    if stats is not None:
                        stats.add_stall(time.perf_counter() - t0, t0)
                if stats is not None:
                    stats.note_peak(budget)
            carried = None
            ready.append((item, c))
            return True

        while True:
            if cancel is not None:
                # the unit-boundary gate: stop issuing new IO the moment
                # the request is cancelled/expired; the finally below still
                # drains the window and releases every charged byte
                cancel.check()
            while len(pending) < prefetch:
                if not ready and (exhausted or not pull(block_ok=True)):
                    break
                item, c = ready.popleft()
                pending.append((ex.submit(run, item), c))
                if stats is not None:
                    stats.set_queue_depth(len(pending))
            if feed is not None:
                # the engine-backed lookahead: pulling submits IO, so keep
                # pulling while the engine has free fetch slots (and the
                # ready backlog stays bounded); never block on the budget
                while (not exhausted and len(ready) < feed.max_inflight
                       and feed.want_more() and pull(block_ok=False)):
                    pass
            if not pending:
                if exhausted and carried is None and not ready:
                    break
                continue  # budget-carried item with empty window: block-acquire
            fut, c = pending.popleft()
            if stats is not None:
                stats.set_queue_depth(len(pending))
            try:
                res = fut.result()
            finally:
                if budget is not None and c:
                    budget.release(c)
            yield res
    finally:
        if stats is not None:
            stats.set_queue_depth(0)
        for fut, _c in pending:
            fut.cancel()
        ex.shutdown(wait=True)
        for fut, c in pending:
            if budget is not None and c:
                budget.release(c)
            if not fut.cancelled():
                fut.exception()  # retrieve, so failures aren't warned as lost
        for _item, c in ready:
            # ahead-pulled items never reached the pool: refund their charge
            if budget is not None and c:
                budget.release(c)
