"""PlanCache: the shared read-through state a high-QPS scan service runs on.

Every one-shot open pays three metadata costs before the first data byte:
the footer thrift parse, the ScanPlan construction (group pruning + the
footer walk into chunk byte ranges), and — for dictionary-encoded columns —
the dictionary-page decompress + decode, per chunk, per scan.  Under
concurrent traffic over a bounded working set those costs repeat millions of
times for identical inputs.  This module holds all three behind ONE bounded
LRU keyed by file *generation*:

- **footers** (parsed ``FileMetaData`` + a ``Schema``) keyed by
  ``(path, size, mtime_ns)`` for local files, or ``(identity_token, size)``
  for :class:`~tpu_parquet.iostore.ByteStore`-backed objects — the
  read-through footer cache ROADMAP item 4 owed for re-opened
  ``GenericRangeStore`` objects.  A changed file changes its key, so stale
  entries can never be served (and the previous generation is dropped
  eagerly — ``invalidations`` counts them);
- **ScanPlans** (:mod:`tpu_parquet.scanplan`) keyed by
  ``(file key, projection, filter fingerprint)`` — replayed, not rebuilt,
  so the route memo and pruning memo accumulate across requests;
- **decoded dictionaries** keyed by ``(file key, row group, column,
  decode kind)`` — shared read-only with every decoder
  (:class:`BoundDictCache` is the per-file adapter the readers duck-call).

Bounded: total cached bytes are capped (``TPQ_PLAN_CACHE_MB``, default 256)
with LRU eviction; ``hits``/``misses``/``evictions`` counters per kind ride
the registry ``serve`` section and the flight dumps.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

from ..footer import read_file_metadata
from ..iostore import ByteStore
from ..obs import current_request_trace, env_int, profiler_span

__all__ = ["PlanCache", "BoundDictCache", "CacheStats"]

_KINDS = ("footer", "plan", "dict")


class CacheStats:
    """Per-kind hit/miss/eviction counters (thread-safe via the owning
    cache's lock; this object only aggregates)."""

    __slots__ = ("hits", "misses", "evictions", "invalidations")

    def __init__(self):
        self.hits = {k: 0 for k in _KINDS}
        self.misses = {k: 0 for k in _KINDS}
        self.evictions = 0
        self.invalidations = 0

    def as_dict(self) -> dict:
        return {
            **{f"{k}_hits": self.hits[k] for k in _KINDS},
            **{f"{k}_misses": self.misses[k] for k in _KINDS},
            "evictions": self.evictions,
            "invalidations": self.invalidations,
        }


class PlanCache:
    """Bounded read-through cache over footers, ScanPlans, and decoded
    dictionaries.  Thread-safe; one instance is shared by every worker of a
    :class:`~tpu_parquet.serve.ScanService` (or passed to ``scan_files``
    via ``plan_cache=``)."""

    def __init__(self, max_bytes: "int | None" = None, results=None,
                 result_cache_mb: "int | None" = None,
                 result_cache_hbm_mb: "int | None" = None):
        from .result_cache import ResultCache

        if max_bytes is None:
            max_bytes = env_int("TPQ_PLAN_CACHE_MB", 256, lo=1) << 20
        self.max_bytes = int(max_bytes)
        # the tiered decoded-result cache (result_cache.py) this plan cache
        # feeds: decoded DICTIONARIES live there (one LRU, one byte budget
        # — the PR 10 dict seam folded), and — when sized (the explicit
        # MB args, else TPQ_RESULT_CACHE_MB/TPQ_RESULT_CACHE_HBM_MB) —
        # decoded column-chunk results too.  With the result tier off the
        # dictionary store stays bounded by THIS cache's budget.
        self.results = (results if results is not None else ResultCache(
            max_bytes=(None if result_cache_mb is None
                       else int(result_cache_mb) << 20),
            hbm_bytes=(None if result_cache_hbm_mb is None
                       else int(result_cache_hbm_mb) << 20),
            dict_fallback_bytes=self.max_bytes))
        self.stats = CacheStats()
        self._lock = threading.Lock()
        # full key -> (value, nbytes); insertion order = recency (LRU)
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        # file identity -> current generation key: a re-opened file whose
        # generation moved drops the stale entries eagerly instead of
        # letting them age out of the LRU
        self._gen: dict = {}
        # single-flight build locks: N concurrent first-touches of one key
        # build ONCE (the "footer parsed exactly once per file" acceptance
        # is a guarantee, not a race outcome); late arrivals count as hits
        self._building: dict = {}

    # -- identity --------------------------------------------------------------

    @staticmethod
    def file_key(source, store: "ByteStore | None" = None):
        """The file-generation cache key, or None when the source has no
        stable identity (an anonymous stream: never cached, never stale).

        Local paths key by ``(abspath, size, mtime_ns)``; stores by their
        ``identity_token`` + ``size()`` (the satellite contract: a changed
        object — new token or new size — invalidates cleanly)."""
        if store is not None and isinstance(store, ByteStore):
            tok = store.identity_token
            if tok is None:
                return None
            return ("store", tok, int(store.size()))
        if isinstance(source, (str, os.PathLike)):
            path = os.path.abspath(os.fspath(source))
            try:
                st = os.stat(path)
            except OSError:
                return None
            return ("file", path, int(st.st_size), int(st.st_mtime_ns))
        return None

    # -- the one LRU -----------------------------------------------------------

    def _get(self, kind: str, key: tuple):
        with self._lock:
            full = (kind, *key)
            hit = self._entries.get(full)
            if hit is not None:
                self._entries.move_to_end(full)
                self.stats.hits[kind] += 1
                return hit[0]
            self.stats.misses[kind] += 1
            return None

    def _read_through(self, kind: str, key: tuple, build):
        """Get-or-build with single-flight semantics: exactly one builder
        per key runs (one miss counted); concurrent callers wait on the
        build lock and count as hits.  ``build()`` returns
        ``(value, nbytes)``."""
        tr = current_request_trace()
        if tr is None:
            return self._read_through_inner(kind, key, build)
        t0 = time.perf_counter()
        misses0 = self.stats.misses[kind]
        try:
            return self._read_through_inner(kind, key, build)
        finally:
            # best-effort hit attribution: under concurrent traffic a
            # neighbor's miss can tick between our two reads, but a probe
            # span is evidence, not accounting
            tr.add_timed(f"cache_{kind}", t0, time.perf_counter(),
                         hit=self.stats.misses[kind] == misses0)

    def _read_through_inner(self, kind: str, key: tuple, build):
        full = (kind, *key)
        with self._lock:
            hit = self._entries.get(full)
            if hit is not None:
                self._entries.move_to_end(full)
                self.stats.hits[kind] += 1
                return hit[0]
            lock = self._building.get(full)
            if lock is None:
                lock = self._building[full] = threading.Lock()
        with lock:
            with self._lock:
                hit = self._entries.get(full)
                if hit is not None:
                    self._entries.move_to_end(full)
                    self.stats.hits[kind] += 1
                    return hit[0]
                self.stats.misses[kind] += 1
            try:
                value, nbytes = build()
                # publish BEFORE dropping the build lock's registration: a
                # thread arriving after the pop must find the entry (pop
                # first and it would rebuild — a second counted miss and a
                # second plan object whose memos no longer accumulate)
                self._put(kind, key, value, nbytes)
            finally:
                with self._lock:
                    self._building.pop(full, None)
            return value

    def _drop_stale_locked(self, ident: tuple, keep) -> int:
        """Drop every cached entry whose file key shares ``ident`` but is
        not ``keep`` (the current generation; None drops ALL of the
        identity's entries).  One copy of the invalidation bookkeeping —
        shared by the read path (:meth:`_put` observing a moved footer)
        and the write path (:meth:`note_mutation`).  Caller holds the
        lock; returns the number of entries dropped."""
        stale = [f for f in self._entries
                 if isinstance(f[1], tuple)
                 and f[1][:2] == ident and f[1] != keep]
        for f in stale:
            _v, n = self._entries.pop(f)
            self._bytes -= n
            self.stats.invalidations += 1
        return len(stale)

    def _put(self, kind: str, key: tuple, value, nbytes: int) -> None:
        with self._lock:
            full = (kind, *key)
            old = self._entries.pop(full, None)
            if old is not None:
                self._bytes -= old[1]
            nbytes = max(int(nbytes), 1)
            self._entries[full] = (value, nbytes)
            self._bytes += nbytes
            # generation bookkeeping: a new generation of the same file
            # drops the PREVIOUS generation's entries in full (footer/plan/
            # dict alike) — they can never be served again, so aging them
            # out of the LRU is pure waste.  A file key is ("file", path,
            # size, mtime_ns) or ("store", token, size); identity = kind +
            # name, generation = the full tuple.
            fk = key[0]
            moved = False
            if isinstance(fk, tuple) and len(fk) >= 2:
                ident = fk[:2]
                prev = self._gen.get(ident)
                if prev is not None and prev != fk:
                    moved = True
                    self._drop_stale_locked(ident, fk)
                self._gen[ident] = fk
            # ONE byte budget: when the result tier is unsized, the
            # dictionary store rides THIS cache's budget — its resident
            # bytes displace footer/plan entries here (a 1/16 slice is
            # always reserved for footers/plans so a dictionary flood
            # cannot evict every footer)
            limit = self.max_bytes
            if self.results.dict_fallback_active:
                limit = max(self.max_bytes - self.results.host_held(),
                            self.max_bytes // 16, 1)
            while self._bytes > limit and len(self._entries) > 1:
                _f, (_v, n) = self._entries.popitem(last=False)
                self._bytes -= n
                self.stats.evictions += 1
        if moved:
            # decoded results invalidate at the same moment plans do — the
            # mutated file's cached chunks/dictionaries can never be
            # served, and the result cache's `invalidations` counters must
            # account them NOW, not whenever a later decode happens by
            self.results.note_generation(fk)

    # -- footers ---------------------------------------------------------------

    def footer(self, source, store: "ByteStore | None" = None):
        """Read-through footer: ``(FileMetaData, Schema)`` for a path or a
        ByteStore-backed object.  Un-keyable sources load fresh every time
        (counted as misses) — correct, just uncached."""
        from ..schema.core import Schema

        def build():
            if store is not None and isinstance(store, ByteStore):
                meta = read_file_metadata(_StoreFile(store),
                                          validate_head_magic=False)
                nbytes = _footer_len(store=store)
            else:
                meta = read_file_metadata(source)
                nbytes = _footer_len(path=source)
            return (meta, Schema.from_file_metadata(meta)), nbytes + 4096

        key = self.file_key(source, store)
        with profiler_span("open"):
            if key is None:
                with self._lock:
                    self.stats.misses["footer"] += 1
                return build()[0]
            return self._read_through("footer", (key,), build)

    # -- plans -----------------------------------------------------------------

    def plan(self, key, columns, row_filter, meta=None, schema=None,
             source=None, store=None):
        """Read-through ScanPlan for ``(file key, projection, filter)``.

        ``meta``/``schema`` may be passed when the caller already holds the
        footer; otherwise they read through :meth:`footer`.  Returns the
        SHARED plan object — its route/pruning memos accumulate across every
        consumer, which is the point."""
        from ..scanplan import build_scan_plan, predicate_fingerprint

        fp = predicate_fingerprint(row_filter)
        cols_sig = _columns_sig(columns)

        def build():
            m, s = ((meta, schema) if meta is not None and schema is not None
                    else self.footer(source, store))
            sel = _selected_schema(s, columns)
            plan = build_scan_plan(m, sel, file_key=key,
                                   row_filter=row_filter, filter_fp=fp)
            return plan, plan.nbytes()

        cacheable = key is not None and (row_filter is None or fp is not None)
        with profiler_span("plan"):
            if not cacheable:
                with self._lock:
                    self.stats.misses["plan"] += 1
                return build()[0]
            return self._read_through("plan", (key, cols_sig, fp), build)

    # -- decoded dictionaries --------------------------------------------------
    # Folded into the tiered ResultCache (one LRU, one byte budget with the
    # decoded chunk results — not a parallel dictionary budget); these
    # delegates keep the PR 10 seam and its counters stable.

    def dict_get(self, key, rg, column, kind):
        if key is None:
            return None
        from .result_cache import ResultCache

        hit = self.results.get(ResultCache.dict_key(key, rg, column, kind))
        with self._lock:
            if hit is not None:
                self.stats.hits["dict"] += 1
            else:
                self.stats.misses["dict"] += 1
        return hit

    def dict_put(self, key, rg, column, kind, value, nbytes) -> None:
        if key is None:
            return
        from .result_cache import ResultCache

        self.results.put(ResultCache.dict_key(key, rg, column, kind),
                         value, nbytes, "host")

    # -- writer integration ----------------------------------------------------

    def note_mutation(self, source, store: "ByteStore | None" = None) -> int:
        """Eagerly invalidate a file the write side just REPLACED or
        removed (the sharded writer's atomic publish and the compaction
        service call this the moment their ``os.replace`` lands).

        Without it, stale plans/results die only when the next footer
        open happens to observe the new generation; with it, the
        invalidation is synchronous with the mutation — the counters a
        mutation-mid-sweep test can assert exactly.  Computes the path's
        NEW generation key and drops every entry of previous generations
        across footers/plans/dictionaries, then notifies the decoded-
        result tier (:meth:`ResultCache.note_generation`).  A file that
        no longer exists (compaction removed it) drops by identity; its
        decoded results are unreachable afterwards (the key can never be
        rebuilt) and age out of the LRU.  Returns the number of
        plan-cache entries dropped."""
        fk = self.file_key(source, store)
        with self._lock:
            if fk is None:
                if not isinstance(source, (str, os.PathLike)):
                    return 0
                ident = ("file", os.path.abspath(os.fspath(source)))
                dropped = self._drop_stale_locked(ident, None)
                self._gen.pop(ident, None)
                return dropped
            ident = fk[:2]
            dropped = 0
            if self._gen.get(ident) != fk:
                dropped = self._drop_stale_locked(ident, fk)
                self._gen[ident] = fk
        self.results.note_generation(fk)
        return dropped

    # -- reader integration ----------------------------------------------------

    def bind_results(self, key, plan, row_filter=None, device: bool = False,
                     validate_crc=None, tenant: "str | None" = None):
        """The ONE bind gate for the decoded-result tier (shared by
        :meth:`reader_kwargs` and ``ScanService``): a filtered DEVICE
        scan whose predicate has no stable fingerprint gets no result
        cache — two unfingerprintable predicates must never share
        page-pruned device output.  ``tenant`` attributes inserts to that
        tenant's cache byte share (ISSUE 17).  Returns a
        :class:`~tpu_parquet.serve.BoundResultCache` or None."""
        if device and row_filter is not None and plan.filter_fp is None:
            return None
        return self.results.bind(key, device=device,
                                 validate_crc=validate_crc,
                                 filter_fp=plan.filter_fp, tenant=tenant)

    def reader_kwargs(self, source, columns=None, row_filter=None,
                      store: "ByteStore | None" = None, device: bool = False,
                      validate_crc=None) -> dict:
        """The ``metadata=``/``plan=``/``dict_cache=`` (and, when the
        result tier is sized, ``result_cache=``) kwargs that make a
        ``FileReader``/``DeviceFileReader`` (or ``scan_files``) run over
        this cache's shared state.  ``device``/``validate_crc`` pin the
        decode signature of the result tier (see :meth:`bind_results`)
        and MUST match the consuming reader: the default (host shape,
        env-resolved CRC) fits a bare ``FileReader``; pass
        ``device=True`` for ``DeviceFileReader``/``scan_files``.  The
        readers verify the signature at adoption and drop a mismatched
        adapter rather than serve the wrong decode shape — a mismatch
        costs the caching, never correctness."""
        key = self.file_key(source, store)
        meta, schema = self.footer(source, store)
        plan = self.plan(key, columns, row_filter, meta=meta, schema=schema)
        kw = {"metadata": meta, "plan": plan,
              "dict_cache": BoundDictCache(self, key)}
        rc = self.bind_results(key, plan, row_filter=row_filter,
                               device=device, validate_crc=validate_crc)
        if rc is not None:
            kw["result_cache"] = rc
        return kw

    # -- reporting -------------------------------------------------------------

    def counters(self) -> dict:
        with self._lock:
            return {
                **self.stats.as_dict(),
                "held_bytes": self._bytes,
                "capacity_bytes": self.max_bytes,
                "entries": len(self._entries),
            }

    # flight-source sample (obs.register_flight_source duck type)
    sample = counters


class BoundDictCache:
    """A :class:`PlanCache` bound to one file generation — the adapter the
    chunk decoders duck-call (``get(rg, column, kind)`` /
    ``put(rg, column, kind, value, nbytes)``).  ``kind`` separates the two
    decode shapes ("host": plain-decoded arrays, "dev": the device
    assembler's value-table entry)."""

    __slots__ = ("cache", "key")

    def __init__(self, cache: PlanCache, key):
        self.cache = cache
        self.key = key

    def get(self, rg, column, kind):
        return self.cache.dict_get(self.key, rg, column, kind)

    def put(self, rg, column, kind, value, nbytes) -> None:
        self.cache.dict_put(self.key, rg, column, kind, value, nbytes)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class _StoreFile:
    """Minimal seek/read file view over a ByteStore (whence-aware, which
    the SharedReader pread view deliberately is not) — enough for
    :func:`~tpu_parquet.footer.read_file_metadata`."""

    __slots__ = ("_s", "_pos")

    def __init__(self, store: ByteStore):
        self._s = store
        self._pos = 0

    def seek(self, pos: int, whence: int = 0) -> int:
        if whence == os.SEEK_END:
            self._pos = self._s.size() + pos
        elif whence == os.SEEK_CUR:
            self._pos += pos
        else:
            self._pos = pos
        return self._pos

    def read(self, size: int = -1) -> bytes:
        if size < 0:
            size = max(self._s.size() - self._pos, 0)
        b = self._s.read_range(self._pos, size)
        self._pos += len(b)
        return b


def _footer_len(path=None, store: "ByteStore | None" = None) -> int:
    """The footer's thrift length (cache accounting): read from the 8-byte
    tail; 0 on any failure (accounting only, never correctness)."""
    import struct

    try:
        if store is not None:
            size = store.size()
            tail = store.read_range(size - 8, 8)
        else:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(size - 8)
                tail = f.read(8)
        return struct.unpack("<I", tail[:4])[0]
    except Exception:  # noqa: BLE001 — accounting only
        return 0


def _columns_sig(columns) -> "tuple | None":
    if columns is None:
        return None
    out = []
    for c in columns:
        out.append(c if isinstance(c, str) else ".".join(c))
    return tuple(sorted(out))


def _selected_schema(schema, columns):
    """A fresh Schema with ``columns`` applied (the shared cached Schema is
    never mutated — selection is per-consumer state)."""
    if columns is None:
        return schema
    import copy

    from ..scanplan import apply_selection

    sel = copy.deepcopy(schema)
    apply_selection(sel, columns)
    return sel
