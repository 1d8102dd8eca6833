"""FileReader: the low-level read API.

Equivalent of the reference's file_reader.go FileReader — options (column projection,
CRC validation, memory budget, externally-supplied metadata), row-group cursor
(seek/skip/preload), and metadata accessors — but columnar-first: the primary API
returns decoded column arrays per row group (`read_row_group` / `read_all`); the
row-map iteration of the reference (`NextRow`, file_reader.go:258-273) is provided
on top by tpu_parquet.assembly.
"""

from __future__ import annotations

import io
import os
from typing import BinaryIO, Iterable, Optional, Sequence, Union

import numpy as np

from .alloc import AllocTracker, InFlightBudget
from .chunk_decode import ChunkDecoder, read_chunk, validate_chunk_meta
from .column import ByteArrayData, ColumnData
from .errors import DataIntegrityError
from .footer import ParquetError, read_file_metadata
from .format import FileMetaData, Type
from .iostore import CoalescedFetcher, require_full, resolve_store
from .iostore_async import engine_for_store
from .obs import profiler_span
from .pipeline import PipelineStats, SharedReader, prefetch_map
from .schema.core import Schema, SchemaNode


class _ChunkFailed:
    """In-band marker for a quarantined chunk riding the ordered prefetch
    stream (the stream must keep flowing — a raise would kill the pool).
    Carries the annotated exception; the CONSUMER notes exactly one
    quarantine record per failed unit (the first failing chunk in column
    order), so the ledger is identical at every prefetch depth."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class FileReader:
    """Low-level parquet reader over a seekable byte source.

    Options mirror file_reader.go:65-149: ``columns`` (projection),
    ``validate_crc``, ``max_memory`` (WithMaximumMemorySize), ``metadata``
    (WithFileMetaData).

    ``prefetch=K`` (K > 0) turns the group/chunk iteration APIs into an
    overlapped pipeline: a bounded pool of K threads runs IO + CRC +
    decompression + decode for upcoming chunks — flattened ACROSS row-group
    boundaries, so the pipeline never drains between groups — while the
    consumer assembles finished groups.  Output is bit-identical to the
    sequential path.  Memory semantics shift from the sequential path's
    per-row-group AllocTracker raise to per-CHUNK bomb enforcement plus a
    blocking in-flight cap over the same ``max_memory`` (backpressure
    instead of an error when only concurrency, not any single chunk,
    exceeds the budget).  ``pipeline_stats()`` exposes per-stage timing.
    """

    def __init__(
        self,
        source: Union[str, os.PathLike, BinaryIO, bytes],
        columns: Optional[Iterable[Union[str, Sequence[str]]]] = None,
        validate_crc=None,
        max_memory: int = 0,
        metadata: Optional[FileMetaData] = None,
        row_filter=None,
        prefetch: int = 0,
        trace=None,
        store=None,
        on_data_error=None,
        quarantine=None,
        plan=None,
        dict_cache=None,
        result_cache=None,
        cancel=None,
    ):
        from .obs import resolve_tracer
        from .quarantine import Quarantine, resolve_validate

        # span tracer (obs.py): None = the TPQ_TRACE process tracer; a path
        # = per-reader tracer written (with the registry) at close()
        self._tracer, self._owns_tracer = resolve_tracer(trace)
        if isinstance(source, (str, os.PathLike)):
            self._f: BinaryIO = open(source, "rb")
            self._owns_file = True
            self._source_name = os.fspath(source)
        elif isinstance(source, (bytes, bytearray, memoryview)):
            self._f = io.BytesIO(bytes(source))
            self._owns_file = False
            self._source_name = "<memory>"
        else:
            self._f = source
            self._owns_file = False
            self._source_name = getattr(source, "name", None) or "<stream>"
        # data-error containment (quarantine.py): ``on_data_error`` picks
        # the policy (raise | skip_unit | skip_file, TPQ_ON_DATA_ERROR);
        # ``quarantine=`` shares one engine across readers (scan_files,
        # DeviceFileReader's host half) so the budget and ledger are global
        self.quarantine = (quarantine if quarantine is not None
                           else Quarantine(on_data_error))
        # per-request lifecycle token (resilience.CancelToken): its
        # deadline/cancellation is checked at every unit boundary and
        # rides the scan token into every store read — the serve tier's
        # deadline-propagation contract
        self._cancel = cancel
        validate_crc = resolve_validate(validate_crc)
        try:
            self.metadata = (metadata if metadata is not None
                             else read_file_metadata(self._f))
            # the IO backend every chunk byte enters through (iostore.py):
            # LocalStore by default (zero-overhead pread), a
            # GenericRangeStore for fault-tolerant/remote reads.  A factory
            # callable gets this reader's open file; an instance is the
            # caller's (single-file use, caller owns/closes it).
            self._owns_store = store is None or callable(store)
            self._store = resolve_store(self._f, store)
            self._sr = SharedReader(self._f, store=self._store)
            self.schema = Schema.from_file_metadata(self.metadata)
            self._preloaded: Optional[dict[str, ColumnData]] = None
            if columns is not None:
                self.set_selected_columns(columns)
            self.validate_crc = validate_crc
            self.alloc = AllocTracker(max_memory)
            self.prefetch = int(prefetch)
            self._pipe_stats = PipelineStats(prefetch=self.prefetch,
                                             budget_bytes=int(max_memory),
                                             tracer=self._tracer)
            self._current_row_group = 0
            self._preloaded: Optional[dict[str, ColumnData]] = None
            # statistics-based row-group pruning (predicate pushdown): groups
            # whose footer stats prove the predicate can never match are
            # skipped by the iteration APIs — their bytes are never read
            self.row_filter = row_filter
            # decoded-dictionary read-through cache (serve.BoundDictCache
            # duck type); threaded into every ChunkDecoder below
            self._dict_cache = dict_cache
            # decoded column-chunk result cache (serve.BoundResultCache
            # duck type, bound to this file generation + the HOST decode
            # signature): a cached (row group, column) unit skips its IO +
            # decompress + decode entirely; misses decode once under
            # single-flight and publish for every concurrent waiter.
            # Served values are shared READ-ONLY.  An adapter whose
            # signature doesn't match THIS reader's decode shape is
            # dropped, not adopted: a device-signed one would publish
            # host ColumnData where jax arrays are expected, and one
            # signed for a different CRC tier would let a
            # validate_crc=True request adopt unvalidated decodes.
            if result_cache is not None:
                sig = getattr(result_cache, "sig", None) or ()
                want = ("host", "v1" if validate_crc else "v0")
                if tuple(sig[:2]) != want:
                    result_cache = None
            self._result_cache = result_cache
            from .scanplan import build_scan_plan, predicate_fingerprint

            # the ScanPlan replay or build (tpq.plan in a profiler capture)
            with profiler_span("plan"):
                fp = predicate_fingerprint(row_filter)
                cols_sig = tuple(sorted(
                    ".".join(l.path) for l in self.schema.selected_leaves()))
                fp_match = ((row_filter is None and plan is not None
                             and plan.filter_fp is None)
                            or (fp is not None and plan is not None
                                and plan.filter_fp == fp))
                if plan is not None and fp_match and plan.columns == cols_sig:
                    # replay a cached ScanPlan (scanplan.py): the group-pruning
                    # verdict is adopted, never recomputed; a plan whose
                    # projection or filter doesn't match falls through to a
                    # fresh build rather than a wrong replay
                    self._plan = plan
                    self._rg_keep = (list(plan.rg_keep)
                                     if plan.rg_keep is not None else None)
                else:
                    if row_filter is not None:
                        from .predicate import prune_row_groups

                        self._rg_keep = prune_row_groups(
                            self.metadata, self.schema, row_filter)
                    else:
                        self._rg_keep = None
                    self._plan = build_scan_plan(
                        self.metadata, self.schema, row_filter=row_filter,
                        filter_fp=fp, rg_keep=self._rg_keep)
        except BaseException:
            # a constructor failure (bad footer, bad projection, bad filter)
            # must not leak the fd this reader opened
            if self._owns_file:
                self._f.close()
            raise

    def set_selected_columns(self, columns) -> None:
        """Re-project mid-read (SetSelectedColumns parity, schema.go:347-367):
        subsequent row-group reads decode only these columns, seeking past the
        rest.  ``None`` restores all columns.  Clears any preloaded group.
        Validates BEFORE applying: a failed call leaves the selection as it
        was (an applied-then-raised empty selection would make later reads
        silently return {})."""
        from .scanplan import apply_selection

        apply_selection(self.schema, columns)
        self._preloaded = None
        # the plan IR is projection-scoped: re-projecting rebuilds it (a
        # cheap footer walk) so its chunk slices and byte estimates always
        # describe the CURRENT selection.  During __init__ the first plan
        # has not been built yet — the constructor builds it right after.
        if hasattr(self, "_plan"):
            from .scanplan import build_scan_plan

            self._plan = build_scan_plan(self.metadata, self.schema,
                                         row_filter=self.row_filter,
                                         rg_keep=self._rg_keep)

    def row_group_selected(self, index: int) -> bool:
        """False when ``row_filter`` proves row group ``index`` cannot match."""
        return self._rg_keep is None or self._rg_keep[index]

    @property
    def num_selected_rows(self) -> int:
        """Total rows in the row groups that survive ``row_filter`` — the
        count the iteration APIs will actually yield (``num_rows`` stays the
        footer total; pruning is group-granular, so surviving groups may
        still contain rows the predicate rejects)."""
        if self._rg_keep is None:
            return self.metadata.num_rows
        return sum(
            rg.num_rows for rg, keep in
            zip(self.metadata.row_groups, self._rg_keep) if keep
        )

    # -- context management ---------------------------------------------------

    def close(self):
        if getattr(self, "_owns_store", False):
            self._store.close()
        if self._owns_file:
            self._f.close()
        if self._owns_tracer:
            self._tracer.write(registry=self.obs_registry())
            self._owns_tracer = False

    def obs_registry(self):
        """This reader's unified metrics tree (obs.StatsRegistry): the
        pipeline's per-stage sums + histograms, the alloc peak, and the IO
        backend's retry/coalescing counters when the store keeps any."""
        from .obs import StatsRegistry

        reg = StatsRegistry()
        reg.add_pipeline(self._pipe_stats)
        reg.note_alloc_peak(self.alloc)
        if self._store.stats is not None:
            reg.add_io(self._store.stats)
        if getattr(self._store, "supports_async", False):
            # the io.engine subtree + io.queue_wait histogram (the doctor's
            # io-concurrency-bound evidence); no-op when no engine ran
            from .iostore_async import fold_engine_stats

            fold_engine_stats(reg)
        if len(self.quarantine.log) or self.quarantine.units_skipped:
            reg.add_data_errors(self.quarantine)
        return reg

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- metadata accessors (file_reader.go parity) ---------------------------

    @property
    def num_rows(self) -> int:
        return self.metadata.num_rows

    @property
    def num_row_groups(self) -> int:
        return len(self.metadata.row_groups)

    def row_group_num_rows(self, index: int) -> int:
        return self.metadata.row_groups[index].num_rows

    @property
    def created_by(self) -> Optional[str]:
        return self.metadata.created_by

    def key_value_metadata(self) -> dict:
        return {
            kv.key: kv.value for kv in (self.metadata.key_value_metadata or [])
        }

    def columns(self) -> list[SchemaNode]:
        return self.schema.selected_leaves()

    # -- columnar reads --------------------------------------------------------

    def pipeline_stats(self) -> PipelineStats:
        """Per-stage timing of the last/current prefetch pipeline
        (io / decompress / stall / peak in-flight); zeros when ``prefetch``
        was never used.  See pipeline.PipelineStats.overlap_efficiency."""
        return self._pipe_stats

    def _decode_row_groups(self, indices, k: int, contain: bool = True):
        """Chunk-granular overlapped decode (the prefetch pipeline).

        Work items are (row group, chunk) pairs FLATTENED across ``indices``
        — row-group lookahead falls out of the flattening: the K-deep window
        spans group boundaries, so worker threads keep decoding the next
        group's chunks while a finished group is assembled and yielded.
        Yields ``(index, {dotted_path: ColumnData})`` in ``indices`` order;
        per-group missing-column checks match read_row_group exactly.

        Memory: every worker chunk gets its own AllocTracker(max_memory)
        (the per-chunk decompression-bomb guard), and cross-chunk in-flight
        bytes are bounded by an InFlightBudget over the same budget —
        backpressure in the submitting thread, never a raise for a file the
        sequential path would accept chunk by chunk.

        device_reader._chunk_feed mirrors this flatten/regroup protocol
        (different payloads); a change here should be checked against it.
        """
        stats = PipelineStats(prefetch=k, budget_bytes=self.alloc.max_size,
                              tracer=self._tracer)
        self._pipe_stats = stats
        budget = InFlightBudget(self.alloc.max_size)
        sr = self._sr
        store = self._store
        # fresh per-scan retry budget + coalescing state, scoped to THIS
        # scan's token (a store shared between concurrent requests never
        # shares budgets); the request deadline/cancel rides it into every
        # read_range
        scan_tok = store.begin_scan(cancel=self._cancel)
        sr.set_scan(scan_tok)
        # async fetch engine routing (iostore_async): eligible stores put
        # a whole row group's ranges in flight on the engine's event loop
        # — prefetch=k keeps bounding DECODE parallelism, in-flight IO is
        # bounded by TPQ_IO_INFLIGHT; None = the threaded/pread path
        eng = engine_for_store(store)
        q = self.quarantine
        contain = contain and q.contains
        if contain:
            q.begin_scan(len(indices) if hasattr(indices, "__len__") else None)
        pending: dict[int, dict] = {}  # rg index -> regrouping slot

        def gen_items():
            # runs in the CONSUMER thread as the window refills, so the
            # schema-selection snapshot keeps sequential semantics
            for i in indices:
                rg = self.metadata.row_groups[i]
                by_path = {l.path: l for l in self.schema.selected_leaves()}
                items = []
                for chunk in rg.columns or []:
                    md = chunk.meta_data
                    if md is None or md.path_in_schema is None:
                        raise ParquetError("column chunk missing metadata/path")
                    path = tuple(md.path_in_schema)
                    leaf = by_path.get(path)
                    if leaf is None:
                        continue  # unselected: never read its bytes
                    items.append([i, path, chunk, leaf, None])
                # range coalescing (iostore.py): adjacent chunk reads of
                # this group merge into fewer, larger, individually-
                # retryable fetches — only for stores that ask for it
                # (remote/fault-injecting; the local path pays nothing,
                # not even the range collection below)
                use_coalesce = (store.prefers_coalescing
                                and not (scan_tok.coalesce_disabled
                                         if scan_tok is not None
                                         else store.coalesce_disabled)
                                and len(items) > 1)
                fetch_items = items
                if eng is not None and rc is not None and items:
                    # engine mode submits IO at PLAN time, so the result
                    # cache must be probed here, not at decode time: a
                    # warm unit's bytes are never fetched (the zero-store-
                    # read warm-scan contract).  Evicted-between-probe-and-
                    # decode units fall back to a plain single-range read.
                    fetch_items = [
                        c for c in items
                        if not rc.has_group(c[0], [".".join(c[1])])]
                if fetch_items and (use_coalesce or eng is not None):
                    ranges = []
                    for it in fetch_items:
                        _md, offset = validate_chunk_meta(it[2], it[3])
                        ranges.append((offset, _md.total_compressed_size))
                    # engine mode submits the group's fetches NOW (merged
                    # spans, or single ranges once the ladder disables
                    # merging) — decode catches up through the futures
                    fetcher = CoalescedFetcher(store, ranges, scan=scan_tok,
                                               engine=eng,
                                               coalesce=use_coalesce)
                    for it in fetch_items:
                        it[4] = fetcher
                pending[i] = {
                    "expect": {".".join(p) for p in by_path},
                    "todo": max(len(items), 1),
                    "out": {},
                }
                if not items:
                    # sentinel so an empty group still finalizes in order
                    items.append([i, None, None, None, None])
                yield from map(tuple, items)

        def chunk_cost(item):
            _i, _path, chunk, _leaf, _fetcher = item
            if chunk is None:
                return 0
            md = chunk.meta_data
            comp = max(md.total_compressed_size or 0, 0)
            return comp + max(md.total_uncompressed_size or 0, comp)

        rc = self._result_cache

        def decode_item(item):
            i, path, chunk, leaf, fetcher = item
            if chunk is None:
                return i, None, None
            name = ".".join(path)
            ctx = {"file": self._source_name, "row_group": i, "column": name}

            def decode_chunk():
                md, offset = validate_chunk_meta(chunk, leaf)
                alloc = AllocTracker(self.alloc.max_size)
                alloc.register(md.total_compressed_size)
                with stats.timed("io"):
                    buf = (fetcher.read(offset, md.total_compressed_size)
                           if fetcher is not None
                           else sr.pread(offset, md.total_compressed_size))
                require_full(buf, offset, md.total_compressed_size,
                             context=f"column {name}")
                with stats.timed("decompress"):
                    dec = ChunkDecoder(leaf, validate_crc=self.validate_crc,
                                       alloc=alloc,
                                       context={**ctx,
                                                "chunk_offset": offset},
                                       dict_cache=self._dict_cache)
                    return dec.decode(buf, md.codec, md.num_values)

            try:
                if rc is not None:
                    # decoded-result seam (serve/result_cache.py): a warm
                    # unit is returned without touching the store; a cold
                    # one decodes ONCE (single-flight across every
                    # concurrent scan of this file generation) and
                    # publishes.  Failed decodes are never published.
                    cd = rc.get_or_build(i, name,
                                         _cache_build(decode_chunk))
                else:
                    cd = decode_chunk()
            except ParquetError as e:
                # containment seam (quarantine.py): under a skip policy the
                # failure becomes a marker + a poisoned unit instead of an
                # aborted scan; the CONSUMER notes the record (once per
                # unit, ordered — so the ledger matches prefetch=0 exactly)
                if not contain or isinstance(e, DataIntegrityError):
                    raise
                return i, name, _ChunkFailed(e)
            stats.count_chunk()
            return i, name, cd

        stats.touch_wall()
        for i, name, cd in prefetch_map(gen_items(), decode_item, k,
                                        budget=budget, cost=chunk_cost,
                                        stats=stats, cancel=self._cancel,
                                        feed=eng):
            slot = pending[i]
            if name is not None:
                if isinstance(cd, _ChunkFailed):
                    slot.setdefault("failed", cd)
                else:
                    slot["out"][name] = cd
            slot["todo"] -= 1
            if slot["todo"] == 0:
                del pending[i]
                failed = slot.get("failed")
                if failed is not None:
                    # a quarantined unit: ONE record (the first failing
                    # chunk), nothing yielded, the skip accounted;
                    # skip_file on a single-file reader ends the scan here.
                    # note() raises DataIntegrityError on budget exhaustion.
                    q.note(failed.exc, file=self._source_name, row_group=i)
                    rg = self.metadata.row_groups[i]
                    q.note_unit_skipped(int(rg.num_rows or 0))
                    if q.policy == "skip_file":
                        # collateral: the file's remaining groups are
                        # accounted (results yield in order, so none of
                        # them has been yielded yet)
                        q.note_file_skipped()
                        pos = list(indices).index(i)
                        for j in list(indices)[pos + 1:]:
                            q.note_unit_skipped(int(
                                self.metadata.row_groups[j].num_rows or 0))
                        break
                    continue
                missing = slot["expect"] - set(slot["out"])
                if missing:
                    raise ParquetError(
                        f"row group {i} missing columns {sorted(missing)}"
                    )
                stats.count_row_group()
                stats.note_peak(budget)
                stats.touch_wall()
                yield i, slot["out"]
        stats.touch_wall()

    def read_row_group(self, index: int,
                       prefetch: Optional[int] = None) -> dict[str, ColumnData]:
        """Decode all selected column chunks of one row group.

        Returns {dotted_column_path: ColumnData}.  This is the TPU work unit:
        each chunk is one contiguous IO + one batch decode.  With
        ``prefetch`` > 0 (argument, else the reader's setting) the group's
        chunks decode concurrently on the pipeline pool.
        """
        if not 0 <= index < self.num_row_groups:
            raise IndexError(f"row group {index} of {self.num_row_groups}")
        k = self.prefetch if prefetch is None else int(prefetch)
        if k > 0:
            # contain=False: an EXPLICITLY requested group must raise, not
            # silently skip itself (the iteration APIs own the skip policy)
            for _i, out in self._decode_row_groups([index], k,
                                                   contain=False):
                return out
        rg = self.metadata.row_groups[index]
        self.alloc.reset()
        leaves = self.schema.selected_leaves()
        by_path = {l.path: l for l in leaves}
        out: dict[str, ColumnData] = {}
        # every byte enters through the store, sequential path included —
        # the fault-tolerance (and fault-injection) contract covers
        # prefetch=0 bit-identically.  begin_scan here means the "scan"
        # unit on this path is one row group (a looser retry-budget bound
        # than the pipelined whole-iteration scan, but bounded) — and a
        # watchdog abort from a previous scan never poisons this one.
        self._sr.set_scan(self._store.begin_scan(cancel=self._cancel))
        f = self._sr.as_file()
        # the one shared footer walk (scanplan.py): unselected chunks'
        # bytes are never read (skipChunk parity)
        from .scanplan import row_group_chunks

        rc = self._result_cache
        for path, leaf, chunk, md, offset in row_group_chunks(rg, by_path):
            if self._cancel is not None:
                self._cancel.check()  # unit boundary: stop issuing new IO
            name = ".".join(path)

            def decode_chunk(chunk=chunk, leaf=leaf, md=md, offset=offset):
                return read_chunk(
                    f, chunk, leaf,
                    validate_crc=self.validate_crc, alloc=self.alloc,
                    context={"file": self._source_name, "row_group": index},
                    dict_cache=self._dict_cache, meta=(md, offset),
                )

            if rc is not None:
                # decoded-result seam, sequential path: same contract as
                # the pipelined one (see _decode_row_groups)
                out[name] = rc.get_or_build(index, name,
                                            _cache_build(decode_chunk))
            else:
                out[name] = decode_chunk()
        missing = set(".".join(p) for p in by_path) - set(out)
        if missing:
            raise ParquetError(f"row group {index} missing columns {sorted(missing)}")
        return out

    def iter_row_groups(self, prefetch: Optional[int] = None):
        k = self.prefetch if prefetch is None else int(prefetch)
        selected = [i for i in range(self.num_row_groups)
                    if self.row_group_selected(i)]  # pruned: bytes never read
        if k > 0:
            for _i, out in self._decode_row_groups(selected, k):
                yield out
            return
        q = self.quarantine
        q.begin_scan(len(selected))
        for i in selected:
            if not q.contains:
                yield self.read_row_group(i, prefetch=0)
                continue
            try:
                out = self.read_row_group(i, prefetch=0)
            except ParquetError as e:
                # containment (quarantine.py): the unit is recorded and
                # skipped; a budget-exhausted DataIntegrityError aborts
                if isinstance(e, DataIntegrityError):
                    raise
                q.note(e, file=self._source_name, row_group=i)
                q.note_unit_skipped(
                    int(self.metadata.row_groups[i].num_rows or 0))
                if q.policy == "skip_file":
                    q.note_file_skipped()
                    for j in selected[selected.index(i) + 1:]:
                        q.note_unit_skipped(int(
                            self.metadata.row_groups[j].num_rows or 0))
                    return
                continue
            yield out

    def read_all(self, prefetch: Optional[int] = None) -> dict[str, ColumnData]:
        """Concatenate all row groups' columns (convenience for small files).

        ``prefetch`` overrides the reader's pipeline depth for this call
        (0 forces the sequential path, K > 0 the overlapped one)."""
        groups = list(self.iter_row_groups(prefetch=prefetch))
        if not groups:
            return {
                ".".join(l.path): ColumnData(
                    values=np.zeros(0, dtype=np.int64),
                    max_def=l.max_def, max_rep=l.max_rep,
                )
                for l in self.schema.selected_leaves()
            }
        if len(groups) == 1:
            return groups[0]
        out = {}
        for key in groups[0]:
            out[key] = _concat_column_data([g[key] for g in groups])
        return out

    # -- row-group cursor (SeekToRowGroup/SkipRowGroup/PreLoad parity) ---------

    def seek_to_row_group(self, index: int) -> None:
        if not 0 <= index < self.num_row_groups:
            raise IndexError(f"row group {index} of {self.num_row_groups}")
        if index != self._current_row_group:
            self._preloaded = None
        self._current_row_group = index

    def skip_row_group(self) -> None:
        if self._current_row_group >= self.num_row_groups:
            raise IndexError("already past the last row group")
        self._current_row_group += 1
        self._preloaded = None

    def preload(self) -> dict[str, ColumnData]:
        """Decode the cursor's row group now and cache it (PreLoad parity,
        file_reader.go:280-288).  Row iteration consumes this cache."""
        if self._current_row_group >= self.num_row_groups:
            raise IndexError("no row group to preload")
        if self._preloaded is None:
            self._preloaded = self.read_row_group(self._current_row_group)
        return self._preloaded

    def current_row_group(self):
        if self._current_row_group >= self.num_row_groups:
            raise IndexError("cursor past the last row group")
        return self.metadata.row_groups[self._current_row_group]

    # -- row-oriented API (NextRow parity) -------------------------------------

    def iter_rows(self):
        """Iterate raw nested dict rows (reference NextRow semantics)."""
        from .assembly import RowIterator

        return RowIterator(self)

    def iter_rows_logical(self):
        """Iterate rows with LIST/MAP wrappers unwrapped to python lists/dicts."""
        from .logical import unwrap_row

        for row in self.iter_rows():
            yield unwrap_row(self.schema, row)

    def __iter__(self):
        return self.iter_rows()

    # -- python-value conversion ----------------------------------------------

    def read_pylist(self) -> dict[str, list]:
        """Flat columns as Python lists with None for nulls (testing/CLI aid)."""
        out = {}
        for name, cd in self.read_all().items():
            leaf = self.schema.leaf_by_path(tuple(name.split(".")))
            out[name] = column_to_pylist(cd, leaf)
        return out


def _cache_build(decode):
    """Adapt a no-arg chunk decode to the result cache's get_or_build
    contract (``build() -> (value, nbytes)``)."""
    def build():
        from .serve.result_cache import column_nbytes

        cd = decode()
        return cd, column_nbytes(cd)
    return build


def _concat_column_data(parts: list[ColumnData]) -> ColumnData:
    first = parts[0]

    def cat_opt(attr):
        arrs = [getattr(p, attr) for p in parts]
        if any(a is None for a in arrs):
            return None
        return np.concatenate(arrs)

    if isinstance(first.values, ByteArrayData):
        offsets = [first.values.offsets]
        heaps = [first.values.heap]
        base = int(first.values.offsets[-1])
        for p in parts[1:]:
            offsets.append(p.values.offsets[1:] + base)
            heaps.append(p.values.heap)
            base += int(p.values.offsets[-1])
        values = ByteArrayData(np.concatenate(offsets), np.concatenate(heaps))
    else:
        values = np.concatenate([p.values for p in parts])
    return ColumnData(
        values=values,
        def_levels=cat_opt("def_levels"),
        rep_levels=cat_opt("rep_levels"),
        max_def=first.max_def,
        max_rep=first.max_rep,
        num_leaf_slots=sum(p.num_leaf_slots for p in parts),
    )


def column_to_pylist(cd: ColumnData, leaf: Optional[SchemaNode] = None) -> list:
    """Flat (max_rep==0) column → Python list with None in null slots.

    BYTE_ARRAY becomes str when the column is logically UTF8, else bytes.
    """
    if cd.max_rep > 0:
        raise ParquetError("column_to_pylist only handles flat columns")
    from .assembly import materialize_leaf_values

    vals = materialize_leaf_values(leaf, cd) if leaf is not None else (
        cd.values.to_list() if isinstance(cd.values, ByteArrayData) else cd.values.tolist()
    )
    if cd.def_levels is None:
        return vals
    out = [None] * cd.num_leaf_slots
    vi = 0
    mask = cd.validity()
    for i in range(cd.num_leaf_slots):
        if mask[i]:
            out[i] = vals[vi]
            vi += 1
    return out
