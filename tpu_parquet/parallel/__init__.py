"""Multi-chip / multi-host parallel decode (SPMD over a jax.sharding.Mesh).

The reference is strictly single-threaded value-at-a-time (TODO.md:15 — the
reader is not concurrent); its natural block hierarchy (file → row group →
column chunk → page, SURVEY.md §5.7) is what this module turns into parallel
axes:

- **pages** of identical geometry batch under ``vmap`` and shard over the mesh's
  ``data`` axis with ``shard_map`` — each device decodes its slice of the page
  batch, and cross-device reductions (global stats) ride ICI collectives
  (``psum``/``pmin``/``pmax``), never the host;
- **row groups** are embarrassingly parallel and are *assigned*, not exchanged:
  a greedy LPT plan balances compressed bytes across shards (hosts or chips) —
  the §5.8 stance that the decode path needs sharded work lists, not an
  NCCL-analog exchange;
- **multi-host**: each process decodes the row groups its shard owns;
  ``jax.make_array_from_process_local_data`` assembles the global sharded array
  view when a training step consumes the columns.

Everything compiles once per page geometry: within a mesh the per-device page
count is static, so the same executable serves every batch of that shape.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..format import Type
from .. import jax_kernels as K
from ..jax_kernels import scoped_x64
from ..jax_decode import HybridMeta, DeltaMeta, parse_hybrid_meta, parse_delta_meta, _bucket, _SLACK

def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

__all__ = [
    "make_mesh",
    "plan_shards",
    "process_shard",
    "shard_scan_row_groups",
    "PageBatch",
    "pack_hybrid_pages",
    "pack_delta_pages",
    "sharded_dict_decode",
    "sharded_dict_decode_2d",
    "sharded_delta_decode",
    "sharded_plain_decode",
    "column_stats",
    "shard_row_ranges",
    "decode_row_span",
    "global_column_array",
    "process_local_column",
]


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None, axis: str = "data"
) -> Mesh:
    """1-D data mesh over all (or given) devices — the decode path needs no
    model axis; re-sharding decoded columns onto a 2-D mesh is the consumer's
    pjit's job (XLA inserts the all-to-all)."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis,))


# ---------------------------------------------------------------------------
# Work-list sharding (row groups → shards)
# ---------------------------------------------------------------------------

def plan_shards(sizes: Sequence[int], n_shards: int) -> list[list[int]]:
    """Greedy LPT assignment of row groups to shards, balanced by byte size.

    ``sizes[i]`` is row group i's total_compressed_size (or total_byte_size).
    Returns per-shard lists of row-group indices.  Deterministic, so every
    host computes the identical plan from the shared footer — no coordination
    traffic (DCN only ships the footer, per SURVEY.md §5.8).
    """
    if n_shards <= 0:
        raise ValueError(f"n_shards must be positive, got {n_shards}")
    order = sorted(range(len(sizes)), key=lambda i: -int(sizes[i]))
    loads = [0] * n_shards
    plan: list[list[int]] = [[] for _ in range(n_shards)]
    for i in order:
        s = loads.index(min(loads))
        plan[s].append(i)
        loads[s] += int(sizes[i])
    for shard in plan:
        shard.sort()
    return plan


def process_shard() -> tuple[int, int]:
    """This process's ``(shard_index, n_shards)`` under ``jax.distributed``.

    The shard tuple ``data.DataLoader`` (and any plan_shards caller) wants on
    a multi-host job: every host derives the identical LPT plan from the
    shared footers, so the only coordination is jax.distributed's own
    process enumeration.  On a single-process runtime this is ``(0, 1)`` —
    the same code serves tests and clusters.
    """
    return int(jax.process_index()), int(jax.process_count())


def _reader_prefetch(reader) -> int:
    """A reader's configured pipeline depth: FileReader exposes ``prefetch``,
    DeviceFileReader ``_prefetch``; any other reader defaults to 0."""
    return int(getattr(reader, "prefetch", None)
               or getattr(reader, "_prefetch", 0) or 0)


def shard_scan_row_groups(reader, shard_index: int, n_shards: int,
                          prefetch: Optional[int] = None):
    """Decode the row groups LPT-assigned to ``shard_index``, pipelined.

    The per-SHARD pipeline form of the work-list split: every shard computes
    the identical byte-balanced plan from the shared footer (plan_shards —
    no coordination traffic) and decodes only its own groups, each through
    the reader's overlapped chunk pipeline (``prefetch`` per-call override;
    the reader's own setting otherwise).  Shards run in different
    processes/hosts, so pipelines are deliberately per-shard rather than
    one global pool.  Yields ``(row_group_index, {column: ColumnData})``.
    """
    sizes = [
        sum(cc.meta_data.total_compressed_size or 0
            for cc in (rg.columns or []) if cc.meta_data is not None)
        for rg in reader.metadata.row_groups
    ]
    plan = plan_shards(sizes, n_shards)
    if not 0 <= shard_index < n_shards:
        raise ValueError(f"shard {shard_index} of {n_shards}")
    mine = plan[shard_index]
    k = _reader_prefetch(reader) if prefetch is None else int(prefetch)
    if k > 0 and hasattr(reader, "_decode_row_groups"):
        # ONE pipeline over the whole shard: the window spans group
        # boundaries (per-group read_row_group calls would build and drain
        # a pool at every boundary — exactly the stall this exists to hide)
        yield from reader._decode_row_groups(mine, k)
        return
    for i in mine:
        # bare call: the generic reader contract (a DeviceFileReader's
        # read_row_group has no prefetch kwarg)
        yield i, reader.read_row_group(i)


# ---------------------------------------------------------------------------
# Page batching: N same-geometry pages → stacked device arrays
# ---------------------------------------------------------------------------

@dataclass
class PageBatch:
    """A batch of same-geometry encoded pages, stacked for vmap/shard_map.

    ``bufs`` u8[B, S]: padded page bytes.  Hybrid (dictionary-index) batches
    carry run tables [B, R]; delta batches carry miniblock tables [B, M].
    ``count`` values per page is uniform; a short tail page is padded with a
    synthetic zero run via pack_hybrid_pages(counts=...) and callers slice the
    decoded tail back (delta batches require equal counts — pack_delta_pages
    raises otherwise).
    """

    bufs: jax.Array
    count: int
    width: int = 0                      # hybrid: index bit width
    run_ends: Optional[jax.Array] = None
    run_is_rle: Optional[jax.Array] = None
    run_values: Optional[jax.Array] = None
    run_bit_starts: Optional[jax.Array] = None
    first_values: Optional[jax.Array] = None    # delta: per-page seed
    mini_bit_starts: Optional[jax.Array] = None
    mini_widths: Optional[jax.Array] = None
    mini_min_delta: Optional[jax.Array] = None
    values_per_mini: int = 0
    max_width: int = 0

    @property
    def num_pages(self) -> int:
        return int(self.bufs.shape[0])


def _stack_padded_bufs(raws: list[bytes]) -> np.ndarray:
    size = _bucket(max(len(r) for r in raws) + _SLACK, 64)
    out = np.zeros((len(raws), size), dtype=np.uint8)
    for i, r in enumerate(raws):
        out[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
    return out


@scoped_x64
def pack_hybrid_pages(
    raws: list[bytes],
    width: int,
    count: int,
    pos: int = 0,
    counts: Optional[Sequence[int]] = None,
) -> PageBatch:
    """Parse + stack N hybrid (RLE/bit-packed) streams of ``count`` values each.

    ``counts`` gives per-page actual value counts when they differ (the usual
    short tail page): shorter pages are padded to ``count`` with a synthetic
    zero-value RLE run, and callers slice the decoded tail back to its real
    length.  Host cost is O(total run headers); run tables are padded to the
    batch max (power-of-two bucketed) so one executable serves all batches of
    this shape.
    """
    per_page = list(counts) if counts is not None else [count] * len(raws)
    if len(per_page) != len(raws):
        raise ValueError(f"{len(per_page)} counts for {len(raws)} pages")
    if any(c > count for c in per_page):
        raise ValueError(f"page count exceeds batch count {count}")
    metas = [
        parse_hybrid_meta(r, width, c, pos=pos) for r, c in zip(raws, per_page)
    ]
    for m, c in zip(metas, per_page):
        if c < count:  # pad: one RLE run of zeros fills the tail
            # run_ends stays sorted: real runs end ≤ c, bucket padding == c,
            # the synthetic run == count, so the tail slots fall in this run
            m.run_ends = np.concatenate([m.run_ends, [count]]).astype(np.int64)
            m.run_is_rle = np.concatenate([m.run_is_rle, [True]])
            m.run_values = np.concatenate([m.run_values, [0]]).astype(np.uint32)
            m.run_bit_starts = np.concatenate([m.run_bit_starts, [0]]).astype(np.int64)
    r_max = max(m.run_ends.shape[0] for m in metas)
    ends = np.full((len(metas), r_max), count, dtype=np.int64)
    is_rle = np.zeros((len(metas), r_max), dtype=bool)
    vals = np.zeros((len(metas), r_max), dtype=np.uint32)
    starts = np.zeros((len(metas), r_max), dtype=np.int64)
    for i, m in enumerate(metas):
        r = m.run_ends.shape[0]
        ends[i, :r] = m.run_ends
        is_rle[i, :r] = m.run_is_rle
        vals[i, :r] = m.run_values
        starts[i, :r] = m.run_bit_starts
    return PageBatch(
        bufs=jnp.asarray(_stack_padded_bufs(raws)),
        count=count,
        width=width,
        run_ends=jnp.asarray(ends),
        run_is_rle=jnp.asarray(is_rle),
        run_values=jnp.asarray(vals),
        run_bit_starts=jnp.asarray(starts),
    )


@scoped_x64
def pack_delta_pages(raws: list[bytes], bits: int, count: int) -> PageBatch:
    """Parse + stack N DELTA_BINARY_PACKED streams of ``count`` values each."""
    metas = [parse_delta_meta(r, bits) for r in raws]
    for m in metas:
        if m.count != count:
            raise ValueError(f"page holds {m.count} values, batch expects {count}")
    m_max = max(m.mini_bit_starts.shape[0] for m in metas)
    starts = np.zeros((len(metas), m_max), dtype=np.int64)
    widths = np.zeros((len(metas), m_max), dtype=np.int32)
    mins = np.zeros((len(metas), m_max), dtype=np.uint64)
    firsts = np.zeros(len(metas), dtype=np.int64)
    for i, m in enumerate(metas):
        k = m.mini_bit_starts.shape[0]
        starts[i, :k] = m.mini_bit_starts
        widths[i, :k] = m.mini_widths
        mins[i, :k] = m.mini_min_delta
        firsts[i] = m.first_value
    return PageBatch(
        bufs=jnp.asarray(_stack_padded_bufs(raws)),
        count=count,
        first_values=jnp.asarray(firsts),
        mini_bit_starts=jnp.asarray(starts),
        mini_widths=jnp.asarray(widths),
        mini_min_delta=jnp.asarray(mins),
        values_per_mini=metas[0].values_per_mini,
        max_width=max(1, *(int(m.mini_widths.max(initial=0)) for m in metas)),
    )


# ---------------------------------------------------------------------------
# Sharded decode steps (shard_map over the data axis)
# ---------------------------------------------------------------------------

@scoped_x64
def sharded_dict_decode(
    batch: PageBatch, dict_u8: jax.Array, dtype: str, mesh: Mesh,
    axis: str = "data", with_stats: bool = False,
):
    """Decode a batch of dictionary-index pages and gather values, sharded.

    Pages shard across ``axis``; the dictionary replicates (it is per-chunk and
    small — ≤ 32767 entries by the format's own fallback rule).  Returns the
    decoded values [B, count, ...] with the same sharding, so a downstream pjit
    consumes them without a host round-trip; XLA inserts any re-shard
    collectives.  ``with_stats`` adds a psum/pmin/pmax over ICI — the global
    column statistics every shard sees identically.
    """
    width, count = batch.width, batch.count

    def shard_fn(bufs, ends, is_rle, vals, starts, d_u8):
        idx = jax.vmap(
            lambda b, e, r, v, s: K.expand_rle_hybrid(b, e, r, v, s, width, count)
        )(bufs, ends, is_rle, vals, starts)
        flat = K.dict_gather_bytes(d_u8, idx.reshape(-1), dtype)
        out = flat.reshape(idx.shape + flat.shape[1:])
        if not with_stats:
            return out, jnp.zeros(3, dtype=jnp.int64)
        stats = jnp.stack([
            jax.lax.psum(jnp.int64(idx.size), axis),
            jax.lax.pmin(jnp.min(idx).astype(jnp.int64), axis),
            jax.lax.pmax(jnp.max(idx).astype(jnp.int64), axis),
        ])
        return out, stats

    fn = _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(None, None)),
        out_specs=(P(axis, None), P()),
    )
    return fn(
        batch.bufs, batch.run_ends, batch.run_is_rle, batch.run_values,
        batch.run_bit_starts, dict_u8,
    )


@scoped_x64
def sharded_dict_decode_2d(
    batch: PageBatch, dict_u8: jax.Array, dtype: str, mesh: Mesh,
    data_axis: str = "data", model_axis: str = "model",
):
    """Dict decode on a 2-D mesh: pages shard over ``data``, the *dictionary*
    shards over ``model`` — the expert-parallel-shaped variant for dictionaries
    too large to replicate.

    Each device gathers only the indices that fall in its dictionary shard
    (masked local gather) and a psum over ``model`` assembles full values: the
    index-routing pattern of MoE dispatch, with the reduction riding ICI.
    Requires an integer ``dtype`` (psum assembles words exactly; float dicts
    replicate via :func:`sharded_dict_decode` instead).
    """
    width, count = batch.width, batch.count
    n_model = mesh.shape[model_axis]
    k = int(dict_u8.shape[0])
    shard_rows = (k + n_model - 1) // n_model
    pad_rows = shard_rows * n_model - k
    if pad_rows:
        dict_u8 = jnp.concatenate(
            [dict_u8, jnp.zeros((pad_rows, dict_u8.shape[1]), dtype=jnp.uint8)]
        )

    def shard_fn(bufs, ends, is_rle, vals, starts, d_u8_local):
        m = jax.lax.axis_index(model_axis)
        lo = m.astype(jnp.int64) * shard_rows
        idx = jax.vmap(
            lambda b, e, r, v, s: K.expand_rle_hybrid(b, e, r, v, s, width, count)
        )(bufs, ends, is_rle, vals, starts)
        flat = idx.reshape(-1).astype(jnp.int64)
        local = flat - lo
        mine = (local >= 0) & (local < shard_rows)
        safe = jnp.clip(local, 0, shard_rows - 1).astype(jnp.int32)
        gathered = K.dict_gather_bytes(d_u8_local, safe, dtype)
        gathered = jnp.where(
            mine.reshape(mine.shape + (1,) * (gathered.ndim - 1)),
            gathered,
            jnp.zeros((), dtype=gathered.dtype),
        )
        full = jax.lax.psum(gathered, model_axis)
        return full.reshape(idx.shape + full.shape[1:])

    fn = _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(data_axis, None), P(data_axis, None), P(data_axis, None),
                  P(data_axis, None), P(data_axis, None), P(model_axis, None)),
        out_specs=P(data_axis, None),
    )
    return fn(
        batch.bufs, batch.run_ends, batch.run_is_rle, batch.run_values,
        batch.run_bit_starts, dict_u8,
    )


@scoped_x64
def sharded_delta_decode(
    batch: PageBatch, bits: int, mesh: Mesh, axis: str = "data",
):
    """Decode a batch of DELTA_BINARY_PACKED pages, sharded over the mesh."""
    count = batch.count
    vpm, mw = batch.values_per_mini, batch.max_width

    def shard_fn(bufs, firsts, starts, widths, mins):
        return jax.vmap(
            lambda b, f, s, w, m: K.delta_reconstruct(
                b, f, s, w, m, vpm, count, bits, mw
            )
        )(bufs, firsts, starts, widths, mins)

    fn = _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=P(axis, None),
    )
    return fn(
        batch.bufs, batch.first_values, batch.mini_bit_starts,
        batch.mini_widths, batch.mini_min_delta,
    )


@scoped_x64
def sharded_plain_decode(
    bufs: jax.Array, dtype: str, count: int, mesh: Mesh, axis: str = "data",
):
    """PLAIN fixed-width pages [B, S] → values [B, count], sharded bitcast."""

    def shard_fn(b):
        return jax.vmap(lambda x: K.plain_decode_fixed(x, dtype, count))(b)

    fn = _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(axis, None),),
        out_specs=P(axis, None),
    )
    return fn(bufs)


@scoped_x64
def column_stats(values: jax.Array, mesh: Mesh, axis: str = "data"):
    """Global min/max/count over a sharded int column — one ICI reduction.

    The device-side analog of the reference's write-side stats trackers
    (stats.go): every shard computes local extrema, psum/pmin/pmax make them
    global without gathering the data anywhere.
    """

    def shard_fn(v):
        return jnp.stack([
            jax.lax.psum(jnp.int64(v.size), axis),
            jax.lax.pmin(jnp.min(v).astype(jnp.int64), axis),
            jax.lax.pmax(jnp.max(v).astype(jnp.int64), axis),
        ])

    fn = _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(axis, None),), out_specs=P(),
    )
    return fn(values)


# ---------------------------------------------------------------------------
# Multi-host work list → global sharded array (SURVEY.md §5.8)
# ---------------------------------------------------------------------------

def shard_row_ranges(total_rows: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous, equal-size row spans, one per shard (last may be short).

    Equal spans are what a NamedSharding over the row axis requires; each
    shard decodes only the row groups its span touches (boundary groups are
    decoded by both neighbors and sliced — the standard input-pipeline trade
    against cross-host exchange).  Deterministic from (total_rows, n_shards),
    so every host derives the identical plan from the footer alone — DCN
    carries no work-list coordination, matching SURVEY.md §5.8.
    """
    if n_shards <= 0:
        raise ValueError(f"n_shards must be positive, got {n_shards}")
    per = -(-total_rows // n_shards) if total_rows else 0
    return [
        (min(i * per, total_rows), min((i + 1) * per, total_rows))
        for i in range(n_shards)
    ]


_FIXED_DTYPES = {
    Type.INT32: np.dtype(np.int32),
    Type.INT64: np.dtype(np.int64),
    Type.FLOAT: np.dtype(np.float32),
    Type.DOUBLE: np.dtype(np.float64),
    Type.BOOLEAN: np.dtype(bool),
}


def column_span_dtype(reader, column: str) -> np.dtype:
    """The numpy dtype a flat column decodes to — derivable from the schema
    alone, so shards with EMPTY spans pad with the right dtype without
    decoding anything."""
    leaf = reader.schema.leaf_by_path(tuple(column.split(".")))
    if leaf is None:
        raise KeyError(f"no such column {column!r}")
    dt = _FIXED_DTYPES.get(leaf.physical_type)
    if dt is None:
        raise TypeError(
            f"global span decode needs a fixed-width column; {column!r} is "
            f"{leaf.physical_type!r}"
        )
    return dt


def decode_row_span(reader, column: str, row_start: int, row_end: int,
                    prefetch: Optional[int] = None) -> np.ndarray:
    """Decode exactly rows [row_start, row_end) of a flat column on host.

    Touches only the row groups the span intersects (others are never read —
    the skipChunk discipline of chunk_reader.go:271-297 at row-group
    granularity) and slices boundary groups.  Column selection is narrowed to
    the one requested column for the duration of the call, so sibling chunks
    in touched row groups are seeked past, not decoded.

    ``prefetch`` > 0 routes each touched group through the reader's chunk
    pipeline (reader.FileReader prefetch semantics) — the per-SHARD decode
    pipeline: every shard of a work list overlaps its own IO and
    decompression independently, so a multi-host scan pipelines on every
    host without coordination.
    """
    dtype = column_span_dtype(reader, column)
    parts = []
    base = 0
    # touched groups + their row slices, planned up front so the pipelined
    # path can run ONE window across all of them (a per-group
    # read_row_group call would drain the pool at every boundary)
    touched = []  # (index, lo, hi, n)
    for i, rg in enumerate(reader.metadata.row_groups):
        n = rg.num_rows
        lo, hi = max(row_start - base, 0), min(row_end - base, n)
        if lo < hi:
            touched.append((i, lo, hi, n))
        base += n
        if base >= row_end:
            break
    k = _reader_prefetch(reader) if prefetch is None else int(prefetch)
    prev_selected = [tuple(l.path) for l in reader.schema.selected_leaves()]
    reader.schema.set_selected([tuple(column.split("."))])
    try:
        spans = {i: (lo, hi, n) for i, lo, hi, n in touched}
        if k > 0 and hasattr(reader, "_decode_row_groups"):
            groups = reader._decode_row_groups(sorted(spans), k)
        elif hasattr(reader, "_decode_row_groups"):
            # our FileReader: honor an explicit prefetch=0 even when the
            # reader's own setting is pipelined
            groups = ((i, reader.read_row_group(i, prefetch=0))
                      for i in sorted(spans))
        else:
            # generic reader contract: bare call only
            groups = ((i, reader.read_row_group(i)) for i in sorted(spans))
        for i, cols in groups:
            lo, hi, n = spans[i]
            cd = cols[column]
            vals = cd.values
            if len(vals) != n:
                raise ValueError(
                    f"decode_row_span requires a flat required column; "
                    f"{column!r} has {len(vals)} values for {n} rows"
                )
            parts.append(np.asarray(vals)[lo:hi])
    finally:
        reader.schema.set_selected(prev_selected)
    if not parts:
        return np.zeros(0, dtype=dtype)
    return np.concatenate(parts) if len(parts) > 1 else parts[0]


def _pad_span(local: np.ndarray, per: int, dtype: np.dtype) -> np.ndarray:
    """Zero-pad a decoded span to the uniform shard size (tail/empty shards)."""
    if len(local) >= per:
        return local
    return np.concatenate(
        [local.astype(dtype), np.zeros(per - len(local), dtype=dtype)]
    )


@scoped_x64
def global_column_array(
    reader, column: str, mesh: Mesh, axis: str = "data",
    prefetch: Optional[int] = None,
) -> tuple[jax.Array, int]:
    """Work-list → one global row-sharded device array (single-host form).

    Every addressable device in ``mesh`` stands in for one shard of the work
    list: shard i decodes its row span on host and its slice is placed on its
    device; ``jax.make_array_from_single_device_arrays`` stitches the global
    view without any cross-device exchange (row groups are assigned, not
    traded — SURVEY.md §5.7/5.8).  Returns (global_array, valid_rows):
    the tail shard is zero-padded to the uniform span size, so the global
    length is per*n — consumers mask with valid_rows.
    """
    total = int(reader.metadata.num_rows)
    # shard the work list along the NAMED axis only; other mesh axes (e.g. a
    # model axis on a 2-D mesh) see the same rows replicated — each span is
    # decoded once and placed on every device whose ``axis`` coordinate
    # matches, so the function serves any mesh rank, not just 1-D
    n = int(mesh.shape[axis])
    ax = mesh.axis_names.index(axis)
    spans = shard_row_ranges(total, n)
    per = spans[0][1] - spans[0][0] if total else 0
    sharding = NamedSharding(mesh, P(axis))
    dtype = column_span_dtype(reader, column)
    if not per:
        return jnp.zeros((0,), dtype=dtype), 0
    decoded = [
        _pad_span(decode_row_span(reader, column, lo, hi, prefetch=prefetch),
                  per, dtype)
        for lo, hi in spans
    ]
    pieces = [
        jax.device_put(decoded[idx[ax]], dev)
        for idx, dev in np.ndenumerate(mesh.devices)
    ]
    global_shape = (per * n,)
    arr = jax.make_array_from_single_device_arrays(global_shape, sharding, pieces)
    return arr, total


@scoped_x64
def process_local_column(
    reader, column: str, mesh: Mesh, axis: str = "data",
    prefetch: Optional[int] = None,
) -> tuple[jax.Array, int]:
    """True multi-host form: this process decodes only ITS span of the work
    list and contributes it via ``jax.make_array_from_process_local_data``.

    Each host computes the identical plan from the shared footer
    (shard_row_ranges over jax.process_count()), decodes the rows owned by
    its process, and the runtime assembles the global sharded array — the
    decode path's only cross-host traffic is the ICI/DCN assembly the
    consumer's pjit triggers.  On a single-process mesh this degrades to
    decoding everything locally, so the same code serves tests and clusters.
    """
    total = int(reader.metadata.num_rows)
    nproc = jax.process_count()
    spans = shard_row_ranges(total, nproc)
    lo, hi = spans[jax.process_index()]
    per = spans[0][1] - spans[0][0] if total else 0
    local = _pad_span(decode_row_span(reader, column, lo, hi,
                                      prefetch=prefetch), per,
                      column_span_dtype(reader, column))
    sharding = NamedSharding(mesh, P(axis))
    arr = jax.make_array_from_process_local_data(
        sharding, local, (per * nproc,)
    )
    return arr, total
