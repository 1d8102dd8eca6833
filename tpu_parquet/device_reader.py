"""Batched device reader: one staged buffer + one fused dispatch per chunk.

The page-at-a-time DeviceChunkDecoder (jax_decode.py) is correct but transfer-
latency-bound: every page pays several host→device staging calls, and each
blocking transfer has a fixed cost regardless of size.
This reader restructures the decode around the transfer economics
(SURVEY.md §7.4.7 — pipelining beats any single kernel):

- per chunk, ALL pages' decompressed value bytes are assembled into ONE host
  buffer and staged with ONE async transfer;
- per-page stream structure is folded into chunk-global metadata tables
  (hybrid run tables with global bit offsets; per-page delta miniblock tables
  stacked for vmap), so each column decodes with ONE fused XLA dispatch;
- nothing blocks until ``finalize()``: staging and dispatches are async, the
  deferred dictionary-index range checks sync once at the end;
- dictionary string columns stay dictionary-encoded on device — (dict bytes,
  indices) like an Arrow DictionaryArray — and materialize lazily, because the
  gather output size is data-dependent and forcing it would sync per chunk.

Encoding coverage matches DeviceChunkDecoder; byte-array value streams decode
on host (inherently sequential, SURVEY.md §7.4.2/§7.4.4) and stage their
(offsets, heap) result in two async transfers.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from contextlib import nullcontext as _noop_ctx
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import jax_kernels as K
from .jax_kernels import scoped_x64
from .chunk_decode import _check_crc, walk_pages
from .column import ByteArrayData
from .kernels import bitpack
from .compress import decompress_block
from .footer import ParquetError
from .format import Encoding, PageType, Type, parse_encoding
from .iostore import require_full
from .scanplan import int_stats_span as _int_stats_span, row_group_chunks
from .jax_decode import (
    DeviceColumnData, ParsedDataPage, _bucket, _bucket_bytes, _bucket_count,
    _SLACK, _concat_jit, _concat_ragged_jit, _dict_gather_bytes_jit,
    _dict_rows_jit, _hybrid_jit, _hybrid_vw_jit, _max_jit, _plain_flba_jit,
    _plain_jit, _plain_rows_jit, _PTYPE_TO_NAME, _stack_jit,
    host_decode_dictionary, parse_data_page, parse_hybrid_meta, parse_delta_meta,
)
from .pallas_kernels import bp_value_index
from .schema.core import SchemaNode
from .ship import (
    ChunkFacts, FUSED_ROUTES, ROUTE_DEVICE_SNAPPY, ROUTE_FUSED_PLAIN,
    ROUTE_NARROW, ROUTE_NARROW_SNAPPY, ROUTE_PLAIN,
    ROUTE_RECOMPRESS, SNAPPY_WORTH_RATIO, ShipPlanner, default_planner,
)

__all__ = ["DeviceFileReader", "DeviceStats", "ReaderStats",
           "decode_chunk_batched", "DeviceDictColumn", "scan_files"]


@dataclass
class DeviceDictColumn(DeviceColumnData):
    """A dictionary-encoded device column: values stay as (dictionary, indices).

    ``indices`` uint32[n_defined]; the dictionary is either fixed-width byte
    rows (``dict_u8`` + ``dict_dtype``) or ragged (``dict_offsets``/``dict_heap``).
    ``materialize()`` gathers on device (fixed-width) or host (ragged).
    """

    indices: Optional[jax.Array] = None
    dict_u8: Optional[jax.Array] = None
    dict_dtype: Optional[str] = None
    dict_offsets: Optional[jax.Array] = None
    dict_heap: Optional[jax.Array] = None

    @scoped_x64
    def materialize(self) -> DeviceColumnData:
        if self.dict_u8 is not None:
            # padded tail indices are zeros (expand_rle_hybrid n_valid mask),
            # so the gather stays in bounds; n_values carries the real count
            vals = _dict_gather_bytes_jit(self.dict_u8, self.indices, dtype=self.dict_dtype)
            return DeviceColumnData(
                values=vals, def_levels=self.def_levels, rep_levels=self.rep_levels,
                max_def=self.max_def, max_rep=self.max_rep,
                num_leaf_slots=self.num_leaf_slots, value_dtype=self.value_dtype,
                n_values=self.n_values,
            )
        off = np.asarray(self.dict_offsets)
        heap = np.asarray(self.dict_heap)
        idx = np.asarray(self.indices, dtype=np.int64)[: self.num_values]
        host = ByteArrayData(offsets=off, heap=heap).take(idx)
        return DeviceColumnData(
            offsets=jnp.asarray(host.offsets), heap=jnp.asarray(host.heap),
            def_levels=self.def_levels, rep_levels=self.rep_levels,
            max_def=self.max_def, max_rep=self.max_rep,
            num_leaf_slots=self.num_leaf_slots,
        )

    @property
    def num_values(self) -> int:
        if self.n_values is not None:
            return self.n_values
        return int(self.indices.shape[0]) if self.indices is not None else 0

    def to_host(self):
        off_or_none = self.dict_offsets
        idx = np.asarray(self.indices, dtype=np.int64)[: self.num_values]
        if self.dict_u8 is not None:
            rows = np.asarray(self.dict_u8)
            n, nb = rows.shape
            if self.dict_dtype == "uint32":  # INT96
                return rows.view("<u4").reshape(n, -1)[idx]
            return rows[idx].copy().view(f"<{np.dtype(self.dict_dtype).str[1:]}").reshape(len(idx))
        return ByteArrayData(
            offsets=np.asarray(off_or_none), heap=np.asarray(self.dict_heap)
        ).take(idx)


@functools.partial(
    jax.jit,
    static_argnames=("values_per_mini", "mb", "count", "bits", "max_width",
                     "total", "n_pages", "m_max"),
)
def _delta_pages_staged_jit(buf, tbase, *, values_per_mini, mb, count, bits,
                            max_width, total, n_pages, m_max):
    """_delta_pages_jit with COMPACT metadata tables read from the staged
    buffer at ``tbase``.

    The format carries one min-delta varint and one payload position per
    BLOCK (``mb`` miniblocks), and miniblock payloads are contiguous within
    a block — so the tables ship per-block starts/mins plus one width BYTE
    per mini (layout: firsts i64[P] | block_starts i32[P,B] | widths u8[P,M]
    | block_mins u64[P,B] | page_starts i64[P+1], B = M/mb), ~4 bytes per
    mini instead of the 20 of the round-3 per-mini tables — the tables were
    rivaling the payload bytes on 32-value-mini streams.  Per-mini starts
    and mins expand here in-graph (a within-block exclusive cumsum of the
    widths and a repeat)."""
    P, M = n_pages, m_max
    B = M // mb
    o = 0
    firsts = _tslice(buf, tbase, o, P, jnp.int64); o += P * 8
    bstarts = _tslice(buf, tbase, o, P * B, jnp.int32).reshape(P, B); o += P * B * 4
    widths_u8 = _tslice(buf, tbase, o, P * M, jnp.uint8).reshape(P, M); o += P * M
    bmins = _tslice(buf, tbase, o, P * B, jnp.uint64).reshape(P, B); o += P * B * 8
    page_starts = _tslice(buf, tbase, o, P + 1, jnp.int64)
    widths = widths_u8.astype(jnp.int32)
    bpm = (widths * (values_per_mini // 8)).reshape(P, B, mb)
    excl = jnp.cumsum(bpm, axis=-1) - bpm  # within-block byte offsets
    starts = ((bstarts.astype(jnp.int64)[:, :, None] + excl)
              .reshape(P, M)) * 8  # bit starts (minis are byte-aligned)
    mins = jnp.repeat(bmins, mb, axis=1)
    return _delta_pages_jit(
        buf, firsts, starts, widths, mins, page_starts,
        values_per_mini=values_per_mini, count=count, bits=bits,
        max_width=max_width, total=total,
    )


@functools.partial(
    jax.jit,
    static_argnames=("values_per_mini", "count", "bits", "max_width", "total"),
)
def _delta_pages_jit(buf, firsts, starts, widths, mins, page_starts, *,
                     values_per_mini, count, bits, max_width, total):
    """Decode P delta pages; flatten to the per-page real extents in-graph.

    Every shape here is *bucketed* static (page count, per-page value count,
    total output), and the real per-page extents arrive as the traced
    ``page_starts`` (int64[P+1], cumulative defined counts, last = real
    total).  One executable therefore serves every delta chunk whose geometry
    lands in the same buckets — per-page exact counts as static args would
    compile a fresh program per chunk, at seconds to tens of seconds
    each.  Tail lanes (pad pages, output past the real total)
    gather clamped garbage that callers slice off via ``n_values``.
    """
    vals = jax.vmap(
        lambda f, s, w, m: K.delta_reconstruct(
            buf, f, s, w, m, values_per_mini, count, bits, max_width
        )
    )(firsts, starts, widths, mins)
    i = jnp.arange(total, dtype=jnp.int64)
    p = jnp.searchsorted(page_starts, i, side="right") - 1
    p = jnp.clip(p, 0, vals.shape[0] - 1)
    within = jnp.clip(i - page_starts[p], 0, count - 1)
    return vals[p, within]


@functools.partial(jax.jit, static_argnames=("count_pad", "heap_pad",
                                             "n_pages"))
def _plain_bytes_staged_jit(buf, lens_base, tbase, *, count_pad, heap_pad,
                            n_pages):
    """_plain_bytes_pages_jit with the page tables read from the staged
    buffer (layout: page_byte_base i64[P] | page_val_start i32[P+1])."""
    page_byte_base = _tslice(buf, tbase, 0, n_pages, jnp.int64)
    page_val_start = _tslice(buf, tbase, n_pages * 8, n_pages + 1, jnp.int32)
    return _plain_bytes_pages_jit(
        buf, lens_base, page_byte_base, page_val_start,
        count_pad=count_pad, heap_pad=heap_pad,
    )


def _bytes_heap_src(buf, lens_base, page_base, page_val_start, *, count_pad,
                    heap_pad):
    """Shared front half of the BYTE_ARRAY routes: staged lengths → offsets
    and each heap byte's source position in PAGE-STREAM coordinates.

      offsets  = cumsum(lens)                              (int64[count+1])
      value r of heap byte j via a scatter-of-run-ends + cumsum
      src[j]   = page_base[p] + within-page data offset + 4*(prefixes so far)

    ``page_base`` is staged-buffer coords on the plain route and
    OUTPUT-SPACE coords on the compressed-shipping routes (the caller picks
    the final indirection).  Returns (offsets, src)."""
    lens_raw = jax.lax.dynamic_slice(buf, (lens_base,), (count_pad * 4,))
    lens = jax.lax.bitcast_convert_type(
        lens_raw.reshape(count_pad, 4), jnp.uint32
    ).reshape(count_pad)
    offsets = jnp.concatenate([
        jnp.zeros(1, dtype=jnp.int64),
        jnp.cumsum(lens.astype(jnp.int64)),
    ])
    ends = jnp.clip(offsets[1:], 0, heap_pad)
    marks = jnp.zeros(heap_pad + 1, dtype=jnp.int32).at[ends].add(
        jnp.ones(count_pad, dtype=jnp.int32)
    )
    r = jnp.cumsum(marks[:heap_pad])  # value index of each heap byte
    r = jnp.clip(r, 0, count_pad - 1)
    p = jnp.searchsorted(page_val_start, r, side="right").astype(jnp.int32) - 1
    p = jnp.clip(p, 0, page_base.shape[0] - 1)
    pvs = page_val_start[p].astype(jnp.int64)
    j = jnp.arange(heap_pad, dtype=jnp.int64)
    src = (page_base[p]
           + (offsets[r] - offsets[pvs])        # data bytes before r in page
           + 4 * (r.astype(jnp.int64) - pvs + 1)  # prefixes up to & incl. r
           + (j - offsets[r]))                  # byte within value r
    return offsets, src


@functools.partial(jax.jit, static_argnames=("count_pad", "heap_pad"))
def _plain_bytes_pages_jit(buf, lens_base, page_byte_base, page_val_start,
                           *, count_pad, heap_pad):
    """PLAIN BYTE_ARRAY decode on device: lengths → offsets → heap compaction.

    The host walks ONLY the u32 length prefixes (native
    tpq_bytearray_lengths — O(values), no copies) and stages the RAW value
    streams plus the lengths; this kernel does everything that touches the
    value bytes (SURVEY §7.4.2's "sequential" length walk is sequential only
    in *finding* the lengths — once they are known, offsets are one cumsum
    and the heap compaction is data-parallel; see _bytes_heap_src).

    ``lens_base`` points at the staged uint32 lengths (zero-filled past the
    real count, so pad values are empty).  ``page_val_start`` int32[P+1]
    cumulative value counts; ``page_byte_base`` int64[P] staged byte base of
    each page's raw stream.  Returns (offsets int64[count_pad+1],
    heap uint8[heap_pad]) — callers slice by the real counts.
    """
    offsets, src = _bytes_heap_src(
        buf, lens_base, page_byte_base, page_val_start,
        count_pad=count_pad, heap_pad=heap_pad,
    )
    heap = buf[jnp.clip(src, 0, buf.shape[0] - 1)]
    return offsets, heap


@functools.partial(
    jax.jit,
    static_argnames=("count_pad", "heap_pad", "n_ops", "out_pad", "iters",
                     "n_pages"),
)
def _snappy_bytes_staged_jit(buf, lens_base, tbase, *, count_pad, heap_pad,
                             n_ops, out_pad, iters, n_pages):
    """BYTE_ARRAY heap compaction with the value streams shipped COMPRESSED
    (ship.py ROUTE_DEVICE_SNAPPY / ROUTE_RECOMPRESS — byte-array heaps are
    the lineitem16 byte mover the round-5 VERDICT named).  Identical to
    _plain_bytes_pages_jit except each heap byte's page-stream position is
    an OUTPUT-SPACE coordinate resolved through the snappy source map — one
    extra gather composes the two routes.

    Layout at ``tbase``: op tables (_SNAPPY_OPS_BYTES * n_ops) |
    page_out_base i64[P] | page_val_start i32[P+1].
    """
    S = _resolve_snappy_staged(buf, tbase, n_ops=n_ops, out_pad=out_pad,
                               iters=iters)
    o = _SNAPPY_OPS_BYTES * n_ops
    page_out = _tslice(buf, tbase, o, n_pages, jnp.int64); o += 8 * n_pages
    pvs = _tslice(buf, tbase, o, n_pages + 1, jnp.int32)
    offsets, src = _bytes_heap_src(
        buf, lens_base, page_out, pvs, count_pad=count_pad, heap_pad=heap_pad,
    )
    src32 = jnp.clip(src, 0, out_pad - 1).astype(jnp.int32)
    heap = buf[jnp.clip(S[src32], 0, buf.shape[0] - 1)]
    return offsets, heap


def _fused_words_cast(words, dtype: str):
    """Finished little-endian u32 words from a fused megakernel -> the
    value array (same dtype conventions as plain_decode_fixed: DOUBLE
    stays u32 word pairs — TPU f64 emulation rounds real data).  Runs in
    the plan fn's ambient x64 trace; the kernels themselves are x64-free."""
    if dtype == "float64":
        return words
    if dtype == "int64":
        return jax.lax.bitcast_convert_type(words, jnp.int64)
    return jax.lax.bitcast_convert_type(
        words.reshape(-1), jnp.int32 if dtype == "int32" else jnp.float32)


def _narrow_widen(raw, bias, *, k, dtype, count):
    """Widen ``k``-byte little-endian rows and re-bias: ``v = min +
    zero_extend(bytes)`` (the shared back half of both narrow routes).  All
    arithmetic is modular, so the reconstruction is exact for any int range
    whose *span* fits ``k`` bytes, including negative minima."""
    lo = jnp.zeros((count,), jnp.uint32)
    for i in range(min(k, 4)):
        lo = lo | (raw[:, i].astype(jnp.uint32) << (8 * i))
    if dtype == "int32":
        return jax.lax.bitcast_convert_type(
            bias.astype(jnp.uint32) + lo, jnp.int32
        )
    hi = jnp.zeros((count,), jnp.uint32)
    for i in range(4, k):
        hi = hi | (raw[:, i].astype(jnp.uint32) << (8 * (i - 4)))
    u = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << 32)
    return jax.lax.bitcast_convert_type(bias.astype(jnp.uint64) + u, jnp.int64)


@functools.partial(jax.jit, static_argnames=("k", "dtype", "count"))
def _plain_narrow_jit(buf, base, bias, *, k, dtype, count):
    """Reconstruct a narrow-transcoded PLAIN INT column.

    The host shipped ``(v - min)`` truncated to ``k`` little-endian bytes per
    value (see _ChunkAssembler._plan_narrow_ints); this widens and re-biases
    (_narrow_widen).  ``bias`` is traced (per-chunk data); only
    (k, dtype, count) key the executable.
    """
    raw = jax.lax.dynamic_slice(buf, (base,), (count * k,)).reshape(count, k)
    return _narrow_widen(raw, bias, k=k, dtype=dtype, count=count)


# packed op-table bytes per op slot: ends/asrc/offs int32 + islit uint8.
# Route tables packed behind the ops start at tbase + _SNAPPY_OPS_BYTES*n_ops.
_SNAPPY_OPS_BYTES = 13


def _resolve_snappy_staged(buf, tbase, *, n_ops, out_pad, iters):
    """Slice the packed op tables at ``tbase`` back out of the staged buffer
    and resolve the output-space source map (jax_kernels.snappy_resolve —
    the shared device half of every compressed-shipping route).  Trace-time
    helper; statics (n_ops, out_pad, iters) ride the consuming jit's key."""
    o = 0
    ends = _tslice(buf, tbase, o, n_ops, jnp.int32); o += 4 * n_ops
    asrc = _tslice(buf, tbase, o, n_ops, jnp.int32); o += 4 * n_ops
    offs = _tslice(buf, tbase, o, n_ops, jnp.int32); o += 4 * n_ops
    islit = _tslice(buf, tbase, o, n_ops, jnp.uint8)
    return K.snappy_resolve(ends, asrc, offs, islit, out_pad=out_pad,
                            iters=iters)


@functools.partial(
    jax.jit,
    static_argnames=("n_ops", "out_pad", "iters", "dtype", "count", "n_pages"),
)
def _snappy_plain_staged_jit(buf, tbase, *, n_ops, out_pad, iters, dtype,
                             count, n_pages):
    """Decompress snappy PLAIN pages ON DEVICE and decode their values.

    The host shipped the COMPRESSED page payloads plus tag-walk op tables
    (native tpq_snappy_plan; see _plan_device_snappy).  Byte movement — the
    actual decompression — happens in ``snappy_resolve`` as gathers; this
    kernel then gathers each value's bytes through the source map and
    bitcasts (plain_decode_fixed).

    Output positions past the real total resolve through padded literal ops
    (src 0) and are never selected by the value gather.  All math is int32 —
    the planner falls back to host decompression beyond 2 GiB arenas.
    """
    S = _resolve_snappy_staged(buf, tbase, n_ops=n_ops, out_pad=out_pad,
                               iters=iters)
    o = _SNAPPY_OPS_BYTES * n_ops
    vbase = _tslice(buf, tbase, o, n_pages, jnp.int32); o += 4 * n_pages
    vstart = _tslice(buf, tbase, o, n_pages + 1, jnp.int32)
    width = 8 if dtype in ("int64", "float64") else 4
    i = jnp.arange(count, dtype=jnp.int32)
    p = jnp.clip(
        jnp.searchsorted(vstart, i, side="right").astype(jnp.int32) - 1,
        0, n_pages - 1,
    )
    vpos = vbase[p] + (i - vstart[p]) * width
    byte_idx = (vpos[:, None]
                + jnp.arange(width, dtype=jnp.int32)[None, :]).reshape(-1)
    src = S[jnp.clip(byte_idx, 0, out_pad - 1)]
    bts = buf[jnp.clip(src, 0, buf.shape[0] - 1)]
    return K.plain_decode_fixed(bts, dtype, count)


@functools.partial(
    jax.jit, static_argnames=("n_ops", "out_pad", "iters", "k", "dtype",
                              "count"),
)
def _snappy_narrow_staged_jit(buf, tbase, bias, *, n_ops, out_pad, iters, k,
                              dtype, count):
    """The narrow+snappy composition: the host shipped SNAPPY over the
    ``k``-byte narrow transcode (ship.py ROUTE_NARROW_SNAPPY), so the two
    transfer cuts multiply — narrow residuals are low-entropy and compress
    far below their already-truncated width.  Resolve the stream's output
    space, gather the rows, widen and re-bias (_narrow_widen).  Rows past
    the real count resolve through padded ops — callers slice by
    ``n_values``."""
    S = _resolve_snappy_staged(buf, tbase, n_ops=n_ops, out_pad=out_pad,
                               iters=iters)
    idx = jnp.arange(count * k, dtype=jnp.int32)
    src = S[jnp.clip(idx, 0, out_pad - 1)]
    raw = buf[jnp.clip(src, 0, buf.shape[0] - 1)].reshape(count, k)
    return _narrow_widen(raw, bias, k=k, dtype=dtype, count=count)


@functools.partial(
    jax.jit, static_argnames=("n_ops", "out_pad", "iters", "nbytes"),
)
def _snappy_gather_staged_jit(buf, tbase, *, n_ops, out_pad, iters, nbytes):
    """Materialize the first ``nbytes`` of a snappy stream's output space
    (dictionary value tables, ragged dictionary heaps).  Positions past the
    real output resolve through padded literal ops to staged byte 0 —
    consumers never index them (every valid dictionary index is <
    dict_len; the deferred-check path raises at finalize before clamped
    garbage can escape)."""
    S = _resolve_snappy_staged(buf, tbase, n_ops=n_ops, out_pad=out_pad,
                               iters=iters)
    idx = jnp.arange(nbytes, dtype=jnp.int32)
    src = S[jnp.clip(idx, 0, out_pad - 1)]
    return buf[jnp.clip(src, 0, buf.shape[0] - 1)]


# pointer-doubling round buckets (static arg: executable sharing); 24 covers
# chains of 2^24 ops — more ops than a 16 MiB page can encode
_SNAPPY_ITER_BUCKETS = (2, 4, 8, 16, 24)
# op-table cap: a stream shattered into more ops than this ships decompressed
# (the table would rival the payload)
_SNAPPY_MAX_OPS = 1 << 20
# ratio~1 chunks larger than this take the host-decompress path: the device
# resolve (searchsorted + doubling gathers over the output space) costs more
# than host snappy at ~1.4 GB/s once the chunk spans multiple strips
_SNAPPY_SMALL_OUT = 8 << 20


# transcode only when it saves >= 3 bytes/value: below that the extra host
# pass (min/max + truncating copy) buys too little transfer
_NARROW_SAVE_BYTES = 3
# probe the first page's head before scanning the whole chunk: full-range
# data (8-byte spans) must not pay a full min/max pass just to bail
_NARROW_PROBE = 65536


def _check_plain_sizes(pages, width: int) -> None:
    """Reject PLAIN pages whose value stream is shorter than defined*width
    (shared by every fixed-width staging/transcode/expansion planner)."""
    for p in pages:
        nbytes = (p.comp[2] if p.comp is not None
                  else len(p.raw) - p.value_pos)
        if nbytes < p.defined * width:
            raise ParquetError(
                f"PLAIN data truncated: {nbytes} < {p.defined * width}"
            )


def _span_bytes(lo: int, hi: int) -> int:
    """Bytes needed for the unsigned span hi - lo (>= 1)."""
    return max((int(hi) - int(lo)).bit_length() + 7, 8) // 8


def _narrow_max_k(width: int) -> int:
    """Largest transcoded byte width still worth the host pass.

    Shared by the narrow planner AND _plan_device_snappy's stats-hint
    routing: the two must agree bit for bit, or a chunk each side expects
    the other to claim would silently pay host decompression and full-width
    staging.
    """
    return width - (_NARROW_SAVE_BYTES if width == 8 else 2)


@functools.partial(jax.jit, static_argnames=("count",))
def _bool_pages_jit(buf, page_byte_base, page_val_start, *, count):
    """PLAIN booleans across pages: bit position restarts at each page base."""
    i = jnp.arange(count, dtype=jnp.int64)
    p = jnp.searchsorted(page_val_start, i, side="right") - 1
    p = jnp.clip(p, 0, page_val_start.shape[0] - 1)
    bit_pos = page_byte_base[p] * 8 + (i - page_val_start[p])
    return K.extract_bits(buf, bit_pos, 1, 1).astype(jnp.bool_)


@functools.partial(jax.jit, static_argnames=("size",))
def _fit_rows_jit(x, *, size):
    """Zero-pad leading-axis rows up to ``size`` (batch-carry capacity)."""
    pad = size - x.shape[0]
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], dtype=x.dtype)]
    )


@jax.jit
def _roll_rows_jit(x, shift):
    """Roll rows by a traced shift (batch-carry compaction)."""
    return jnp.roll(x, shift, axis=0)


@jax.jit
def _update_rows_jit(x, update, pos):
    """Write ``update`` rows at a traced offset (batch-carry append)."""
    return jax.lax.dynamic_update_slice(
        x, update, (pos,) + (0,) * (x.ndim - 1)
    )


@functools.partial(jax.jit, static_argnames=("size",))
def _dynslice_jit(buf, start, *, size):
    """Slice ``size`` leading rows at a traced offset (static size, bucketed
    by the caller so executables are shared across chunks/batches)."""
    return jax.lax.dynamic_slice(
        buf, (start,) + (0,) * (buf.ndim - 1), (size,) + buf.shape[1:]
    )


def _on_caller_device(fn):
    """``fn`` bound to the calling thread's default device.  jax's default
    device is thread-local, so a staging worker would otherwise put every
    buffer on device 0 whatever ``jax.default_device`` the scan runs
    under (each shard of a multi-chip scan decodes on its own chip)."""
    dev = jax.config.jax_default_device
    if dev is None:
        return fn

    def run(*a, **k):
        with jax.default_device(dev):
            return fn(*a, **k)

    return run


class _RowGroupStager:
    """One staged host→device transfer for a whole row group.

    Every transfer pays a fixed round trip (~50-100 ms on the remote backend
    the early rounds measured), so per-chunk staging (~8 MB each) runs at a
    fraction of link bandwidth.  Every chunk registers its host byte regions
    here (value streams, level arrays, byte-array heaps); ``stage()`` ships
    ONE buffer and each chunk's kernels address into it by base offset —
    the transfer granularity and the executable granularity are decoupled.

    With an ``executor`` (the reader's staging worker), registration also
    *streams*: every time the arena grows past a 16 MiB strip boundary the
    completed strip is copied and device_put on the worker while the main
    thread is still decompressing the row group's remaining chunks, and
    ``stage()`` concatenates the strips on device.  That overlaps host parse
    with transfer *within* a row group — the single-large-row-group case
    (one 128 MB group per file) that the cross-row-group pipeline cannot
    overlap at all.  Row groups smaller than one strip take the original
    single-buffer path byte for byte, so small-file executable shapes (and
    the warm compile cache) are untouched.
    """

    STRIP = 16 << 20

    def __init__(self, executor=None):
        # ("arr", u8, base, nbytes) | ("segs", segments, base, nbytes)
        self._parts: list[tuple] = []
        self.total = 0
        self._max_read_end = 0
        self._ex = executor
        self._strip_futs: list = []
        self._flushed = 0  # arena bytes handed to strip jobs (STRIP multiple)

    def _reserve(self, nbytes: int, reserve: int | None) -> int:
        base = self.total
        room = max(reserve or 0, nbytes)
        # keep every region 64-byte aligned for clean device layouts
        self.total = base + room + (-(base + room)) % 64
        return base

    def add(self, arr: np.ndarray, reserve: int | None = None) -> int:
        """Register a host array; returns its byte offset in the staged buffer.

        ``reserve`` rounds the region up (tail zero-filled) so callers can
        device-slice a bucketed size without reading past the arena.
        """
        u8 = arr.reshape(-1).view(np.uint8) if arr.dtype != np.uint8 else arr.reshape(-1)
        base = self._reserve(u8.nbytes, reserve)
        self._parts.append(("arr", u8, base, u8.nbytes))
        self._flush_ready()
        return base

    def _copy_range(self, buf: np.ndarray, lo: int, hi: int) -> None:
        """Copy every registered byte in [lo, hi) into ``buf``, zeroing only
        the GAPS (alignment padding + zero-filled reserves) — a full 16 MiB
        memset per strip re-wrote the whole scan's staged volume once over
        (~1 s of a 100M-row rep).  Parts are appended in ascending base
        order and never mutated, so a worker thread may scan the list while
        the main thread appends."""
        pos = lo
        for kind, payload, base, nbytes in self._parts:
            if base >= hi:
                break
            if base + nbytes <= lo:
                continue
            s = max(lo, base)
            if s > pos:
                buf[pos - lo : s - lo] = 0  # reserve tail / alignment gap
            if kind == "arr":
                e = min(hi, base + nbytes)
                buf[s - lo : e - lo] = payload[s - base : e - base]
                pos = e
            else:
                off = base
                for raw, start, size in payload:
                    if off >= hi:
                        break
                    if off + size > lo:
                        s = max(lo, off)
                        e = min(hi, off + size)
                        buf[s - lo : e - lo] = np.frombuffer(
                            raw, np.uint8, e - s, start + (s - off)
                        )
                        pos = e
                    off += size
        if pos < hi:
            buf[pos - lo :] = 0

    def _flush_ready(self) -> None:
        """Hand every newly completed strip to the worker (copy + device_put
        run there, overlapping the main thread's decompress/parse)."""
        if self._ex is None:
            return
        while self.total - self._flushed >= self.STRIP:
            lo = self._flushed
            self._flushed += self.STRIP

            def job(lo=lo, hi=self._flushed):
                buf = np.empty(self.STRIP, dtype=np.uint8)
                self._copy_range(buf, lo, hi)
                return jnp.asarray(buf)

            self._strip_futs.append(self._ex.submit(_on_caller_device(job)))

    def add_segments(self, segments: list[tuple[bytes, int, int]]) -> np.ndarray:
        """Register byte slices (buf, offset, size) laid back to back.

        The slices are copied straight from their source buffers (decompressed
        page bytes) into the staged buffer during ``stage()`` — no per-chunk
        intermediate assembly copy.  Returns each slice's absolute byte base.
        """
        bases = np.empty(len(segments), dtype=np.int64)
        nbytes = 0
        for i, (_, _, size) in enumerate(segments):
            bases[i] = nbytes
            nbytes += size
        base = self._reserve(nbytes, None)
        self._parts.append(("segs", segments, base, nbytes))
        self._flush_ready()
        return bases + base

    def note_read_extent(self, base: int, nbytes: int) -> None:
        """Declare that a kernel will read ``nbytes`` from ``base`` — possibly
        past the registered region (bucketed static-size reads overlap the
        next chunk's bytes harmlessly; only the END of the staged buffer must
        cover the overhang).  ``stage()`` sizes the buffer to the maximum
        declared extent, so dynamic_slice reads never clamp/misalign."""
        self._max_read_end = max(self._max_read_end, base + nbytes)

    def stage(self) -> jax.Array:
        need = max(self.total, self._max_read_end)
        if not self._strip_futs:
            # single-transfer path (row group under one strip, or no worker)
            buf = np.empty(_bucket_bytes(need + _SLACK, 64), dtype=np.uint8)
            pos = 0
            for kind, payload, base, nbytes in self._parts:
                if base > pos:
                    buf[pos:base] = 0
                if kind == "arr":
                    buf[base : base + nbytes] = payload
                else:
                    off = base
                    for raw, start, size in payload:
                        buf[off : off + size] = np.frombuffer(raw, np.uint8,
                                                              size, start)
                        off += size
                pos = base + nbytes
            buf[pos:] = 0
            return jnp.asarray(buf)
        # streaming path: strips are already in flight; copy+ship the tail,
        # then assemble on device (HBM-bandwidth concat, one executable per
        # (strip count, tail bucket) shape set)
        tail_len = _bucket_bytes(need + _SLACK - self._flushed, 64)
        tail = np.empty(tail_len, dtype=np.uint8)
        self._copy_range(tail, self._flushed, self._flushed + tail_len)
        parts = [f.result() for f in self._strip_futs] + [jnp.asarray(tail)]
        self._strip_futs.clear()  # release strip buffers once concat owns them
        return _concat_jit(parts)


_CACHE_ENABLED = False
# the checkout this package runs from: a TPU run with no cache directory
# configured caches at <checkout>/.jax_cache/ (listed in .gitignore)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache on first reader use.

    A cold lineitem scan on a v5e is mostly compilation (PR 21), and a
    fresh process re-opening the same file pays it all again without the
    cache.  The directory is ``JAX_COMPILATION_CACHE_DIR`` when set (jax
    reads it itself; this code then sets no other), else a fixed
    ``<checkout>/.jax_cache/`` on a TPU backend — the path is part of what
    makes a later process hit.  On the CPU nothing is cached unless the
    environment asks: CPU AOT entries carry the host's machine features,
    and a checkout copied to another machine must not load them.  The size
    and time thresholds apply to whichever directory is used.
    """
    global _CACHE_ENABLED
    if _CACHE_ENABLED:
        return
    _CACHE_ENABLED = True
    if not jax.config.jax_compilation_cache_dir:
        if jax.default_backend() != "tpu":
            return
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _pallas_interpret_mode():
    """Whether hybrid decode routes through the Pallas unpack kernel.

    Returns None (off — use the XLA extract path), False (native Mosaic, the
    TPU default), or True (Pallas interpreter — CPU test parity).  Default-on
    for TPU backends per the round-3 directive: the plane kernel is the
    fastest unpack primitive in the repo and BP staging also drops the RLE
    bytes from the transfer.  ``TPQ_PALLAS=0`` forces the XLA path
    everywhere; ``TPQ_PALLAS=1`` forces the interpreter on non-TPU backends
    (tests A/B the two paths with it).
    """
    env = os.environ.get("TPQ_PALLAS", "").strip()
    if env == "0":
        return None
    from .pallas_kernels import pallas_available

    if pallas_available():
        return False
    return True if env == "1" else None


# BP payloads are staged as one host-side segment copy per bit-packed run;
# streams shattered into very many tiny runs (adversarial or ultra-alternating
# data) would make that copy loop the bottleneck, so they keep the XLA
# extract path whose staging is one segment per page.
_PALLAS_MAX_SEGS = 4096


def _pack_tables(stager: _RowGroupStager, arrays) -> int:
    """Pack np arrays into ONE staged region; returns its byte base.

    Every per-chunk metadata table shipped as its own ``jnp.asarray`` costs a
    full transfer round trip (~2.5 ms measured on the early remote backend)
    — at 800 chunks × 4 tables that is the dominant wall-clock at
    multi-GB scale, dwarfing the decode.
    Packing the tables into the row-group buffer makes them part of the ONE
    staged transfer; consuming jits slice them back out at static offsets
    (shapes are bucketed, so offsets are static relative to a traced base).
    Arrays are staged back to back in call order; callers compute the same
    static layout at trace time.
    """
    cat = np.concatenate([np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                          for a in arrays])
    return stager.add(cat)


def _tslice(buf, base, off: int, n: int, dtype):
    """Slice a packed table back out of the staged buffer (trace-time
    helper; ``off``/``n`` static, ``base`` traced)."""
    nbytes = np.dtype(dtype).itemsize
    raw = jax.lax.dynamic_slice(buf, (base + off,), (n * nbytes,))
    if nbytes == 1:
        return raw
    return jax.lax.bitcast_convert_type(
        raw.reshape(n, nbytes), dtype
    ).reshape(n)


@functools.partial(jax.jit, static_argnames=("count", "rp"))
def _hybrid_combine_staged_jit(vals, buf, tbase, n_valid, *, count, rp):
    """Combine Pallas-unpacked BP values with RLE runs into stream order.

    ``vals`` uint32[8 * groups_pad] — BP groups unpacked from the contiguous
    staged payload, value-major (pallas_kernels.bp_value_index).  Every
    output position finds its run with one searchsorted (same structure
    as expand_rle_hybrid), then either
    broadcasts the RLE value or picks its BP element at
    ``bp_idx_base[run] + pos`` — one u32 gather instead of per-value
    multi-byte extraction.  Run tables ride the staged buffer at ``tbase``
    (layout [ends i32 | is_rle u8 | values u32 | bp_idx_base i32] × rp —
    see _pack_tables); all index math is int32, so the trace is
    x64-agnostic."""
    ends = _tslice(buf, tbase, 0, rp, np.int32)
    isr = _tslice(buf, tbase, rp * 4, rp, np.uint8) != 0
    rvals = _tslice(buf, tbase, rp * 5, rp, np.uint32)
    bib = _tslice(buf, tbase, rp * 9, rp, np.int32)
    pos = jnp.arange(count, dtype=jnp.int32)
    r = jnp.searchsorted(ends, pos, side="right").astype(jnp.int32)
    r = jnp.minimum(r, rp - 1)
    bp_idx = jnp.clip(bib[r] + pos, 0, vals.shape[0] - 1)
    out = jnp.where(isr[r], rvals[r],
                    vals[bp_value_index(bp_idx, vals.shape[0] // 8)])
    return jnp.where(pos < n_valid, out, jnp.zeros((), dtype=out.dtype))


def _plan_hybrid_pallas(stager: _RowGroupStager, pages_info, width: int,
                        total: int, count_pad: int, interpret: bool):
    """Plan a hybrid expansion through the Pallas BP-group kernel.

    ``pages_info``: [(HybridMeta, source_buffer, page_value_count)] in stream
    order.  Registers each bit-packed run's payload with the stager so the
    staged buffer holds ALL BP groups contiguously (RLE headers/values never
    ship — they live in the run table), then returns
    ``fn(buf_dev) -> uint32[count_pad]``.  Returns None when the stream has
    no Pallas-eligible shape (width 0, no BP groups, or a pathological run
    count) — callers fall back to the XLA extract path.
    """
    if width <= 0 or width > 32 or total > np.iinfo(np.int32).max:
        # i32 combine math covers byte bases AND value positions; >=2^31
        # value chunks keep the XLA path (int64 throughout)
        return None
    # one vectorized pass over the concatenated run tables (a per-page
    # Python loop here was ~30% of the nested config's host phase)
    ks = np.array([m.n_runs for m, _, _ in pages_info], dtype=np.int64)
    nr = int(ks.sum())
    if nr == 0:
        return None
    ends_c = np.concatenate([m.run_ends[: m.n_runs] for m, _, _ in pages_info])
    isr = np.concatenate([m.run_is_rle[: m.n_runs] for m, _, _ in pages_info])
    rvals = np.concatenate([m.run_values[: m.n_runs] for m, _, _ in pages_info])
    bst = np.concatenate(
        [m.run_bit_starts[: m.n_runs] for m, _, _ in pages_info]
    )
    run_page_start = np.repeat(np.cumsum(ks) - ks, ks)  # first run idx of page
    page_of = np.repeat(np.arange(len(ks)), ks)
    pcounts = np.array([c for _, _, c in pages_info], dtype=np.int64)
    prefix = np.concatenate([[0], np.cumsum(pcounts)[:-1]])
    # within-page run start = previous run's end (0 for a page's first run)
    rstart = np.empty(nr, np.int64)
    rstart[0] = 0
    rstart[1:] = ends_c[:-1]
    first = np.arange(nr) == run_page_start
    rstart[first] = 0
    # payload byte position in src coords: run_bit_starts stores
    # pos*8 - run_start*width (see parse_hybrid_meta)
    pay = (bst + rstart * width) >> 3
    groups = np.where(isr, 0, -(-(ends_c - rstart) // 8))
    sel = np.flatnonzero(groups > 0)
    if len(sel) > _PALLAS_MAX_SEGS or not len(sel):
        return None
    cumg = int(groups.sum())
    gbase = np.cumsum(groups) - groups  # exclusive prefix (global group base)
    ends = (ends_c + prefix[page_of]).astype(np.int32)
    bib = np.where(isr, 0,
                   gbase * 8 - (rstart + prefix[page_of])).astype(np.int32)
    srcs = [s for _, s, _ in pages_info]
    segs = [(srcs[p], int(b), int(g) * width)
            for p, b, g in zip(page_of[sel], pay[sel], groups[sel])]
    from .pallas_kernels import bp_groups_pad, unpack_bp_groups

    rp = _bucket(max(nr, 1))
    if rp > nr:
        pad = rp - nr
        ends = np.concatenate([ends, np.full(pad, total, np.int32)])
        isr = np.concatenate([isr, np.zeros(pad, bool)])
        rvals = np.concatenate([rvals, np.zeros(pad, np.uint32)])
        bib = np.concatenate([bib, np.zeros(pad, np.int32)])
    gpad = bp_groups_pad(cumg)
    if stager.total + gpad * width > np.iinfo(np.int32).max:
        # the kernel's x64-free trace addresses the staged buffer with i32;
        # a >=2 GiB stager region can't — the XLA extract path handles it
        # (checked before ANY stager mutation so fallback leaves no dead bytes)
        return None
    tbase = _pack_tables(stager, [ends, isr.astype(np.uint8), rvals, bib])
    bases = stager.add_segments(segs)
    bp_base = int(bases[0])
    # the unpack reads gpad*width bytes from bp_base: past the real payload
    # it sees later regions' bytes — garbage values the combine never
    # selects (positions past `total` are masked, real positions always map
    # into real groups)
    stager.note_read_extent(bp_base, gpad * width)

    def fn(buf_dev, bp_base_d, tbase_d, total_d):
        vals = unpack_bp_groups(buf_dev, bp_base_d, width, gpad,
                                interpret=interpret)
        return _hybrid_combine_staged_jit(
            vals, buf_dev, tbase_d, total_d, count=count_pad, rp=rp,
        )

    return _Plan(
        ("lvlp", width, gpad, rp, count_pad, bool(interpret)), fn,
        (np.int32(bp_base), np.int64(tbase), np.int32(total)), None,
        stages=2,  # pallas unpack pass + run-table combine pass
    )


def _merge_run_tables(ends_l, rle_l, vals_l, starts_l, fill_end,
                      widths_l=None):
    """Pad per-page hybrid run lists into one bucketed chunk-global table.

    Padding slots get ``run_ends = fill_end`` (so searchsorted clamps past
    the real runs) and zeros elsewhere.  Returns (ends, is_rle, values,
    starts[, widths]) — the argument set of expand_rle_hybrid(_vw).
    """
    rp = _bucket(max(sum(len(e) for e in ends_l), 1))
    ends = np.full(rp, fill_end, dtype=np.int64)
    is_rle = np.zeros(rp, dtype=bool)
    rvals = np.zeros(rp, dtype=np.uint32)
    starts = np.zeros(rp, dtype=np.int64)
    rwidths = np.zeros(rp, dtype=np.uint32) if widths_l is not None else None
    k = 0
    for i, e in enumerate(ends_l):
        ends[k : k + len(e)] = e
        is_rle[k : k + len(e)] = rle_l[i]
        rvals[k : k + len(e)] = vals_l[i]
        starts[k : k + len(e)] = starts_l[i]
        if rwidths is not None:
            rwidths[k : k + len(e)] = widths_l[i]
        k += len(e)
    if rwidths is not None:
        return ends, is_rle, rvals, starts, rwidths
    return ends, is_rle, rvals, starts


class _SnappyShipInfo:
    """Statics + staged table base of one planned compressed shipment."""

    __slots__ = ("tbase", "n_ops", "out_pad", "iters", "shipped", "total_out")

    def __init__(self, tbase, n_ops, out_pad, iters, shipped, total_out):
        self.tbase = tbase
        self.n_ops = n_ops
        self.out_pad = out_pad
        self.iters = iters
        self.shipped = shipped
        self.total_out = total_out


def _plan_snappy_ops(stager: _RowGroupStager, specs, extra_tables=()):
    """Register snappy/raw payloads and pack the op tables the device
    resolver (jax_kernels.snappy_resolve) consumes — the shared host half
    of every compressed-shipping route (ship.py).

    ``specs``: per stream, ``('comp', payload, out_len[, plan])`` — a
    raw-snappy payload whose uncompressed length is ``out_len`` (``plan``
    optionally carries a pre-run ``native.snappy_plan`` result) — or
    ``('raw', buf, pos, out_len)`` — host bytes shipped as one synthetic
    literal op.  Output spaces concatenate in spec order; callers compute
    out-space bases as the exclusive cumsum of out_lens.  ``extra_tables``
    pack behind the op tables at the same ``tbase`` (consuming jits slice
    them at ``_SNAPPY_OPS_BYTES * n_ops_pad``).

    Returns ``_SnappyShipInfo`` or None when infeasible (native library
    absent, stream rejected by the tag walk, op-table cap, i32 arena
    ceiling).  Infeasibility leaves the stager UNTOUCHED, so callers fall
    through to another route with no dead staged bytes.
    """
    from . import native

    if not native.available():
        return None
    plans = []
    n_ops_total = 0
    total_out = 0
    for spec in specs:
        if spec[0] == "comp":
            payload, out_len = spec[1], spec[2]
            r = spec[3] if len(spec) > 3 and spec[3] is not None else (
                native.snappy_plan(payload, out_len))
            if r is None or isinstance(r, int):
                return None
            plans.append((spec, r, out_len))
            n_ops_total += len(r[0])
        else:
            out_len = spec[3]
            plans.append((spec, None, out_len))
            n_ops_total += 1
        total_out += out_len
    if n_ops_total == 0 or n_ops_total > _SNAPPY_MAX_OPS:
        return None
    out_pad = _bucket_bytes(total_out + 8, 8)
    segs = [
        (spec[1], 0, len(spec[1])) if r is not None
        else (spec[1], spec[2], out_len)
        for spec, r, out_len in plans
    ]
    shipped = sum(s[2] for s in segs)
    n_ops_pad = _bucket(n_ops_total)
    extra_bytes = sum(np.ascontiguousarray(t).nbytes for t in extra_tables)
    if (stager.total + shipped + _SNAPPY_OPS_BYTES * n_ops_pad + extra_bytes
            + out_pad > (np.iinfo(np.int32).max >> 1)):
        return None  # i32 source/table math would overflow
    bases = stager.add_segments(segs)
    ends = np.empty(n_ops_total, np.int64)
    asrc = np.empty(n_ops_total, np.int64)
    offs = np.zeros(n_ops_total, np.int32)
    islit = np.empty(n_ops_total, np.uint8)
    at = 0
    out_base = 0
    max_depth = 0
    for (spec, r, out_len), base in zip(plans, bases):
        if r is None:
            ends[at] = out_base + out_len
            asrc[at] = base
            islit[at] = 1
            at += 1
        else:
            dst_end, op_src, is_lit_p, depth = r
            n = len(dst_end)
            if n:
                ends[at : at + n] = dst_end + out_base
                # literal: absolute staged position of the run's payload;
                # copy: chunk-out source base  dst_start - offset
                starts = np.empty(n, np.int64)
                starts[0] = 0
                starts[1:] = dst_end[:-1]
                asrc[at : at + n] = np.where(
                    is_lit_p != 0, op_src + base,
                    out_base + starts - op_src,
                )
                offs[at : at + n] = np.where(is_lit_p != 0, 1, op_src)
                islit[at : at + n] = is_lit_p
                at += n
                max_depth = max(max_depth, depth)
        out_base += out_len
    # `at` always lands on n_ops_total: raw specs write one op each and
    # comp specs exactly len(plan) (counted above)
    assert at == n_ops_total, (at, n_ops_total)
    iters = next(
        (b for b in _SNAPPY_ITER_BUCKETS
         if (1 << b) >= max_depth + 1), _SNAPPY_ITER_BUCKETS[-1]
    ) if max_depth > 0 else 0
    ends_t = np.full(n_ops_pad, out_pad, np.int32)
    ends_t[:n_ops_total] = ends
    asrc_t = np.zeros(n_ops_pad, np.int32)
    asrc_t[:n_ops_total] = asrc
    offs_t = np.ones(n_ops_pad, np.int32)
    offs_t[:n_ops_total] = offs
    islit_t = np.ones(n_ops_pad, np.uint8)
    islit_t[:n_ops_total] = islit
    tbase = _pack_tables(
        stager, [ends_t, asrc_t, offs_t, islit_t, *extra_tables]
    )
    return _SnappyShipInfo(tbase, n_ops_pad, out_pad, iters, shipped,
                           total_out)


def _fixed_value_tables(sizes, counts):
    """Bucket-padded (vbase, vstart) page tables for the fixed-width snappy
    routes: per-page OUT-SPACE byte bases (exclusive cumsum of ``sizes``)
    and cumulative defined ``counts``.  Layout twin of what
    _snappy_plain_staged_jit slices back out — one builder so its call
    sites (_plan_device_snappy, _plan_recompress_fixed) can never
    desynchronize.  Returns (vbase_t, vstart_t, pages_pad, defined)."""
    out_bases = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    vstart = np.concatenate([[0], np.cumsum(counts)])
    pages_pad = _bucket(len(sizes))
    vbase_t = np.zeros(pages_pad, np.int32)
    vbase_t[: len(sizes)] = out_bases
    vstart_t = np.full(pages_pad + 1, vstart[-1], np.int32)
    vstart_t[: len(sizes) + 1] = vstart
    return vbase_t, vstart_t, pages_pad, int(vstart[-1])


class _Plan:
    """A planned device computation: ``fn(buf_dev, *dyn) -> pytree``.

    The fused row-group dispatch (``_run_plans``) traces every chunk's plan
    into ONE jitted call per row group, so all per-chunk dynamic arguments
    ride a single batched transfer and the backend pays ONE
    dispatch per row group instead of one per chunk (the per-call
    scalar-argument `device_put`s were 4.9 s of a 27 s warm 100M-row rep).

    Contract — the correctness of the executable cache rests on it:

    - ``key`` must capture EVERY static the traced body closes over.  The
      fused executable for a row group is cached by the tuple of plan keys;
      a later row group with an equal key tuple reuses the FIRST row
      group's traced closures, so any per-row-group value not in ``dyn``
      and not in ``key`` silently decodes with stale state.
    - ``dyn`` carries all per-row-group values (numpy scalars/arrays).
      Shape changes are safe (jit respecializes); value changes through
      closures are not.
    - ``build(res)`` runs host-side with the jit outputs and the CURRENT
      row group's metadata (it is never cached).
    - ``fn=None`` marks a pass-through plan whose result was already
      materialized at prepare time (`_finish_host`); ``build(None)``
      returns it.
    - ``stages`` is the STRUCTURAL count of separate device passes the
      traced graph contains — XLA fusions with an HBM-materialized
      intermediate between them (slice → decode → validity is 3; the
      snappy chains add their pointer-doubling rounds; a fused Pallas
      megakernel is exactly 1).  It rides the completion timer into the
      registry ``device`` section as ``device_passes``: fused routes
      prove structurally (passes == dispatches) that the round trips are
      gone, where the unfused twins show ≥3 passes per dispatch.
    """

    __slots__ = ("key", "fn", "dyn", "build", "route", "bytes_in",
                 "bytes_staged", "stages")

    def __init__(self, key, fn, dyn, build, stages: "int | None" = None):
        self.key = key
        self.fn = fn
        self.dyn = tuple(dyn)
        self.build = build
        # device-timing attribution (set by _prepare_row_group from the
        # chunk's ship records): the dominant ship route plus the column's
        # logical/shipped byte totals — never part of the executable key
        self.route = None
        self.bytes_in = 0
        self.bytes_staged = 0
        self.stages = (stages if stages is not None
                       else (3 if fn is not None else 0))


_FUSED_CACHE: dict = {}
_FUSED_LOCK = threading.Lock()
# NOTE: whole-row-group fusion (one jit over every chunk's plan) was built
# and measured first: any per-row-group static flip (a narrow-transcode k,
# a snappy iter bucket) changes the FUSED signature and recompiles the
# entire 16-column graph — minutes per signature on the early remote backend.
# Per-plan executables keep the round-4 cache granularity; the per-call
# transfer cost is killed by _memo_dev instead.
_FUSE_RG = os.environ.get("TPQ_FUSE_RG", "") == "1"

_DEV_MEMO: dict = {}
_DEV_MEMO_LOCK = threading.Lock()
_DEV_MEMO_MAX_ARRAY = 4096  # bytes; tables above this ride the staged buffer


def _memo_scope() -> tuple:
    """The (platform, device id) a bare device_put commits to right now.

    Keys are scoped by it so a default-device change mid-process (or a
    multi-backend embedder) never hands a plan an array committed to the
    wrong device."""
    d = jax.config.jax_default_device
    if isinstance(d, str):  # the config also accepts a platform string
        d = jax.devices(d)[0]
    elif d is None:
        d = jax.devices()[0]
    return (d.platform, d.id)


def _memo_dev(x):
    """Device-resident memo for small dynamic plan arguments.

    The staged-buffer layout of a uniform file is identical across row
    groups, so per-chunk scalar args (byte bases, table offsets, value
    counts) repeat with the SAME VALUES every row group.  Shipping each
    distinct value once and handing jit an already-committed device array
    makes later row groups' dispatches transfer-free — the per-call scalar
    `device_put`s were 4.9 s of a 27 s warm 100M-row rep on the early
    remote backend).

    Thread-safe (dispatches may come from pipeline threads) and
    self-healing: entries whose buffers were deleted out from under the
    memo (jax.clear_caches, backend teardown) are dropped and re-put rather
    than handed to a plan as dead arrays.  A racing double put is benign —
    both arrays are valid, last one stays cached."""
    if isinstance(x, np.generic):
        key = ("s", x.dtype.str, x.item())
    elif isinstance(x, np.ndarray):
        if x.ndim == 0:
            key = ("s", x.dtype.str, x.item())
        elif x.nbytes <= _DEV_MEMO_MAX_ARRAY:
            key = ("a", x.dtype.str, x.shape, x.tobytes())
        else:
            return x
    else:
        return x
    key = _memo_scope() + key
    with _DEV_MEMO_LOCK:
        hit = _DEV_MEMO.get(key)
    if hit is not None:
        try:
            if not hit.is_deleted():
                return hit
        except Exception:  # noqa: BLE001 — treat unknowable as dead
            pass
    fresh = jax.device_put(x)
    with _DEV_MEMO_LOCK:
        if len(_DEV_MEMO) > 8192:
            _DEV_MEMO.clear()
        _DEV_MEMO[key] = fresh
    return fresh


def _single_for(key, fn):
    """Per-plan jitted runner, cached by the plan's static key (so the
    executable set has exactly the round-4 granularity: one per
    (kernel-family, bucket) combination, never per row group)."""
    with _FUSED_LOCK:
        hit = _FUSED_CACHE.get(key)
        if hit is None:
            hit = jax.jit(fn)
            _FUSED_CACHE[key] = hit
        return hit


def _fused_for(key, fns, arities):
    """The jitted all-plans runner for a row-group signature (cached)."""
    with _FUSED_LOCK:
        hit = _FUSED_CACHE.get(key)
        if hit is not None:
            return hit

        def run_all(buf, dyn):
            outs, i = [], 0
            for fn, k in zip(fns, arities):
                outs.append(fn(buf, *dyn[i : i + k]))
                i += k
            return tuple(outs)

        jitted = jax.jit(run_all)
        _FUSED_CACHE[key] = jitted
        return jitted


def _run_plans(plans, buf_dev, timer: "_DeviceTimer | None" = None):
    """Execute ``[(name, _Plan)]`` against the staged buffer: pass-throughs
    directly, everything else through per-plan cached jits with
    device-memoized arguments (or one fused call under TPQ_FUSE_RG=1).

    With a ``timer`` (the reader's completion-timing lane), each traced
    plan's raw jit outputs are handed to the worker with the dispatch
    timestamp and the plan's ship-route attribution — the per-route device
    seconds in the registry's ``device`` section."""
    out = {}
    traced = []
    for name, p in plans:
        if p.fn is None:
            out[name] = p.build(None)
        else:
            traced.append((name, p))
    if not traced:
        return out
    timing = timer is not None and timer.enabled
    if _FUSE_RG:
        key = tuple(p.key for _, p in traced)
        fused = _fused_for(
            key,
            tuple(p.fn for _, p in traced),
            tuple(len(p.dyn) for _, p in traced),
        )
        dyn = tuple(_memo_dev(x) for _, p in traced for x in p.dyn)
        t0 = time.perf_counter() if timing else 0.0
        results = fused(buf_dev, dyn)
        if timing:
            # ONE executable ran: one timing entry, attributed to the
            # dominant (most-bytes-in) plan — per-plan submissions with
            # the shared t0 would each bank the whole fused wall and sum
            # to ~N_plans x the real device time
            dom = max((p for _, p in traced), key=lambda p: p.bytes_in)
            timer.submit("dispatch", dom.route or ROUTE_PLAIN,
                         _kernel_family(dom.key), results, t0,
                         bytes_in=sum(p.bytes_in for _, p in traced),
                         bytes_staged=sum(p.bytes_staged
                                          for _, p in traced),
                         passes=sum(p.stages for _, p in traced))
        for (name, p), res in zip(traced, results):
            out[name] = p.build(res)
        return out
    for name, p in traced:
        jfn = _single_for(p.key, p.fn)
        t0 = time.perf_counter() if timing else 0.0
        res = jfn(buf_dev, *(_memo_dev(x) for x in p.dyn))
        if timing:
            timer.submit("dispatch", p.route or ROUTE_PLAIN,
                         _kernel_family(p.key), res, t0,
                         bytes_in=p.bytes_in, bytes_staged=p.bytes_staged,
                         passes=p.stages)
        out[name] = p.build(res)
    return out


def _compose_column(value_plan: "_Plan", d_plan, r_plan) -> "_Plan":
    """Fuse a chunk's value plan with its def/rep level plans into one
    _Plan producing the finished DeviceColumnData."""
    if value_plan.fn is None and d_plan is None and r_plan is None:
        return value_plan
    nv = len(value_plan.dyn)
    nd = len(d_plan.dyn) if d_plan is not None else 0
    v_fn, d_fn = value_plan.fn, d_plan.fn if d_plan is not None else None
    r_fn = r_plan.fn if r_plan is not None else None
    key = ("col", value_plan.key,
           d_plan.key if d_plan is not None else None,
           r_plan.key if r_plan is not None else None)

    def fn(buf, *dyn):
        vres = v_fn(buf, *dyn[:nv]) if v_fn is not None else None
        dres = d_fn(buf, *dyn[nv : nv + nd]) if d_fn is not None else None
        rres = r_fn(buf, *dyn[nv + nd :]) if r_fn is not None else None
        return (vres, dres, rres)

    dyn = (value_plan.dyn
           + (d_plan.dyn if d_plan is not None else ())
           + (r_plan.dyn if r_plan is not None else ()))
    stages = (value_plan.stages
              + (d_plan.stages if d_plan is not None else 0)
              + (r_plan.stages if r_plan is not None else 0))

    def build(res):
        vres, dres, rres = res
        col = value_plan.build(vres)
        if d_plan is not None:
            col.def_levels = dres
        if r_plan is not None:
            col.rep_levels = rres
        return col

    return _Plan(key, fn, dyn, build, stages=stages)


class _ChunkAssembler:
    """Collects a chunk's pages, then emits one fused device decode."""

    def __init__(self, leaf: SchemaNode, deferred_checks: list):
        self.leaf = leaf
        self.pages: list[ParsedDataPage] = []
        self.dict_u8: Optional[np.ndarray] = None
        self.dict_dtype: Optional[str] = None
        self.dict_ragged: Optional[ByteArrayData] = None
        self.dict_len = 0
        self._deferred = deferred_checks  # (maxima_device_scalar, dict_len, path)
        # (min, max) int hint from chunk-level Statistics — routes the
        # device-snappy vs narrow-transcode choice; never trusted for
        # correctness (see _plan_device_snappy)
        self.stats_span: "tuple[int, int] | None" = None
        self.pages_kept_compressed = 0
        self.pages_pruned = 0  # page-level predicate pushdown skips
        # ship planner state (see preship / tpu_parquet.ship): the ordered
        # route preference, host-built artifacts keyed by route family, and
        # the per-stream (route, logical, shipped) decisions for stats
        self.dict_comp: "tuple | None" = None  # (snappy payload, ulen)
        self.alloc = None  # AllocTracker: recompression copies count too
        self._ship_pref: "list | None" = None
        self._ship: dict = {}
        self._ship_costs: dict = {}  # route -> planner's modeled seconds
        self._ship_dev_costs: dict = {}  # route -> modeled DEVICE seconds
        # fused route -> the UNFUSED chain's modeled device seconds
        # (ship.ShipPlanner.unfused_device_costs) — recorded on fused ship
        # records so the doctor's fusion-win verdict has the prediction
        # the measured fused lane must beat
        self._ship_unfused_dev: dict = {}
        # fused routes that degraded to their unfused twin (caps, level
        # lanes, i32 ceilings) — a counter, never a crash
        self.fused_fallbacks = 0
        self._dict_costs: dict = {}  # same, for the dictionary value table
        self._dict_dev_costs: dict = {}
        self._dict_ship: "tuple | None" = None  # (route, payload, out_len)
        self._bytes_walk: "tuple | None" = None  # (lens_l, span_l)
        self._narrow_compress = False
        self.ship_records: list = []
        # memoized route from a replayed ScanPlan (scanplan.py): preship
        # puts it first in the preference order, so a plain memo skips the
        # failed narrow/recompress probes a first pass already paid
        self._route_hint: "str | None" = None

    def _record_ship(self, route: str, logical: int, shipped: int,
                     predicted: "float | None" = None,
                     predicted_device: "float | None" = None) -> None:
        # the planner's modeled seconds for the route that actually ran —
        # obs.StatsRegistry.ship_feedback puts it next to the measured link
        # lane (TPQ_LINK_MBPS calibration); value-stream records default to
        # the preship plan's cost table, dict-table records pass their own.
        # The device-lane prediction rides the same record so the measured
        # per-route completion timing has a model to calibrate against.
        # Fused records additionally carry the UNFUSED chain's modeled
        # device seconds (0.0 elsewhere) — the fusion-win comparison.
        if predicted is None:
            predicted = self._ship_costs.get(route, 0.0)
        if predicted_device is None:
            predicted_device = self._ship_dev_costs.get(route, 0.0)
        self.ship_records.append(
            (route, int(logical), int(shipped), float(predicted),
             float(predicted_device),
             float(self._ship_unfused_dev.get(route, 0.0))))

    def _apply_route_hint(self) -> None:
        """Reorder the planner's preference behind a replayed route memo.

        Only a route the model priced FEASIBLE for this chunk moves up (a
        hint recorded for different data never forces an impossible
        build); everything else of the ranked order stays as fallback.
        A forced route (``TPQ_FORCE_ROUTE``) wins over any memo."""
        h = self._route_hint
        if (h and self._ship_pref and h in (self._ship_costs or {})
                and self._ship_pref[0] != h):
            self._ship_pref = [h] + [r for r in self._ship_pref if r != h]

    def _route_enabled(self, route: str) -> bool:
        """Whether the planner ranked ``route`` ahead of the plain tail
        (True when no preship ran — legacy chain semantics)."""
        if self._ship_pref is None:
            return True
        for r in self._ship_pref:
            if r == route:
                return True
            if r == ROUTE_PLAIN:
                return False
        return False

    # -- dictionary ----------------------------------------------------------

    @scoped_x64
    def set_dictionary(self, raw: bytes, encoding: int, count: int) -> None:
        decoded = host_decode_dictionary(raw, self.leaf, encoding, count)
        if isinstance(decoded, ByteArrayData):
            self.dict_ragged = decoded
            self.dict_len = len(decoded)
        else:
            self.dict_u8, self.dict_dtype, self.dict_len = decoded

    def dict_cache_entry(self) -> "dict | None":
        """This chunk's decoded dictionary as a read-through cache entry
        (serve.PlanCache): the decoded table, its compressed ship payload
        when the file's own snappy page covers the rows, and a byte size
        for cache accounting.  None when the chunk has no dictionary."""
        if self.dict_len == 0:
            return None
        if self.dict_u8 is not None:
            nbytes = int(self.dict_u8.nbytes)
        elif self.dict_ragged is not None:
            nbytes = int(self.dict_ragged.offsets.nbytes
                         + self.dict_ragged.heap.nbytes)
        else:
            return None
        if self.dict_comp is not None:
            nbytes += len(self.dict_comp[0])
        return {
            "u8": self.dict_u8, "dtype": self.dict_dtype,
            "ragged": self.dict_ragged, "len": self.dict_len,
            "comp": self.dict_comp, "nbytes": nbytes,
        }

    def adopt_dictionary(self, entry: dict) -> None:
        """Adopt a cached decoded dictionary (inverse of
        :meth:`dict_cache_entry`) — shared READ-ONLY across assemblers;
        every consumer gathers/copies, never mutates the tables."""
        self.dict_u8 = entry.get("u8")
        self.dict_dtype = entry.get("dtype")
        self.dict_ragged = entry.get("ragged")
        self.dict_len = int(entry.get("len") or 0)
        self.dict_comp = entry.get("comp")

    # -- ship planning (host half; see tpu_parquet.ship) ----------------------

    def _try_snappy(self, stream, pipe_stats=None):
        """snappy over one host stream (buffer-protocol, no copies); returns
        the payload only when it beats SNAPPY_WORTH_RATIO — thin wins lose
        to the op tables + device resolve."""
        from . import native

        if not native.available():
            return None
        nbytes = len(stream) if isinstance(stream, (bytes, bytearray)) \
            else stream.nbytes
        if nbytes == 0:
            return None
        if self.alloc is not None:
            # register the worst-case compressed size BEFORE materializing
            # it (raise-don't-OOM: the guard must fire before the peak)
            self.alloc.register_transient(nbytes + nbytes // 6 + 32)
        ctx = (pipe_stats.timed("recompress") if pipe_stats is not None
               else _noop_ctx())
        with ctx:
            comp = native.snappy_compress(stream)
        if len(comp) > SNAPPY_WORTH_RATIO * nbytes:
            return None
        return comp

    def _recompress_streams(self, streams, pipe_stats=None):
        """Link recompression (ship.py ROUTE_RECOMPRESS): snappy over each
        page's value stream.  ``streams``: [(buf, pos, size)].  Returns the
        per-page payloads, or None when the whole chunk didn't compress
        past SNAPPY_WORTH_RATIO (the builder then falls through)."""
        from . import native

        if not native.available():
            return None
        total = sum(s[2] for s in streams)
        if total == 0:
            return None
        if self.alloc is not None:
            # the compressed copies coexist with the decompressed originals
            # at their peak — register the worst-case bound BEFORE the
            # copies exist (raise-don't-OOM), per-stream snappy worst case
            # being n + n/6 + 32
            self.alloc.register_transient(
                total + total // 6 + 32 * len(streams))
        ctx = (pipe_stats.timed("recompress") if pipe_stats is not None
               else _noop_ctx())
        payloads = []
        with ctx:
            for buf, pos, size in streams:
                payloads.append(native.snappy_compress(
                    np.frombuffer(buf, np.uint8, size, pos)))
        if sum(len(c) for c in payloads) > SNAPPY_WORTH_RATIO * total:
            return None
        return payloads

    def _narrow_host_transcode(self, width: int):
        """Host half of the narrow routes: span probe, exact min/max, and
        the k-byte truncating transcode into one dense buffer.  Returns
        (k, min, uint8 buffer) or None when the span is too wide (full-range
        data pays only a 64k-value probe, never a full scan).  Pages are
        peeked, not materialized, so a later route can still ship the
        file's compressed payload."""
        from . import native

        if not native.available():
            return None
        max_k = _narrow_max_k(width)
        defined = sum(p.defined for p in self.pages)
        if defined == 0:
            return None
        for p in self.pages:
            p.peek()
        if any(len(p.raw) - p.value_pos < p.defined * width
               for p in self.pages):
            return None  # truncated: the plain path raises with diagnostics
        probe = next(p for p in self.pages if p.defined)
        head = native.int_minmax(
            probe.raw, probe.value_pos, min(probe.defined, _NARROW_PROBE),
            width,
        )
        if _span_bytes(*head) > max_k:
            return None
        mms = [native.int_minmax(p.raw, p.value_pos, p.defined, width)
               for p in self.pages if p.defined]
        mn = min(m[0] for m in mms)
        mx = max(m[1] for m in mms)
        k = _span_bytes(mn, mx)
        if k > max_k:
            return None
        # one truncating pass per page, written straight into a single dense
        # buffer: (v - min) mod 2^width wraps to a value that fits k bytes by
        # construction (negative minima included)
        out = np.empty(defined * k, dtype=np.uint8)
        at = 0
        for p in self.pages:
            native.int_truncate(p.raw, p.value_pos, p.defined, width, mn, k,
                                out[at:])
            at += p.defined * k
        return k, mn, out

    def preship(self, planner: "ShipPlanner | None" = None,
                pipe_stats=None, route_hint: "str | None" = None) -> None:
        """Route choice + link-byte host work for this chunk (ship.py).

        Runs on the prefetch pool's worker threads when prefetch > 0 — the
        same threads that decompress, so ROUTE_RECOMPRESS's snappy pass and
        the narrow transcode overlap the consumer thread's stage/dispatch —
        and inline on the sequential path.  Stores the ordered route
        preference plus any host-built artifacts; ``finish`` executes the
        routes in order, falling through on infeasibility.  Compression
        seconds land in PipelineStats' ``recompress`` stage.

        ``route_hint`` (a replayed ScanPlan's memoized route) moves that
        route to the head of the preference order when the model still
        prices it feasible — the builders' fall-through keeps correctness
        if the replay turns out infeasible on this chunk.
        """
        if planner is None:
            planner = default_planner()
        # a forced route (TPQ_FORCE_ROUTE) wins over any replayed memo
        self._route_hint = route_hint if planner.force is None else None
        self._preship_dict(planner, pipe_stats)
        if not self.pages:
            return
        encs = {parse_encoding(p.encoding) for p in self.pages}
        if encs != {Encoding.PLAIN}:
            return
        leaf = self.leaf
        if leaf.physical_type in _PTYPE_TO_NAME:
            self._preship_fixed(planner, pipe_stats)
        elif leaf.physical_type == Type.BYTE_ARRAY:
            self._preship_bytes(planner, pipe_stats)

    def _preship_fixed(self, planner, pipe_stats) -> None:
        from . import native

        leaf = self.leaf
        name = _PTYPE_TO_NAME[leaf.physical_type]
        width = np.dtype(name).itemsize
        defined = sum(p.defined for p in self.pages)
        logical = defined * width
        comp_bytes = sum(len(p.comp[0]) for p in self.pages
                         if p.comp is not None)
        is_int = leaf.physical_type in (Type.INT32, Type.INT64)
        narrow_k = 0
        if is_int and self.stats_span is not None:
            k = _span_bytes(*self.stats_span)
            if k <= _narrow_max_k(width):
                narrow_k = k
        facts = ChunkFacts(
            logical=logical, width=width, narrow_k=narrow_k,
            narrow_possible=is_int and native.available(),
            comp_bytes=comp_bytes, native=native.available(),
            flat=leaf.max_def == 0 and leaf.max_rep == 0,
        )
        self._ship_pref, self._ship_costs = planner.plan(facts)
        self._ship_dev_costs = planner.device_costs(
            facts, routes=self._ship_costs)
        self._ship_unfused_dev = planner.unfused_device_costs(
            facts, routes=self._ship_costs)
        self._apply_route_hint()
        # failed host work is memoized as a None sentinel so the finish
        # builders (and a later pref entry naming the same family) never
        # repeat a full-chunk scan that already failed — preship exists to
        # keep that work OFF the consumer thread
        for route in self._ship_pref:
            if route in (ROUTE_NARROW, ROUTE_NARROW_SNAPPY):
                if not is_int or defined == 0:
                    continue
                if "narrow" in self._ship:  # earlier pref entry failed
                    continue
                art = self._narrow_host_transcode(width)
                if art is None:
                    self._ship["narrow"] = None
                    continue
                k, mn, out = art
                comp = (self._try_snappy(out, pipe_stats)
                        if route == ROUTE_NARROW_SNAPPY else None)
                self._ship["narrow"] = (k, mn, out, comp)
                return
            if route == ROUTE_DEVICE_SNAPPY:
                if comp_bytes:
                    return  # planned at finish (needs the stager)
                continue
            if route == ROUTE_RECOMPRESS:
                if comp_bytes or defined == 0:
                    continue
                if any(len(p.raw) - p.value_pos < p.defined * width
                       for p in self.pages):
                    continue  # truncated: plain path raises diagnostics
                payloads = self._recompress_streams(
                    [(p.raw, p.value_pos, p.defined * width)
                     for p in self.pages], pipe_stats)
                if payloads is None:
                    self._ship["recompress"] = None
                    continue
                self._ship["recompress"] = payloads
                return
            if route in (ROUTE_PLAIN, ROUTE_FUSED_PLAIN):
                return  # no host artifacts to prepare for either

    def _preship_bytes(self, planner, pipe_stats) -> None:
        from . import native

        if not native.available():
            return
        lens_l, span_l = [], []
        for p in self.pages:
            p.peek()
            res = native.bytearray_lengths(p.raw, p.defined, pos=p.value_pos)
            if res is None or isinstance(res, int):
                return  # finish raises (or falls back) with diagnostics
            lens, end = res
            lens_l.append(lens)
            span_l.append(end - p.value_pos)
        self._bytes_walk = (lens_l, span_l)
        logical = sum(span_l)
        comp_bytes = sum(len(p.comp[0]) for p in self.pages
                         if p.comp is not None)
        facts = ChunkFacts(
            logical=logical, width=0, comp_bytes=comp_bytes, native=True,
        )
        self._ship_pref, self._ship_costs = planner.plan(facts)
        self._ship_dev_costs = planner.device_costs(
            facts, routes=self._ship_costs)
        self._apply_route_hint()
        for route in self._ship_pref:
            if route == ROUTE_DEVICE_SNAPPY:
                if comp_bytes:
                    return  # planned at finish
                continue
            if route == ROUTE_RECOMPRESS:
                if comp_bytes or logical == 0:
                    continue
                payloads = self._recompress_streams(
                    [(p.raw, p.value_pos, s)
                     for p, s in zip(self.pages, span_l)], pipe_stats)
                # failure memoized (None): _plan_snappy_bytes must not
                # repeat the compression on the consumer thread
                self._ship["recompress_bytes"] = payloads
                if payloads is None:
                    continue
                return
            if route == ROUTE_PLAIN:
                return

    def _preship_dict(self, planner, pipe_stats) -> None:
        """Dictionary VALUE TABLE shipping: fixed-width dictionaries whose
        page payload is exactly the rows (PLAIN) can keep the file's snappy
        payload; ragged heaps (and non-snappy files) recompress.  The
        decoded host copy is dropped after staging either way — only the
        link bytes change."""
        from . import native

        if self.dict_len == 0:
            return
        if self.dict_u8 is not None:
            nbytes = self.dict_u8.nbytes
            src = self.dict_u8
        elif self.dict_ragged is not None:
            nbytes = int(self.dict_ragged.heap.nbytes)
            src = self.dict_ragged.heap
        else:
            return
        # the snappy page payload covers the rows only for fixed-width
        # dictionaries (ragged payloads interleave u32 length prefixes)
        comp0 = None
        if (self.dict_u8 is not None and self.dict_comp is not None
                and self.dict_comp[1] >= nbytes):
            comp0 = self.dict_comp
        facts = ChunkFacts(
            logical=nbytes, width=0,
            comp_bytes=len(comp0[0]) if comp0 is not None else 0,
            native=native.available(),
            host_bytes_ready=True,  # dict pages always decompress on host
        )
        dict_routes, self._dict_costs = planner.plan(facts)
        self._dict_dev_costs = planner.device_costs(
            facts, routes=self._dict_costs)
        for route in dict_routes:
            if route == ROUTE_DEVICE_SNAPPY and comp0 is not None:
                self._dict_ship = (route, comp0[0], comp0[1])
                return
            if route == ROUTE_RECOMPRESS and comp0 is None:
                comp = self._try_snappy(np.ascontiguousarray(src),
                                        pipe_stats)
                if comp is None:
                    continue
                self._dict_ship = (route, comp, nbytes)
                return
            if route == ROUTE_PLAIN:
                return

    # -- finish: fused decode -------------------------------------------------

    @scoped_x64
    def finish(self, stager: _RowGroupStager):
        """Phase A (host): parse structure, register bytes with the stager.

        Returns a closure ``fn(buf_dev) -> DeviceColumnData`` that dispatches
        the chunk's kernels against the staged row-group buffer.
        """
        leaf = self.leaf
        slots = sum(p.num_values for p in self.pages)
        encs = {parse_encoding(p.encoding) for p in self.pages}
        encs = {
            Encoding.RLE_DICTIONARY if e == Encoding.PLAIN_DICTIONARY else e
            for e in encs
        }
        # lazily-compressed pages are only consumed by the compressed-ship
        # routes (PLAIN fixed-width and PLAIN BYTE_ARRAY — see ship.py);
        # every other route gets host bytes
        lazy_ok = encs == {Encoding.PLAIN} and (
            leaf.physical_type in _PTYPE_TO_NAME
            or leaf.physical_type == Type.BYTE_ARRAY
        )
        if any(p.comp is not None for p in self.pages) and not lazy_ok:
            for p in self.pages:
                p.materialize()
        slots_pad = _bucket_count(slots)
        d_plan = r_plan = None
        if leaf.max_def > 0:
            d_plan = self._plan_levels(
                stager, [p.def_stream for p in self.pages],
                bitpack.bit_width(leaf.max_def), slots, slots_pad,
                metas=[p.def_meta for p in self.pages],
            )
        if leaf.max_rep > 0:
            r_plan = self._plan_levels(
                stager, [p.rep_stream for p in self.pages],
                bitpack.bit_width(leaf.max_rep), slots, slots_pad,
            )

        common = dict(
            max_def=leaf.max_def, max_rep=leaf.max_rep, num_leaf_slots=slots,
            value_dtype=(
                "float64" if leaf.physical_type == Type.DOUBLE else None
            ),
        )

        if len(encs) == 1:
            enc = next(iter(encs))
            if enc == Encoding.RLE_DICTIONARY:
                value_fn = self._finish_dict(common, stager)
            elif enc == Encoding.PLAIN and leaf.physical_type in _PTYPE_TO_NAME:
                value_fn = self._finish_plain_fixed(common, stager)
            elif enc == Encoding.PLAIN and leaf.physical_type == Type.BOOLEAN:
                value_fn = self._finish_plain_bool(common, stager)
            elif enc == Encoding.PLAIN and leaf.physical_type == Type.BYTE_ARRAY:
                value_fn = self._finish_plain_bytes(common, stager)
            elif (enc == Encoding.PLAIN and leaf.physical_type == Type.INT96):
                value_fn = self._finish_plain_rows(common, stager, 12)
            elif (enc == Encoding.PLAIN
                  and leaf.physical_type == Type.FIXED_LEN_BYTE_ARRAY
                  and (leaf.type_length or 0) > 0):
                value_fn = self._finish_plain_rows(common, stager,
                                                   leaf.type_length,
                                                   flba=True)
            elif enc == Encoding.DELTA_BINARY_PACKED:
                value_fn = self._finish_delta(common, stager)
            else:
                value_fn = self._finish_host(common)
        elif (encs == {Encoding.RLE_DICTIONARY, Encoding.PLAIN}
              and leaf.physical_type in _PTYPE_TO_NAME
              and self.dict_u8 is not None):
            # dictionary-overflow fallback: early pages dict-encoded, later
            # pages PLAIN (type_dict.go:101-103 semantics on the write side)
            value_fn = self._finish_mixed_dict_plain(common, stager)
        else:
            # other mixed encodings, BSS, INT96, FLBA, delta byte arrays,
            # boolean RLE: host decode per page, stage per chunk
            value_fn = self._finish_host(common)

        # every plan has captured what it needs; dropping the parsed pages
        # here releases all raw decompressed page bytes before dispatch (the
        # iter_row_groups pipeline otherwise pins a whole extra row group)
        self.pages = []
        # level arrays expand on device from the staged RLE streams at the
        # bucketed slot count (tail zeros past num_leaf_slots)
        return _compose_column(value_fn, d_plan, r_plan)

    def _plan_levels(self, stager: _RowGroupStager, streams, width: int,
                     slots: int, slots_pad: int, metas=None):
        """Stage the pages' raw RLE level streams and expand them on device.

        Levels are run-dominated: the encoded stream is a fraction of the
        4-bytes-per-slot decoded array, so staging the stream + run tables
        instead of host-decoded uint32 arrays cuts the dominant transfer on
        nested files (~2/3 of staged bytes on the LIST/MAP bench config).
        Returns ``fn(buf_dev) -> uint32[slots_pad]`` (tail past ``slots``
        zeroed).  Every decode_levels=False parse records the stream span
        whenever max_def/max_rep > 0, so a missing span is a caller bug.
        """
        if metas is None:
            metas = [None] * len(self.pages)
        if any(s is None for s in streams):
            raise ParquetError(
                "internal: level stream span missing on the batched path"
            )
        metas = [
            m if m is not None else parse_hybrid_meta(
                src, width, p.num_values, pos=start, end=start + size
            )
            for (src, start, size), p, m in zip(streams, self.pages, metas)
        ]
        interp = _pallas_interpret_mode()
        if interp is not None:
            plan = _plan_hybrid_pallas(
                stager,
                [(m, src, p.num_values)
                 for (src, _, _), p, m in zip(streams, self.pages, metas)],
                width, slots, slots_pad, interp,
            )
            if plan is not None:
                return plan
        bases = stager.add_segments(list(streams))
        ends_l, rle_l, vals_l, starts_l = [], [], [], []
        prefix = 0
        for (src, start, size), base, p, meta in zip(streams, bases,
                                                     self.pages, metas):
            n = meta.n_runs
            ends_l.append(meta.run_ends[:n] + prefix)
            rle_l.append(meta.run_is_rle[:n])
            vals_l.append(meta.run_values[:n])
            # source byte b lands at staged (b - start + base); rebase bit
            # starts for the copy and for the global value position
            starts_l.append(
                meta.run_bit_starts[:n] + (int(base) - start) * 8
                - prefix * width
            )
            prefix += p.num_values
        ends, is_rle, rvals, starts = _merge_run_tables(
            ends_l, rle_l, vals_l, starts_l, fill_end=slots
        )

        def fn(buf_dev, ends_d, isr_d, rvals_d, starts_d, slots_d):
            return _hybrid_jit(buf_dev, ends_d, isr_d, rvals_d, starts_d,
                               slots_d, width=width, count=slots_pad)

        return _Plan(("lvlx", width, slots_pad), fn,
                     (ends, is_rle, rvals, starts, np.int64(slots)), None,
                     stages=2)  # run-table expand pass + tail-mask pass

    def _value_segments(self, stager: _RowGroupStager) -> np.ndarray:
        """Register all pages' value streams back-to-back; returns byte bases
        (absolute offsets in the staged buffer), int64[P].  The page bytes are
        copied exactly once, by ``stage()``, straight into the row-group
        buffer."""
        return stager.add_segments([
            (p.raw, p.value_pos, len(p.raw) - p.value_pos) for p in self.pages
        ])

    def _stage_fixed_width(self, stager, width: int):
        """Register exactly the pages' value bytes back-to-back for a
        ``width``-bytes-per-value PLAIN stream.

        Returns (base, defined, count): the staged byte base, the real value
        count, and the bucketed static count the kernel decodes — it reads
        past the segments into whatever follows in the staged buffer
        (harmless garbage past n_values, in-bounds by note_read_extent), so
        one executable is shared across chunks.
        """
        defined = sum(p.defined for p in self.pages)
        _check_plain_sizes(self.pages, width)
        segs = [(p.raw, p.value_pos, p.defined * width) for p in self.pages]
        base = (int(stager.add_segments(segs)[0]) if segs
                else stager._reserve(0, None))
        count = _bucket_count(defined)
        stager.note_read_extent(base, count * width)
        return base, defined, count

    def _finish_plain_fixed(self, common, stager):
        """PLAIN fixed-width dispatcher: execute the ship planner's route
        preference in order (ship.py), falling through on infeasibility —
        the ``plain`` tail can never fail.  Without a preship pass (direct
        decode_chunk_batched callers) the legacy chain applies:
        device-snappy, then narrow, then plain."""
        name = _PTYPE_TO_NAME[self.leaf.physical_type]
        pref = self._ship_pref
        if pref is None:
            pref = [ROUTE_DEVICE_SNAPPY, ROUTE_NARROW, ROUTE_PLAIN]
        for route in pref:
            plan = None
            if route == ROUTE_PLAIN:
                break  # the infallible tail below; later entries are dead
            if route == ROUTE_DEVICE_SNAPPY:
                if any(p.comp is not None for p in self.pages):
                    plan = self._plan_device_snappy(common, stager, name)
            elif route == ROUTE_FUSED_PLAIN:
                plan = self._plan_fused_plain(common, stager, name)
            elif route in (ROUTE_NARROW, ROUTE_NARROW_SNAPPY):
                if name in ("int32", "int64"):
                    self._narrow_compress = route == ROUTE_NARROW_SNAPPY
                    plan = self._plan_narrow_ints(common, stager, name)
            elif route == ROUTE_RECOMPRESS:
                plan = self._plan_recompress_fixed(common, stager, name)
            if plan is None and route in FUSED_ROUTES:
                # forced/planned fused on a stream the megakernel cannot
                # claim (levels, i32 ceilings):
                # degrade to the next-ranked route with a COUNTER, never a
                # crash — the fuzz target's invariant
                self.fused_fallbacks += 1
            if plan is not None:
                return plan
        for p in self.pages:
            p.materialize()
        base, defined, count = self._stage_fixed_width(
            stager, np.dtype(name).itemsize
        )
        logical = defined * np.dtype(name).itemsize
        self._record_ship(ROUTE_PLAIN, logical, logical)
        return _Plan(
            ("plain", name, count),
            lambda buf, base_d: _plain_jit(buf, base_d, dtype=name,
                                           count=count),
            (np.int64(base),),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
        )

    def _plan_recompress_fixed(self, common, stager, name: str):
        """Link recompression for PLAIN fixed-width chunks stored GZIP/ZSTD/
        uncompressed (ship.py ROUTE_RECOMPRESS): the host decompressed these
        bytes anyway, so one more snappy pass trades cheap host cycles for
        link bytes, and the device expands through the same resolver as
        native snappy files.  Normally prepared by preship on the prefetch
        pool; compresses inline when reached without one."""
        width = np.dtype(name).itemsize
        if any(p.comp is not None for p in self.pages):
            return None  # the file's own payload is the better ship
        defined = sum(p.defined for p in self.pages)
        if defined == 0:
            return None
        _check_plain_sizes(self.pages, width)
        if "recompress" in self._ship:
            payloads = self._ship["recompress"]  # None: preship declined
        else:
            payloads = self._recompress_streams(
                [(p.raw, p.value_pos, p.defined * width) for p in self.pages])
        if payloads is None:
            return None
        sizes = [p.defined * width for p in self.pages]
        specs = [("comp", c, n, None) for c, n in zip(payloads, sizes)]
        vbase_t, vstart_t, pages_pad, _ = _fixed_value_tables(
            sizes, [p.defined for p in self.pages])
        count = _bucket_count(defined)
        info = _plan_snappy_ops(stager, specs,
                                extra_tables=[vbase_t, vstart_t])
        if info is None:
            return None
        self.pages_kept_compressed = len(specs)
        self._record_ship(ROUTE_RECOMPRESS, defined * width, info.shipped)
        n_ops, out_pad, iters = info.n_ops, info.out_pad, info.iters
        return _Plan(
            ("snappy", n_ops, out_pad, iters, name, count, pages_pad),
            lambda buf, tbase_d: _snappy_plain_staged_jit(
                buf, tbase_d, n_ops=n_ops, out_pad=out_pad,
                iters=iters, dtype=name, count=count, n_pages=pages_pad,
            ),
            (np.int64(info.tbase),),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
            # op-map pass + `iters` doubling rounds + byte gather + decode
            stages=3 + iters,
        )

    def _plan_device_snappy(self, common, stager, name: str):
        """Ship COMPRESSED snappy PLAIN pages; decompress + decode on device.

        Host work per page collapses to the native tag walk (~1 byte touched
        per ~60 payload bytes) — no decompression, no value copies; the
        staged transfer carries the compressed stream.  See
        _snappy_plain_staged_jit for the device side.  Returns None when the
        chunk should fall back (narrow-int stats hint, 2 GiB i32 ceiling,
        shattered op tables, native library absent) — the caller then
        materializes and takes the standard host paths.
        """
        from . import native

        width = np.dtype(name).itemsize
        # legacy stats hint (pre-planner chain only): a narrow int span
        # means host decompress + narrow transcode ships FEWER bytes than
        # the compressed stream — decline so the chain's next step claims
        # it.  With a planner preference the hint already routed via
        # ChunkFacts.narrow_k, and declining HERE would fight it: narrow
        # may rank after plain, have already failed (lying stats), or be
        # absent entirely under TPQ_FORCE_ROUTE=device_snappy.
        if (self._ship_pref is None and name in ("int32", "int64")
                and self.stats_span is not None):
            lo, hi = self.stats_span
            if _span_bytes(lo, hi) <= _narrow_max_k(width):
                return None
        _check_plain_sizes(self.pages, width)
        specs = []
        sizes = []
        lazy_out = comp_bytes = 0
        for p in self.pages:
            if p.comp is not None:
                payload, _codec, ulen = p.comp
                r = native.snappy_plan(payload, ulen)
                if r is None:
                    return None
                if isinstance(r, int):
                    # malformed stream: materialize so the standard codec
                    # diagnostics raise (same reject set as the planner)
                    p.materialize()
                    return None
                specs.append(("comp", payload, ulen, r))
                sizes.append(ulen)
                lazy_out += ulen
                comp_bytes += len(payload)
            else:
                nbytes = len(p.raw) - p.value_pos
                # staged segment: the raw value bytes for already-
                # materialized pages (one synthetic literal op each)
                specs.append(("raw", p.raw, p.value_pos, nbytes))
                sizes.append(nbytes)
        # worth-it gate (measured on v5e): shipping compressed pays for the
        # device-side resolve whenever the stream actually compressed; at
        # ratio ~1 the only win is the skipped host decompress, which beats
        # the resolve cost on small chunks but loses on multi-strip ones
        if (lazy_out > 0 and comp_bytes > SNAPPY_WORTH_RATIO * lazy_out
                and lazy_out > _SNAPPY_SMALL_OUT):
            return None
        # out-space bases: value_pos == 0 on lazy pages (parse contract)
        vbase_t, vstart_t, pages_pad, defined = _fixed_value_tables(
            sizes, [p.defined for p in self.pages])
        info = _plan_snappy_ops(stager, specs,
                                extra_tables=[vbase_t, vstart_t])
        if info is None:
            return None
        count = _bucket_count(defined)
        self.pages_kept_compressed = len(
            [1 for s in specs if s[0] == "comp"])
        self._record_ship(ROUTE_DEVICE_SNAPPY, defined * width, info.shipped)
        n_ops, out_pad, iters = info.n_ops, info.out_pad, info.iters
        return _Plan(
            ("snappy", n_ops, out_pad, iters, name, count, pages_pad),
            lambda buf, tbase_d: _snappy_plain_staged_jit(
                buf, tbase_d, n_ops=n_ops, out_pad=out_pad,
                iters=iters, dtype=name, count=count, n_pages=pages_pad,
            ),
            (np.int64(info.tbase),),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
            # op-map pass + `iters` doubling rounds + byte gather + decode
            stages=3 + iters,
        )

    def _plan_narrow_ints(self, common, stager, name: str):
        """Narrow transcode for PLAIN INT columns: ship ``v - min`` truncated
        to the minimal byte width instead of full-width values.

        Real-world int64 columns are overwhelmingly narrow-ranged (ids,
        dates, quantities — TPC-H l_partkey spans 18 bits, shipped 8 bytes
        wide by PLAIN), and the host→device link is the scarce resource
        the whole reader is engineered around.  The host is already
        touching these bytes (decompress), so one extra vectorized pass
        (min/max + truncating copy) buys a (width-k)/width transfer cut; the
        device widens and re-biases in one fused kernel (_plain_narrow_jit).
        Under ship.py's ROUTE_NARROW_SNAPPY the truncated buffer is
        additionally snappy-compressed — narrow residuals are low-entropy,
        so the two transfer cuts multiply (_snappy_narrow_staged_jit).
        Returns None (caller takes the next route) when the span probe shows
        < _NARROW_SAVE_BYTES savings, so full-range data pays only a 64k-value
        probe, not a full scan.
        """
        from . import native

        width = np.dtype(name).itemsize
        _check_plain_sizes(self.pages, width)
        defined = sum(p.defined for p in self.pages)
        if defined == 0 or not native.available():
            return None
        if "narrow" in self._ship:
            art = self._ship["narrow"]
            if art is None:
                return None  # preship already scanned and declined
            k, mn, out, comp = art
        else:
            trans = self._narrow_host_transcode(width)
            if trans is None:
                return None
            k, mn, out = trans
            comp = (self._try_snappy(out) if self._narrow_compress else None)
        count = _bucket_count(defined)
        bias = np.int32(mn) if name == "int32" else np.int64(mn)
        if comp is not None:
            info = _plan_snappy_ops(
                stager, [("comp", comp, out.nbytes, None)])
            if info is not None:
                self.pages_kept_compressed = len(self.pages)
                self._record_ship(ROUTE_NARROW_SNAPPY, defined * width,
                                  info.shipped)
                n_ops, out_pad, iters = info.n_ops, info.out_pad, info.iters
                return _Plan(
                    ("narrows", k, name, count, n_ops, out_pad, iters),
                    lambda buf, tb_d, bias_d: _snappy_narrow_staged_jit(
                        buf, tb_d, bias_d, n_ops=n_ops, out_pad=out_pad,
                        iters=iters, k=k, dtype=name, count=count),
                    (np.int64(info.tbase), bias),
                    lambda v: DeviceColumnData(values=v, n_values=defined,
                                               **common),
                    # op-map pass + `iters` doubling rounds + byte
                    # gather + widen/re-bias
                    stages=3 + iters,
                )
            # op planning fell through: ship the narrow bytes uncompressed
        base = stager.add(out)
        stager.note_read_extent(base, count * k)
        self._record_ship(ROUTE_NARROW, defined * width, out.nbytes)
        return _Plan(
            ("narrow", k, name, count),
            lambda buf, base_d, bias_d: _plain_narrow_jit(
                buf, base_d, bias_d, k=k, dtype=name, count=count),
            (np.int64(base), bias),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
        )

    def _plan_fused_plain(self, common, stager, name: str):
        """ONE Pallas pass for a PLAIN fixed-width chunk (ship.py
        ROUTE_FUSED_PLAIN): byte-plane assembly of the staged value stream
        plus the validity tail mask in a single device dispatch, replacing
        the unfused slice → bitcast → tail chain and its HBM round trips.
        Same link bytes as ``plain`` — the win is the device lane and the
        dispatch count, which the registry ``device`` section proves
        structurally (``device_passes`` == ``dispatches``).  Returns None
        (degrade to the next route, counted by the caller) when the column
        carries level lanes or the staged arena exceeds the kernel's i32
        addressing."""
        from .pallas_kernels import (
            fused_count_pad, fused_plain_words, resolve_interpret,
        )

        leaf = self.leaf
        if leaf.max_def > 0 or leaf.max_rep > 0:
            return None  # fused claims flat streams only (ship.fused_eligible)
        width = np.dtype(name).itemsize
        if width not in (4, 8):
            return None
        _check_plain_sizes(self.pages, width)
        defined = sum(p.defined for p in self.pages)
        count = fused_count_pad(defined)
        if stager.total + count * width > np.iinfo(np.int32).max:
            return None  # x64-free pallas trace addresses the arena with i32
        for p in self.pages:
            p.materialize()
        segs = [(p.raw, p.value_pos, p.defined * width) for p in self.pages]
        base = (int(stager.add_segments(segs)[0]) if segs
                else stager._reserve(0, None))
        stager.note_read_extent(base, count * width)
        interp = resolve_interpret()
        logical = defined * width
        self._record_ship(ROUTE_FUSED_PLAIN, logical, logical)

        def fn(buf, base_d, nv_d):
            words = fused_plain_words(buf, base_d, nv_d, width=width,
                                      count_pad=count, interpret=interp)
            return _fused_words_cast(words, name)

        return _Plan(
            ("fusedp", name, count, bool(interp)), fn,
            (np.int32(base), np.int32(defined)),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
            stages=1,
        )

    def _finish_plain_rows(self, common, stager, k: int, flba: bool = False):
        """PLAIN fixed-length rows: exactly the value bytes back-to-back, one
        bucketed slice — INT96 as u32[n,3] values, FLBA as the uniform
        (offsets, heap) ragged form (matching the host decoder)."""
        base, defined, count = self._stage_fixed_width(stager, k)

        def fn(buf, base_d):
            if flba:
                return _plain_flba_jit(buf, base_d, k=k, count=count)
            return _plain_rows_jit(buf, base_d, k=k, count=count)

        def build(res):
            col = DeviceColumnData(n_values=defined, **common)
            if flba:
                col.offsets, col.heap = res
            else:
                col.values = res
            return col

        return _Plan(("rows", k, bool(flba), count), fn, (np.int64(base),),
                     build)

    def _finish_plain_bool(self, common, stager):
        defined = sum(p.defined for p in self.pages)
        for p in self.pages:
            need = (p.defined + 7) // 8
            if len(p.raw) - p.value_pos < need:
                raise ParquetError(
                    f"PLAIN BOOLEAN truncated: {len(p.raw) - p.value_pos} < {need}"
                )
        bases = self._value_segments(stager)
        n_pages = _bucket(len(self.pages))
        byte_base = np.zeros(n_pages, dtype=np.int64)
        byte_base[: len(self.pages)] = bases
        byte_base[len(self.pages):] = bases[-1] if len(self.pages) else 0
        starts = np.full(n_pages, defined, dtype=np.int64)
        acc = 0
        for i, p in enumerate(self.pages):
            starts[i] = acc
            acc += p.defined
        count = _bucket_count(defined)
        return _Plan(
            ("bool", count, n_pages),
            lambda buf, bb_d, st_d: _bool_pages_jit(buf, bb_d, st_d,
                                                    count=count),
            (byte_base, starts),
            lambda v: DeviceColumnData(values=v, n_values=defined, **common),
        )

    def _finish_plain_bytes(self, common, stager):
        """PLAIN BYTE_ARRAY chunk: host walks only the length prefixes
        (native, no copies); the streams + lengths stage and the heap
        compaction/offset cumsum run on device (_plain_bytes_pages_jit).

        Value streams ship by the planner's route (ship.py): the file's own
        snappy payloads (ROUTE_DEVICE_SNAPPY), a host snappy re-compression
        of the walked spans (ROUTE_RECOMPRESS, prepared by preship on the
        decompress pool), or the raw spans (plain).  Byte-array heaps are
        the dominant mover on string-heavy schemas (lineitem16), so this is
        where compressed shipping pays most.  Falls back to the round-2
        host-decode staging when the native library is unavailable."""
        from . import native

        if self._bytes_walk is not None:
            lens_l, span_l = self._bytes_walk
        else:
            lens_l, span_l = [], []
            for p in self.pages:
                # whole page buffer + offset: no host copy of the stream
                p.peek()
                res = native.bytearray_lengths(p.raw, p.defined,
                                               pos=p.value_pos)
                if res is None:
                    return self._finish_plain_bytes_host(common, stager)
                if isinstance(res, int):
                    if res == -20:
                        raise ParquetError(
                            "byte array: truncated length prefix")
                    raise ParquetError("byte array: length exceeds buffer")
                lens, end = res
                lens_l.append(lens)
                span_l.append(end - p.value_pos)
        n = sum(p.defined for p in self.pages)
        logical = sum(span_l)
        count_pad = _bucket_count(n)
        lens_all = (np.concatenate(lens_l) if lens_l
                    else np.zeros(0, np.uint32))
        total_heap = int(lens_all.astype(np.int64).sum())
        heap_pad = _bucket_bytes(max(total_heap, 1), 64)
        n_pages = _bucket(len(self.pages))
        pvs = np.full(n_pages + 1, n, dtype=np.int32)
        pvs[0] = 0
        np.cumsum([p.defined for p in self.pages],
                  out=pvs[1 : len(self.pages) + 1])

        def build(res):
            offsets, heap = res
            return DeviceColumnData(offsets=offsets, heap=heap, n_values=n,
                                    **common)

        plan = self._plan_snappy_bytes(
            stager, span_l, pvs, count_pad, heap_pad, n_pages, lens_all,
            logical, build)
        if plan is not None:
            return plan
        # plain route: stage exactly the walked stream spans, back to back
        for p in self.pages:
            p.materialize()
        bases = stager.add_segments([
            (p.raw, p.value_pos, c) for p, c in zip(self.pages, span_l)
        ])
        # zero-filled reserve: pad values past n must read length 0
        lens_base = stager.add(lens_all, reserve=count_pad * 4)
        page_base = np.zeros(n_pages, dtype=np.int64)
        page_base[: len(bases)] = bases
        tbase = _pack_tables(stager, [page_base, pvs])
        self._record_ship(ROUTE_PLAIN, logical, logical)
        return _Plan(
            ("bytes", count_pad, heap_pad, n_pages),
            lambda buf, lb_d, tb_d: _plain_bytes_staged_jit(
                buf, lb_d, tb_d, count_pad=count_pad, heap_pad=heap_pad,
                n_pages=n_pages),
            (np.int64(lens_base), np.int64(tbase)),
            build,
        )

    def _plan_snappy_bytes(self, stager, span_l, pvs, count_pad, heap_pad,
                           n_pages, lens_all, logical, build):
        """Compressed-shipping half of _finish_plain_bytes: build the op
        tables for whichever compressed payloads exist (the file's own, or
        preship's re-compression) and wire _snappy_bytes_staged_jit.
        Returns None when no compressed route applies or planning falls
        through — the caller stages the raw spans."""
        route = None
        specs = None
        if (any(p.comp is not None for p in self.pages)
                and self._route_enabled(ROUTE_DEVICE_SNAPPY)):
            comp_total = sum(len(p.comp[0]) for p in self.pages
                             if p.comp is not None)
            # ratio ~1: the op tables + resolve buy nothing — ship raw
            if comp_total <= SNAPPY_WORTH_RATIO * max(logical, 1):
                route = ROUTE_DEVICE_SNAPPY
                specs = [
                    ("comp", p.comp[0], p.comp[2], None)
                    if p.comp is not None
                    else ("raw", p.raw, p.value_pos, span)
                    for p, span in zip(self.pages, span_l)
                ]
        elif self._ship.get("recompress_bytes") is not None:
            route = ROUTE_RECOMPRESS
            specs = [
                ("comp", c, span, None)
                for c, span in zip(self._ship["recompress_bytes"], span_l)
            ]
        if specs is None:
            return None
        out_lens = [s[2] if s[0] == "comp" else s[3] for s in specs]
        page_out = np.zeros(n_pages, dtype=np.int64)
        page_out[: len(specs)] = np.concatenate(
            [[0], np.cumsum(out_lens)[:-1]])
        info = _plan_snappy_ops(stager, specs,
                                extra_tables=[page_out, pvs])
        if info is None:
            return None
        # zero-filled reserve: pad values past n must read length 0
        lens_base = stager.add(lens_all, reserve=count_pad * 4)
        self.pages_kept_compressed = len(
            [1 for s in specs if s[0] == "comp"])
        self._record_ship(route, logical, info.shipped)
        n_ops, out_pad, iters = info.n_ops, info.out_pad, info.iters
        return _Plan(
            ("bytess", count_pad, heap_pad, n_pages, n_ops, out_pad, iters),
            lambda buf, lb_d, tb_d: _snappy_bytes_staged_jit(
                buf, lb_d, tb_d, count_pad=count_pad, heap_pad=heap_pad,
                n_ops=n_ops, out_pad=out_pad, iters=iters, n_pages=n_pages),
            (np.int64(lens_base), np.int64(info.tbase)),
            build,
            stages=3 + iters,
        )

    def _finish_plain_bytes_host(self, common, stager):
        """PLAIN BYTE_ARRAY chunk: native host walk per page, merged offsets,
        heap shipped in the row-group buffer (no per-page transfers)."""
        from .kernels import plain as plain_host

        offs_parts, heap_parts = [], []
        for p in self.pages:
            ba = plain_host.decode_byte_array(
                p.raw[p.value_pos :], p.defined
            )
            offs_parts.append(ba.offsets)
            heap_parts.append(ba.heap)
        counts = np.array([len(o) - 1 for o in offs_parts], dtype=np.int64)
        heap_sizes = np.array([h.nbytes for h in heap_parts], dtype=np.int64)
        n = int(counts.sum())
        offsets = np.empty(n + 1, dtype=np.int64)
        offsets[0] = 0
        pos = 0
        hbase = 0
        for o, hs in zip(offs_parts, heap_sizes):
            k = len(o) - 1
            offsets[pos + 1 : pos + 1 + k] = o[1:] + hbase
            pos += k
            hbase += int(hs)
        heap = (np.concatenate(heap_parts) if len(heap_parts) > 1
                else heap_parts[0])
        heap_len = heap.nbytes
        heap_room = _bucket_bytes(max(heap_len, 1), 64)
        heap_base = stager.add(heap, reserve=heap_room)
        off_base = stager.add(offsets)
        n_off = _bucket_count(n + 1)
        stager.note_read_extent(off_base, n_off * 8)

        def fn(buf, off_d, heap_d):
            # bucketed offset count (tail garbage past n+1, sliced by
            # to_host); bucketed heap slice (zero padding past offsets[-1],
            # trimmed on host) keeps executables shared
            return (_plain_jit(buf, off_d, dtype="int64", count=n_off),
                    _dynslice_jit(buf, heap_d, size=heap_room))

        def build(res):
            col = DeviceColumnData(n_values=n, **common)
            col.offsets, col.heap = res
            return col

        return _Plan(("bytesh", n_off, heap_room), fn,
                     (np.int64(off_base), np.int64(heap_base)), build)

    def _parse_dict_index_page(self, p, host_max):
        """Parse one RLE_DICTIONARY page's index stream; folds the host-side
        max when it is FREE (None = unknown, defer to device check).  Shared
        by the pure-dict and mixed dict+PLAIN finish paths.  Returns the
        sliced stream too so callers staging payload segments reference the
        parsed coords.

        When the dictionary covers the index stream's whole bit-width value
        range (dict_len >= 2^width), NO encodable index can be out of range,
        so the exact-max request is skipped — that upgrade turns the
        O(runs) header walk into an O(values) scan, the single hottest host
        cost on dictionary-heavy files (~4 s of a 100-row-group 22 s scan).
        The deferred device-side max stays OPT-IN (TPQ_DEFER_DICT_CHECK=1)
        even though the _Plan refactor folds the ``jnp.max`` into the
        chunk's one fused executable with all maxima synced once at
        finalize: measured round 5 on the early remote backend, a 100M-row scan
        holding ~700 live tiny max buffers degraded warm reps 24 s → 514 s
        (and round 4's separate-dispatch variant lost 20× before that).
        The host walk's O(values) scan is the cheaper evil at every scale
        measured, and it reports corruption at the exact page.
        """
        stream = p.raw[p.value_pos :]
        if len(stream) < 1:
            raise ParquetError("dictionary page data truncated (missing width)")
        width = int(stream[0])
        if width > 32:
            raise ParquetError(f"dictionary index width {width} invalid")
        covered = width < 31 and self.dict_len >= (1 << width)
        defer = os.environ.get("TPQ_DEFER_DICT_CHECK", "") == "1"
        meta = parse_hybrid_meta(stream, width, p.defined, pos=1,
                                 compute_max=not covered and not defer)
        if p.defined == 0:
            pass  # no indices: nothing to fold into the max
        elif covered:
            # bit-packed values are masked to `width`, hence < 2^width <=
            # dict_len — in range by construction.  RLE run values are RAW
            # unmasked bytes (see meta_parse.cpp note) and can exceed the
            # width's range, so fold them from the run table — O(runs).
            n = meta.n_runs
            rle_mask = meta.run_is_rle[:n]
            if host_max is not None and rle_mask.any():
                host_max = max(host_max,
                               int(meta.run_values[:n][rle_mask].max()))
        elif host_max is not None and meta.max_value is not None:
            host_max = max(host_max, meta.max_value)
        else:
            host_max = None  # Python fallback walk: defer check to device
        return meta, width, stream, host_max

    def _check_dict_range(self, prefix, host_max):
        if prefix and self.dict_len == 0:
            raise ParquetError("dictionary indices with empty dictionary")
        if prefix and host_max is not None and host_max >= self.dict_len:
            raise ParquetError(
                f"dictionary index {host_max} out of range ({self.dict_len}) "
                f"in column {'.'.join(self.leaf.path)}"
            )

    def _finish_dict(self, common, stager):
        if self.dict_u8 is None and self.dict_ragged is None:
            raise ParquetError("dictionary-encoded page but no dictionary page seen")
        # parse every page's index stream once (host_max folds the native
        # walk's per-page maxima; None defers the range check to device)
        parsed = []  # (page, stream, meta)
        page_widths = []
        host_max = 0 if self.pages else None
        for p in self.pages:
            meta, pw, stream, host_max = self._parse_dict_index_page(p, host_max)
            parsed.append((p, stream, meta))
            page_widths.append(pw)
        uniform = len(set(page_widths)) <= 1
        width = page_widths[0] if page_widths else 0
        prefix = sum(p.defined for p in self.pages)
        interp = _pallas_interpret_mode()
        plan = None
        if uniform and prefix and interp is not None:
            plan = _plan_hybrid_pallas(
                stager, [(m, s, p.defined) for p, s, m in parsed],
                width, prefix, _bucket_count(prefix), interp,
            )
        if plan is None:
            bases = self._value_segments(stager)
            ends_l, rle_l, vals_l, starts_l, widths_l = [], [], [], [], []
            pos0 = 0
            for (p, stream, meta), base, pw in zip(parsed, bases, page_widths):
                n = meta.n_runs
                ends_l.append(meta.run_ends[:n] + pos0)
                rle_l.append(meta.run_is_rle[:n])
                vals_l.append(meta.run_values[:n])
                # global bit base: page byte base within buf, re-zeroed for
                # the global value position (see jax_kernels.expand_rle_hybrid)
                starts_l.append(
                    meta.run_bit_starts[:n] + base * 8 - pos0 * pw
                )
                widths_l.append(np.full(n, pw, dtype=np.uint32))
                pos0 += p.defined
            ends, is_rle, rvals, starts, rwidths = _merge_run_tables(
                ends_l, rle_l, vals_l, starts_l, fill_end=prefix,
                widths_l=widths_l,
            )
        self._check_dict_range(prefix, host_max)
        dict_u8 = self.dict_u8
        has_u8 = dict_u8 is not None
        cp = _bucket_count(prefix)
        dyn: list = []
        if plan is not None:
            idx_key, idx_fn, idx_arity = plan.key, plan.fn, len(plan.dyn)
            dyn.extend(plan.dyn)
        elif uniform:
            idx_key = ("hyb", width, cp)
            idx_arity = 5

            def idx_fn(buf, e, r, v, s, nv):
                return _hybrid_jit(buf, e, r, v, s, nv, width=width, count=cp)

            dyn.extend((ends, is_rle, rvals, starts, np.int64(prefix)))
        else:
            # per-page index widths differ (dictionary grew page to page):
            # same fused expansion with per-run widths
            mw = min(max(8, (max(page_widths) + 7) // 8 * 8), 32)
            idx_key = ("hybvw", mw, cp)
            idx_arity = 6

            def idx_fn(buf, e, r, v, s, w, nv):
                return _hybrid_vw_jit(buf, e, r, v, s, w, nv, max_width=mw,
                                      count=cp)

            dyn.extend((ends, is_rle, rvals, starts, rwidths,
                        np.int64(prefix)))
        # no native walk: deferred on-device range check (max rides the
        # fused call's outputs, one sync at finalize); bucketing tail lanes
        # are zeroed by n_valid, so the max reflects only real indices
        need_max = bool(prefix) and host_max is None
        ship = self._dict_ship  # (route, payload, out_len) or None: ship.py
        if has_u8:
            # dictionary bytes ride the row-group buffer (no extra transfer);
            # the row count is bucketed so the slice/gather executables are
            # shared across chunks with different dict sizes
            dict_kp = _bucket(max(self.dict_len, 1))
            dict_itemsize = int(dict_u8.shape[1])
            du8_fn = None
            if ship is not None:
                info = _plan_snappy_ops(
                    stager, [("comp", ship[1], ship[2], None)])
                if info is not None:
                    # value table shipped compressed; the device gathers the
                    # bucketed rows out of the stream's output space.  Rows
                    # past dict_len resolve through padded ops (staged byte
                    # 0) — unlike the plain route's zero reserve they are
                    # garbage, but the deferred range check raises at
                    # finalize before a clamped gather can escape.
                    self._record_ship(
                        ship[0], dict_u8.nbytes, info.shipped,
                        predicted=self._dict_costs.get(ship[0], 0.0),
                        predicted_device=self._dict_dev_costs.get(
                            ship[0], 0.0))
                    dyn.append(np.int64(info.tbase))
                    dkey = ("du8s", dict_kp, dict_itemsize, info.n_ops,
                            info.out_pad, info.iters)
                    _i = info

                    def du8_fn(buf, tb):
                        return _snappy_gather_staged_jit(
                            buf, tb, n_ops=_i.n_ops, out_pad=_i.out_pad,
                            iters=_i.iters,
                            nbytes=dict_kp * dict_itemsize,
                        ).reshape(dict_kp, dict_itemsize)
            if du8_fn is None:
                # zero-filled reserve (NOT a read-extent overlap): clamped
                # out-of-range gathers on the deferred-check path must see
                # zeros, never a neighboring chunk's staged bytes
                dict_base = stager.add(np.ascontiguousarray(dict_u8),
                                       reserve=dict_kp * dict_itemsize)
                dyn.append(np.int64(dict_base))
                dkey = ("du8", dict_kp, dict_itemsize)

                def du8_fn(buf, tb):
                    return _dict_rows_jit(buf, tb, k=dict_kp,
                                          itemsize=dict_itemsize)
        else:
            # ragged (string) dictionaries ride the buffer too — two
            # jnp.asarray transfers per chunk otherwise dominate dict-heavy
            # scans at many-row-group scale (~2.5 ms per transfer)
            roff = np.ascontiguousarray(self.dict_ragged.offsets,
                                        dtype=np.int64)
            roff_n = _bucket_count(len(roff))
            roff_base = stager.add(roff, reserve=roff_n * 8)
            rheap = np.ascontiguousarray(self.dict_ragged.heap)
            rheap_room = _bucket_bytes(max(rheap.nbytes, 1), 64)
            dheap_fn = None
            if ship is not None:
                info = _plan_snappy_ops(
                    stager, [("comp", ship[1], ship[2], None)])
                if info is not None:
                    # heap shipped compressed (offsets stay plain — tiny);
                    # bytes past the real heap resolve through padded ops,
                    # same garbage contract as the plain route's padding
                    self._record_ship(
                        ship[0], rheap.nbytes, info.shipped,
                        predicted=self._dict_costs.get(ship[0], 0.0),
                        predicted_device=self._dict_dev_costs.get(
                            ship[0], 0.0))
                    dyn.extend((np.int64(roff_base), np.int64(info.tbase)))
                    dkey = ("drags", roff_n, rheap_room, info.n_ops,
                            info.out_pad, info.iters)
                    _i = info

                    def dheap_fn(buf, hb):
                        return _snappy_gather_staged_jit(
                            buf, hb, n_ops=_i.n_ops, out_pad=_i.out_pad,
                            iters=_i.iters, nbytes=rheap_room,
                        )
            if dheap_fn is None:
                rheap_base = stager.add(rheap, reserve=rheap_room)
                dyn.extend((np.int64(roff_base), np.int64(rheap_base)))
                dkey = ("drag", roff_n, rheap_room)

                def dheap_fn(buf, hb):
                    return _dynslice_jit(buf, hb, size=rheap_room)

        def fn(buf, *d):
            idx = idx_fn(buf, *d[:idx_arity])
            outs = {"idx": idx}
            if has_u8:
                outs["du8"] = du8_fn(buf, d[idx_arity])
            else:
                # device slices of the staged ragged dictionary (padding
                # past the real offsets is garbage consumers never index:
                # every valid dict index is < dict_len)
                outs["doff"] = _plain_jit(buf, d[idx_arity], dtype="int64",
                                          count=roff_n)
                outs["dheap"] = dheap_fn(buf, d[idx_arity + 1])
            if need_max:
                outs["max"] = _max_jit(idx)
            return outs

        deferred = self._deferred
        dict_len = self.dict_len
        path_name = ".".join(self.leaf.path)
        dict_dtype = self.dict_dtype

        def build(res):
            col = DeviceDictColumn(indices=res["idx"], n_values=prefix,
                                   **common)
            if has_u8:
                col.dict_u8 = res["du8"]
                col.dict_dtype = dict_dtype
            else:
                col.dict_offsets = res["doff"]
                col.dict_heap = res["dheap"]
            if need_max:
                deferred.append((res["max"], dict_len, path_name))
            return col

        return _Plan(("dict", idx_key, dkey, need_max), fn, tuple(dyn), build)

    def _finish_delta(self, common, stager):
        ptype = self.leaf.physical_type
        if ptype not in (Type.INT32, Type.INT64):
            raise ParquetError(f"DELTA_BINARY_PACKED invalid for {ptype!r}")
        bits = 32 if ptype == Type.INT32 else 64
        metas = []
        for p in self.pages:
            m = parse_delta_meta(p.raw[p.value_pos :], bits)
            if m.count < p.defined:
                raise ParquetError(
                    f"delta stream yielded {m.count} of {p.defined} values"
                )
            metas.append(m)
        if any(m.values_per_mini != metas[0].values_per_mini for m in metas):
            # spec-legal but rare: block geometry differs across pages;
            # page-at-a-time fallback rather than a per-page-geometry kernel
            return self._finish_host(common)
        # minis-per-block from the stream's own header varints (the walker's
        # return contract carries only values_per_mini); geometry is constant
        # per stream and already validated by the walk
        from .kernels.delta import _read_uvarint

        mbs = set()
        for p in self.pages:
            bsz, p2 = _read_uvarint(p.raw, p.value_pos)
            mpb, _ = _read_uvarint(p.raw, p2)
            mbs.add(mpb)
        if len(mbs) != 1:
            return self._finish_host(common)
        mb = mbs.pop()
        if any((m.mini_bit_starts & 7).any() for m in metas):
            # miniblocks are byte-aligned by construction; anything else
            # means a walker change this compact path no longer matches
            return self._finish_host(common)
        if (stager.total + sum(len(p.raw) - p.value_pos for p in self.pages)
                > np.iinfo(np.int32).max):
            # block byte starts are staged as i32 (checked before any stager
            # mutation so the fallback leaves no dead bytes)
            return self._finish_host(common)
        bases = self._value_segments(stager)
        # every static shape bucketed; real geometry rides the traced tables.
        # Tables are COMPACT (see _delta_pages_staged_jit): per-BLOCK byte
        # starts + mins, one width byte per mini.
        n_pages = _bucket(len(metas))
        count = _bucket_count(max(m.count for m in metas))
        m_max = _bucket(max(m.mini_bit_starts.shape[0] for m in metas))
        m_max = -(-m_max // mb) * mb  # multiple of mb for the block reshape
        n_blocks = m_max // mb
        bstarts = np.zeros((n_pages, n_blocks), dtype=np.int32)
        widths = np.zeros((n_pages, m_max), dtype=np.uint8)
        bmins = np.zeros((n_pages, n_blocks), dtype=np.uint64)
        firsts = np.zeros(n_pages, dtype=np.int64)
        for i, (m, base) in enumerate(zip(metas, bases)):
            kk = m.mini_bit_starts.shape[0]
            kb = -(-kk // mb)
            bs = (m.mini_bit_starts[::mb] >> 3) + base
            bstarts[i, :kb] = bs
            bstarts[i, kb:] = bs[-1] if kb else 0
            widths[i, :kk] = m.mini_widths
            bmins[i, :kb] = m.mini_min_delta[::mb]
            firsts[i] = m.first_value
        total_real = sum(p.defined for p in self.pages)
        page_starts = np.full(n_pages + 1, total_real, dtype=np.int64)
        page_starts[0] = 0
        np.cumsum([p.defined for p in self.pages],
                  out=page_starts[1 : len(metas) + 1])
        max_width = max(1, int(widths.max(initial=0)))
        max_width = min((max_width + 7) // 8 * 8, 64)  # byte-rounded: 8 shapes
        tbase = _pack_tables(stager, [firsts, bstarts, widths, bmins,
                                      page_starts])
        vpm = metas[0].values_per_mini
        total_b = _bucket_count(total_real)
        return _Plan(
            ("delta", vpm, mb, count, bits, max_width, total_b, n_pages,
             m_max),
            lambda buf, tb_d: _delta_pages_staged_jit(
                buf, tb_d, values_per_mini=vpm, mb=mb, count=count,
                bits=bits, max_width=max_width, total=total_b,
                n_pages=n_pages, m_max=m_max),
            (np.int64(tbase),),
            lambda v: DeviceColumnData(values=v, n_values=total_real,
                                       **common),
        )

    def _finish_mixed_dict_plain(self, common, stager):
        """Fixed-width chunk whose pages mix RLE_DICTIONARY and PLAIN.

        The write-side dictionary-overflow fallback (type_dict.go:101-103)
        always produces a dict-encoded PREFIX of pages followed by a PLAIN
        suffix.  The prefix decodes exactly like _finish_dict (one fused
        expansion + gather over merged run tables); the suffix is one
        contiguous bitcast when the staged segments are exactly the value
        bytes (always true for the overflow shape), else one dispatch per
        page.  Two or three executables per chunk total — per-page dispatch
        diversity costs a dispatch and an executable each.
        """
        name = _PTYPE_TO_NAME[self.leaf.physical_type]
        itemsize = np.dtype(name).itemsize
        kinds = []
        for p in self.pages:
            enc = Encoding(p.encoding)
            kinds.append(Encoding.RLE_DICTIONARY if enc == Encoding.PLAIN_DICTIONARY
                         else enc)
        n_dict = 0
        for k in kinds:
            if k != Encoding.RLE_DICTIONARY:
                break
            n_dict += 1
        if any(k == Encoding.RLE_DICTIONARY for k in kinds[n_dict:]):
            # dict pages after plain pages: not the overflow shape
            return self._finish_host(common)

        bases = self._value_segments(stager)
        dict_pages = self.pages[:n_dict]
        plain_pages = self.pages[n_dict:]

        # --- dict prefix: per-page expansion (widths GROW page to page as
        # the dictionary fills — a merged single-width kernel would corrupt),
        # one concat, ONE gather --------------------------------------------
        dict_calls = []  # (tables..., width, count)
        prefix = 0
        host_max = 0
        for p, base in zip(dict_pages, bases[:n_dict]):
            meta, width, _, host_max = self._parse_dict_index_page(p, host_max)
            dict_calls.append((
                meta.run_ends, meta.run_is_rle, meta.run_values,
                meta.run_bit_starts + int(base) * 8, int(width), p.defined,
            ))
            prefix += p.defined
        self._check_dict_range(prefix, host_max)

        # --- plain suffix: contiguous bitcast when segments are exact -------
        plain_total = sum(p.defined for p in plain_pages)
        _check_plain_sizes(plain_pages, itemsize)
        contiguous = True
        for p, base, nxt in zip(plain_pages, bases[n_dict:],
                                list(bases[n_dict + 1 :]) + [None]):
            seg = len(p.raw) - p.value_pos
            if seg != p.defined * itemsize or (
                nxt is not None and int(nxt) != int(base) + seg
            ):
                contiguous = False
                break
        plain_base = int(bases[n_dict]) if plain_pages else 0
        plain_calls = None
        if not contiguous:
            plain_calls = [
                (int(base), p.defined) for p, base in
                zip(plain_pages, bases[n_dict:])
            ]

        dict_u8 = self.dict_u8
        dict_dtype = self.dict_dtype
        deferred = self._deferred
        dict_len = self.dict_len
        path_name = ".".join(self.leaf.path)

        # dynamic layout: per live dict call (ends, is_rle, values, starts,
        # i64 count) · dict rows array · per plain call i64 base — statics
        # (widths, counts, contiguity) all ride the key
        live_calls = [c for c in dict_calls if c[5]]
        wc = tuple((w, c) for _, _, _, _, w, c in live_calls)
        need_max = bool(prefix) and host_max is None
        plain_desc = (("contig", plain_total) if plain_calls is None
                      else tuple(c for _, c in plain_calls))
        dyn: list = []
        for e, r, v, s, _w, c in live_calls:
            dyn.extend((e, r, v, s, np.int64(c)))
        if prefix:
            dyn.append(np.ascontiguousarray(dict_u8))
        if plain_total:
            if plain_calls is None:
                dyn.append(np.int64(plain_base))
            else:
                dyn.extend(np.int64(b) for b, _ in plain_calls)

        def fn(buf, *d):
            parts = []
            outs = {}
            j = 0
            if prefix:
                idx_parts = []
                for w, c in wc:
                    e, r, v, s, nv = d[j : j + 5]
                    j += 5
                    idx_parts.append(
                        _hybrid_jit(buf, e, r, v, s, nv, width=w, count=c))
                idx = (idx_parts[0] if len(idx_parts) == 1
                       else _concat_jit(idx_parts))
                if need_max:
                    outs["max"] = _max_jit(idx)
                parts.append(_dict_gather_bytes_jit(d[j], idx,
                                                    dtype=dict_dtype))
                j += 1
            if plain_total:
                if plain_calls is None:
                    parts.append(_plain_jit(buf, d[j], dtype=name,
                                            count=plain_total))
                else:
                    for _, c in plain_calls:
                        parts.append(_plain_jit(buf, d[j], dtype=name,
                                                count=c))
                        j += 1
            outs["vals"] = parts[0] if len(parts) == 1 else _concat_jit(parts)
            return outs

        def build(res):
            if need_max:
                deferred.append((res["max"], dict_len, path_name))
            return DeviceColumnData(values=res["vals"], **common)

        return _Plan(
            ("mixed", name, dict_dtype, wc, bool(prefix), plain_desc,
             need_max),
            fn, tuple(dyn), build,
        )

    def _finish_host(self, common):
        """Host decode per page (byte arrays, INT96, BSS, boolean RLE, mixed);
        per-chunk staging, independent of the row-group buffer."""
        from .jax_decode import DeviceChunkDecoder

        helper = DeviceChunkDecoder(self.leaf)
        helper.dict_u8 = (
            jnp.asarray(self.dict_u8) if self.dict_u8 is not None else None
        )
        helper.dict_dtype = self.dict_dtype
        helper.dict_len = self.dict_len
        if self.dict_ragged is not None:
            helper._dict_host_offsets = self.dict_ragged.offsets
            helper.dict_offsets = jnp.asarray(self.dict_ragged.offsets)
            helper.dict_heap = jnp.asarray(self.dict_ragged.heap)
        vals_parts, off_parts, heap_parts = [], [], []
        for p in self.pages:
            v, off, heap = helper._decode_values_device(
                p.encoding, p.raw, p.value_pos, p.defined
            )
            if v is not None:
                vals_parts.append(v)
            else:
                off_parts.append(off)
                heap_parts.append(heap)
        for mx in helper._idx_maxima:
            self._deferred.append((mx, self.dict_len, ".".join(self.leaf.path)))
        out = DeviceColumnData(**common)
        if off_parts:
            if len(off_parts) == 1:
                out.offsets, out.heap = off_parts[0], heap_parts[0]
            else:
                out.offsets, out.heap = _concat_ragged_jit(off_parts, heap_parts)
        elif vals_parts:
            out.values = (
                vals_parts[0] if len(vals_parts) == 1 else _concat_jit(vals_parts)
            )
        else:
            out.values = jnp.asarray(np.zeros(0, dtype=np.int64))
        # transfers already happened above: pass-through plan
        return _Plan(None, None, (), lambda _res: out)


@scoped_x64
def _collect_chunk(
    buf: bytes, codec: int, total_values: int, leaf: SchemaNode,
    deferred_checks: list, validate_crc: bool = False, alloc=None,
    statistics=None, skip_pages=None, context=None, dict_cache=None,
) -> Optional[_ChunkAssembler]:
    """Walk a chunk's pages into an assembler (host phase); None if no data.

    ``skip_pages``: data-page ordinals pruned by page-level predicate
    pushdown — their payloads are never decompressed, parsed, or staged.
    ``context``: decode-site coordinates ({file, column, row_group,
    chunk_offset}) stamped onto every raise (quarantine.error_context),
    plus the failing page's ordinal and byte offset.
    ``dict_cache`` (serve.BoundDictCache duck type): read-through cache of
    DECODED dictionaries keyed by this context's (row_group, column) — a
    hit adopts the decoded value table (and its compressed ship payload)
    without decompressing or parsing the dictionary page again."""
    from .format import CompressionCodec
    from .quarantine import error_context

    ctx = dict(context or {})
    if "column" not in ctx and leaf.path:
        ctx["column"] = ".".join(leaf.path)
    chunk_offset = ctx.pop("chunk_offset", 0) or 0
    asm = _ChunkAssembler(leaf, deferred_checks)
    asm.stats_span = _int_stats_span(statistics, leaf)
    asm.alloc = alloc
    data_ordinal = 0
    # PLAIN SNAPPY chunks (fixed-width AND byte-array) can skip host
    # decompression entirely (device-side expansion — _plan_device_snappy /
    # _plan_snappy_bytes); parse_data_page applies the per-page structural
    # conditions (PLAIN encoding, levels outside the compressed region)
    lazy = (codec == CompressionCodec.SNAPPY
            and (leaf.physical_type in _PTYPE_TO_NAME
                 or leaf.physical_type == Type.BYTE_ARRAY)
            and os.environ.get("TPQ_DEVICE_SNAPPY", "1") != "0")
    if lazy:
        from . import native

        lazy = native.available()
    with error_context(**ctx):
        pages = walk_pages(buf, total_values)
    for ps in pages:
        header = ps.header
        pt = header.type
        if pt == PageType.DICTIONARY_PAGE:
            dk = (ctx.get("row_group"), ctx.get("column"),
                  # CRC tier in the key (chunk_decode._dict_cache_key
                  # contract): a validating request never adopts an
                  # unvalidated decode
                  f"dev:v{1 if validate_crc else 0}")
            if (dict_cache is not None and dk[0] is not None
                    and dk[1] is not None):
                hit = dict_cache.get(dk[0], dk[1], dk[2])
                if hit is not None:
                    asm.adopt_dictionary(hit)
                    continue
            with error_context(offset=chunk_offset + ps.payload_start, **ctx):
                payload = buf[ps.payload_start : ps.payload_end]
                _check_crc(header, payload, validate_crc)
                if alloc is not None:
                    alloc.register(max(header.uncompressed_page_size or 0, 0))
                raw = decompress_block(payload, codec,
                                       header.uncompressed_page_size)
                dh = header.dictionary_page_header
                asm.set_dictionary(raw, dh.encoding, dh.num_values or 0)
            if codec == CompressionCodec.SNAPPY:
                # keep the compressed payload: the ship planner may send the
                # dictionary VALUE TABLE over the link compressed and expand
                # it on device (_preship_dict / _finish_dict)
                asm.dict_comp = (payload,
                                 max(header.uncompressed_page_size or 0, 0))
            if (dict_cache is not None and dk[0] is not None
                    and dk[1] is not None):
                entry = asm.dict_cache_entry()
                if entry is not None:
                    dict_cache.put(dk[0], dk[1], dk[2], entry,
                                   entry["nbytes"])
            continue
        if pt in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
            if skip_pages and data_ordinal in skip_pages:
                asm.pages_pruned += 1
                data_ordinal += 1
                continue
            with error_context(page=data_ordinal,
                               offset=chunk_offset + ps.payload_start, **ctx):
                asm.pages.append(
                    parse_data_page(ps, buf, codec, leaf,
                                    validate_crc=validate_crc,
                                    alloc=alloc, decode_levels=False,
                                    lazy_decompress=lazy)
                )
            data_ordinal += 1
            continue
        # index/unknown pages: skip
    # returned even with zero pages: a fully-pruned chunk still carries its
    # pages_pruned count (callers emit a placeholder column for it)
    return asm


@scoped_x64
def decode_chunk_batched(
    buf: bytes, codec: int, total_values: int, leaf: SchemaNode,
    deferred_checks: list, validate_crc: bool = False,
) -> DeviceColumnData:
    """Decode one chunk with per-chunk fused dispatch (no blocking syncs).

    Dictionary-index range checks land in ``deferred_checks`` as
    (device_max, dict_len, column) tuples — the caller MUST drain them
    (``DeviceFileReader.finalize`` / ``_finalize_many`` semantics) or the
    clamped on-device gather silently tolerates corrupt indices.  Callers
    that decode a single chunk and cannot batch the sync should pass a list
    and check it immediately."""
    asm = _collect_chunk(buf, codec, total_values, leaf, deferred_checks,
                         validate_crc)
    if asm is None or not asm.pages:
        return DeviceColumnData(
            values=jnp.asarray(np.zeros(0, dtype=np.int64)),
            max_def=leaf.max_def, max_rep=leaf.max_rep, num_leaf_slots=0,
        )
    asm.preship()
    stager = _RowGroupStager()
    plan = asm.finish(stager)
    return _run_plans([("c", plan)], stager.stage())["c"]


@dataclass
class ReaderStats:
    """Decode observability counters (SURVEY.md §5.5 — the subsystem the
    reference lacks entirely).  Accumulated per DeviceFileReader; throughput
    properties divide by wall time from first host parse to last dispatch."""

    row_groups: int = 0
    chunks: int = 0
    pages: int = 0
    pages_device_expanded: int = 0  # pages shipped compressed (device snappy)
    pages_pruned: int = 0           # pages skipped by page-level pushdown
    rows: int = 0
    compressed_bytes: int = 0      # chunk bytes read from the file
    staged_bytes: int = 0          # HBM bytes shipped (row-group buffers)
    host_seconds: float = 0.0      # decompress + structure parse + assembly
    # the round-13 `device_seconds` scalar double-counted wall time: the
    # staging worker and the dispatching thread both added their (possibly
    # CONCURRENT) intervals to it, so the sum could exceed the device lane's
    # wall.  Split lanes — on a serial (prefetch=0) run host + stage +
    # dispatch sums back to ~wall (regression-tested); on a pipelined run
    # the lanes overlap and each is honest on its own.
    stage_seconds: float = 0.0     # host->device staging (worker or inline)
    dispatch_seconds: float = 0.0  # issuing fused XLA calls (not queue drain)
    wall_seconds: float = 0.0
    # ship-planner accounting (ship.py): per-route stream counts and byte
    # totals.  `logical` is what plain shipping would have moved; `shipped`
    # what the chosen route actually registered for transfer — the
    # difference IS the link-byte win the round-5 VERDICT prescribed.
    route_streams: dict = field(default_factory=dict)
    route_bytes_logical: dict = field(default_factory=dict)
    route_bytes_shipped: dict = field(default_factory=dict)
    # the cost model's modeled seconds for the routes that RAN, summed per
    # route — obs.StatsRegistry.ship_feedback compares them to the measured
    # link lane (staged bytes / stage seconds) for TPQ_LINK_MBPS calibration
    route_pred_seconds: dict = field(default_factory=dict)
    # the model's DEVICE-lane seconds per route (ship.ShipPlanner
    # .device_costs) — ship_feedback compares them to the measured per-route
    # completion timing (DeviceStats) for TPQ_DEVICE_MBPS calibration
    route_pred_device_seconds: dict = field(default_factory=dict)
    # for FUSED routes: the unfused chain's modeled device seconds
    # (ship.ShipPlanner.unfused_device_costs) — the prediction the doctor's
    # fusion-win verdict compares the measured fused lane against
    route_pred_unfused_device_seconds: dict = field(default_factory=dict)
    # fused routes that degraded to their unfused twin (kernel caps, level
    # lanes, i32 ceilings) — forced-fused on an ineligible stream counts
    # here instead of crashing
    fused_fallbacks: int = 0
    # the link rate the planner ASSUMED (TPQ_LINK_MBPS or the default
    # planning point) — pq_tool doctor prints it next to the measured rate
    # so a recalibration names both sides
    planner_link_mbps: float = 0.0

    def count_route(self, route: str, logical: int, shipped: int,
                    predicted: float = 0.0,
                    predicted_device: float = 0.0,
                    predicted_unfused_device: float = 0.0) -> None:
        self.route_streams[route] = self.route_streams.get(route, 0) + 1
        self.route_bytes_logical[route] = (
            self.route_bytes_logical.get(route, 0) + logical)
        self.route_bytes_shipped[route] = (
            self.route_bytes_shipped.get(route, 0) + shipped)
        self.route_pred_seconds[route] = (
            self.route_pred_seconds.get(route, 0.0) + predicted)
        self.route_pred_device_seconds[route] = (
            self.route_pred_device_seconds.get(route, 0.0) + predicted_device)
        if predicted_unfused_device:
            self.route_pred_unfused_device_seconds[route] = (
                self.route_pred_unfused_device_seconds.get(route, 0.0)
                + predicted_unfused_device)

    @property
    def link_bytes_logical(self) -> int:
        return sum(self.route_bytes_logical.values())

    @property
    def link_bytes_shipped(self) -> int:
        return sum(self.route_bytes_shipped.values())

    @property
    def rows_per_sec(self) -> float:
        return self.rows / self.wall_seconds if self.wall_seconds else 0.0

    @property
    def bytes_per_sec(self) -> float:
        return (self.compressed_bytes / self.wall_seconds
                if self.wall_seconds else 0.0)

    @property
    def pages_per_chunk(self) -> float:
        return self.pages / self.chunks if self.chunks else 0.0

    def as_dict(self) -> dict:
        return {
            "row_groups": self.row_groups, "chunks": self.chunks,
            "pages": self.pages,
            "pages_device_expanded": self.pages_device_expanded,
            "pages_pruned": self.pages_pruned,
            "rows": self.rows,
            "compressed_bytes": self.compressed_bytes,
            "staged_bytes": self.staged_bytes,
            "link_bytes_logical": self.link_bytes_logical,
            "link_bytes_shipped": self.link_bytes_shipped,
            "ship_routes": {
                r: {"streams": self.route_streams[r],
                    "logical": self.route_bytes_logical.get(r, 0),
                    "shipped": self.route_bytes_shipped.get(r, 0),
                    # 9 decimals: a tiny stream's sub-µs prediction must
                    # not round to a 0.0 that ship_feedback would read as
                    # "no prediction" (nulling the error ratio)
                    "predicted_s": round(
                        self.route_pred_seconds.get(r, 0.0), 9),
                    "predicted_device_s": round(
                        self.route_pred_device_seconds.get(r, 0.0), 9),
                    # nonzero only on fused routes: the unfused chain's
                    # modeled device seconds (fusion-win's bar)
                    "predicted_unfused_device_s": round(
                        self.route_pred_unfused_device_seconds.get(r, 0.0),
                        9)}
                for r in sorted(self.route_streams)
            },
            "fused_fallbacks": self.fused_fallbacks,
            "planner_link_mbps": round(self.planner_link_mbps, 1),
            "host_seconds": round(self.host_seconds, 6),
            "stage_seconds": round(self.stage_seconds, 6),
            "dispatch_seconds": round(self.dispatch_seconds, 6),
            "wall_seconds": round(self.wall_seconds, 6),
            "rows_per_sec": round(self.rows_per_sec, 1),
            "bytes_per_sec": round(self.bytes_per_sec, 1),
            "pages_per_chunk": round(self.pages_per_chunk, 3),
        }


# ---------------------------------------------------------------------------
# per-route device timing (the completion-side lane, TPQ_DEVICE_TIMING)
# ---------------------------------------------------------------------------

# plan-key leading token -> kernel family, the granularity the device lane
# is attributed at (doctor names "the gather family of the dict route", not
# an opaque executable hash).  Families follow the decode pipeline's device
# passes: snappy_resolve (op-table source-map resolves), unpack (bitpack /
# delta reconstruction), gather (dictionary index gathers), narrow
# (widen/re-bias of truncated ints), levels (RLE-hybrid level expansion),
# plain (reshape/bitcast-only decodes and host pass-throughs).
_KERNEL_FAMILIES = {
    "snappy": "snappy_resolve", "bytess": "snappy_resolve",
    "narrows": "narrow", "narrow": "narrow",
    "lvlx": "levels", "lvlp": "levels",
    "dict": "gather", "mixed": "gather",
    "hyb": "unpack", "hybvw": "unpack", "delta": "unpack",
    "plain": "plain", "rows": "plain", "bytes": "plain", "bytesh": "plain",
    "bool": "plain",
    # the fused megakernel is its OWN family: one pallas pass running what
    # the plain family does as a staged chain (ISSUE 13) — the doctor
    # names it directly when it dominates, and the fusion-win verdict
    # compares it against the unfused chain's prediction
    "fusedp": "fused",
}


def _kernel_family(key) -> str:
    """Kernel family of a plan key (a ``("col", value_key, ...)`` composite
    classifies by its VALUE plan — levels ride every column)."""
    if isinstance(key, tuple) and key:
        if key[0] == "col":
            return _kernel_family(key[1])
        return _KERNEL_FAMILIES.get(key[0], "plain")
    return "plain"


def _device_timing_enabled() -> bool:
    """Whether the completion-timing lane may run: ``TPQ_DEVICE_TIMING``
    (default on) AND a live jax backend to time against.  A host with no
    usable device (mis-set JAX_PLATFORMS, driverless box) drops the lane
    with ONE warning instead of failing every reader construction — the
    CPU backend counts as a device (block_until_ready is its clock)."""
    from .obs import env_int, warn_env_once

    if env_int("TPQ_DEVICE_TIMING", 1, lo=0) == 0:
        return False
    try:
        ok = bool(jax.devices())
    except Exception:  # noqa: BLE001 — no backend is a disable, not a raise
        ok = False
    if not ok:
        warn_env_once("TPQ_DEVICE_TIMING", "<no jax device>",
                      "disabled (no device clock)")
        return False
    return True


class DeviceStats:
    """Per-route / per-kernel-family device completion timing counters.

    The device half of :class:`~tpu_parquet.pipeline.PipelineStats`: where
    the pipeline's ``dispatch_seconds`` is the HOST wall of issuing async
    XLA calls (microseconds), these are the seconds until the dispatched
    work actually COMPLETED on device (``block_until_ready``), keyed by
    ship route and kernel family — the attribution the plain_int64 gap and
    the fused-megakernel work need (ROADMAP direction 2).

    Per route: ``dispatches`` (fused column dispatches timed),
    ``device_seconds`` (dispatch→completion), ``bytes_in`` (logical output
    bytes the kernels produce — the planner's per-OUTPUT-byte device charge,
    so ``bytes_in / device_seconds`` IS the measured ``TPQ_DEVICE_MBPS``),
    and ``bytes_staged`` (link bytes staged for the route's columns).
    ``h2d`` times the staged row-group buffer transfers the same way.
    Thread-safe: the timing worker accumulates while readers snapshot.

    Caveat — completion semantics: the worker serializes each interval
    against the previous completion (see ``_devtimer_worker``), so the
    per-route seconds partition ONE device timeline — route shares of
    the serialized device lane, never a sum that can exceed it.  Per-op
    exclusive kernel time is ``TPQ_XPROF``'s job, not this lane's.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # route -> [dispatches, s, b_in, b_staged, device_passes]
        self._routes: dict = {}
        self._kernels: dict = {}  # family -> [dispatches, s]
        self._h2d = [0, 0.0, 0]   # transfers, seconds, bytes

    def note_dispatch(self, route: str, family: str, seconds: float,
                      bytes_in: int = 0, bytes_staged: int = 0,
                      passes: int = 1) -> None:
        with self._lock:
            r = self._routes.setdefault(route, [0, 0.0, 0, 0, 0])
            r[0] += 1
            r[1] += seconds
            r[2] += int(bytes_in)
            r[3] += int(bytes_staged)
            r[4] += int(passes)
            k = self._kernels.setdefault(family, [0, 0.0])
            k[0] += 1
            k[1] += seconds

    def note_h2d(self, seconds: float, nbytes: int = 0) -> None:
        with self._lock:
            self._h2d[0] += 1
            self._h2d[1] += seconds
            self._h2d[2] += int(nbytes)

    def progress(self) -> dict:
        """Cumulative counters for the sampler's ``device`` track and the
        watchdog heartbeat (their slope is live device throughput)."""
        with self._lock:
            return {
                "dispatches": sum(r[0] for r in self._routes.values()),
                "device_seconds": round(
                    sum(r[1] for r in self._routes.values()), 6),
                "h2d_transfers": self._h2d[0],
                "h2d_seconds": round(self._h2d[1], 6),
            }

    def as_dict(self) -> dict:
        # 9 decimals on seconds: a tiny run's sub-µs kernel must not round
        # to a 0.0 that ship_feedback would read as "unmeasured" (same
        # contract as ReaderStats.predicted_s)
        with self._lock:
            return {
                "dispatches": sum(r[0] for r in self._routes.values()),
                "device_seconds": round(
                    sum(r[1] for r in self._routes.values()), 9),
                "routes": {
                    # device_passes: STRUCTURAL separate-device-pass count
                    # (see _Plan.stages) — passes == dispatches is the
                    # registry-level proof a route ran fused (no HBM
                    # round trips between stages)
                    route: {"dispatches": r[0],
                            "device_seconds": round(r[1], 9),
                            "bytes_in": r[2], "bytes_staged": r[3],
                            "device_passes": r[4]}
                    for route, r in sorted(self._routes.items())
                },
                "kernels": {
                    fam: {"dispatches": k[0],
                          "device_seconds": round(k[1], 9)}
                    for fam, k in sorted(self._kernels.items())
                },
                "h2d": {"transfers": self._h2d[0],
                        "device_seconds": round(self._h2d[1], 9),
                        "bytes": self._h2d[2]},
            }


class _DeviceTimer:
    """Completion-side timing worker for the device lane.

    Dispatches (and staged transfers) are ASYNC — blocking the dispatching
    thread on ``block_until_ready`` would serialize the very pipeline the
    timing is meant to attribute.  Instead each dispatch hands its output
    arrays (plus route/family/bytes and its dispatch timestamp) to one
    daemon worker (``tpq-devtimer``, covered by bench.py's zero-leaked-
    daemon-threads gate) that blocks until the work completes and folds
    ``t_complete - t_dispatch`` into :class:`DeviceStats` — and, when a
    tracer is listening, emits a ``device.<route>`` span so ``pq_tool
    trace`` prints device lanes in the same p50/p95 table as the host
    stages.

    Disabled (``TPQ_DEVICE_TIMING=0`` or no backend): ``submit`` is one
    attribute check, guarded <3% by the tier-1 overhead test.  The worker
    starts lazily on first submit and ``stop()`` joins it (idempotent;
    submits after stop are dropped, so a closed reader can never respawn
    the thread).
    """

    def __init__(self, stats: DeviceStats, tracer=None,
                 enabled: "bool | None" = None):
        self.stats = stats
        self.tracer = tracer
        self.enabled = (_device_timing_enabled() if enabled is None
                        else bool(enabled))
        self._lock = threading.Lock()
        self._q = None
        self._thread = None
        self._closed = False

    def submit(self, kind: str, route: str, family: str, arrays, t0: float,
               bytes_in: int = 0, bytes_staged: int = 0,
               passes: int = 1) -> None:
        if not self.enabled:
            return
        q = self._q
        if q is None:
            q = self._start()
            if q is None:
                return  # closed
        q.put((kind, route, family, arrays, t0, bytes_in, bytes_staged,
               passes))

    def _start(self):
        import queue
        import weakref

        with self._lock:
            if self._closed:
                return None
            if self._q is None:
                self._q = queue.Queue()
                # the worker references only (queue, stats, tracer) — never
                # this timer — so an abandoned reader (no close()) lets the
                # timer become unreachable and the finalizer below delivers
                # the shutdown sentinel: no thread outlives its reader's
                # collection, even without the explicit stop()
                self._thread = threading.Thread(
                    target=_devtimer_worker,
                    args=(self._q, self.stats, self.tracer),
                    name="tpq-devtimer", daemon=True)
                self._thread.start()
                weakref.finalize(self, self._q.put, None)
            return self._q

    def drain(self, timeout: float = 2.0) -> None:
        """Wait (bounded) until every submitted dispatch has been timed —
        a mid-session stats read must not observe 1 of a group's 3
        dispatches just because the worker is still blocking on the other
        two.  Bounded: a wedged device must not also wedge a flight dump
        whose registry provider calls this."""
        import time as _time

        q = self._q
        if q is None or not self.enabled:
            return
        deadline = _time.monotonic() + timeout
        while q.unfinished_tasks and _time.monotonic() < deadline:
            _time.sleep(0.002)

    def stop(self) -> None:
        """Drain and join the worker (idempotent, thread-leak-safe: every
        already-submitted dispatch is still timed before the join)."""
        with self._lock:
            self._closed = True
            q, t = self._q, self._thread
            self._q = self._thread = None
        if t is None:
            return
        q.put(None)
        t.join(timeout=10.0)


def _devtimer_worker(q, stats: DeviceStats, tracer) -> None:
    """The completion worker's loop (module-level on purpose: it must not
    reference the :class:`_DeviceTimer`, or the timer could never be
    collected and its shutdown finalizer could never fire).

    Intervals are SERIALIZED against the previous completion: dispatches
    ride one async device queue, so an interval anchored at its own
    dispatch time would also contain every earlier dispatch's device time
    and the per-route sums would overcount the device wall several-fold
    (K columns back-to-back → ~K/2x).  Anchoring each entry at
    ``max(own dispatch, previous completion)`` partitions the busy lane:
    the sums are route shares of one serialized device timeline, directly
    comparable to the wall-clock host lanes the doctor weighs them
    against."""
    import time as _time

    prev_done = 0.0
    while True:
        item = q.get()
        if item is None:
            return
        try:
            kind, route, family, arrays, t0, b_in, b_staged, passes = item
            try:
                jax.block_until_ready(arrays)
            except Exception:  # noqa: BLE001 — a failed dispatch
                continue       # reports through the consumer
            t1 = _time.perf_counter()
            start = max(t0, prev_done)
            prev_done = t1
            dt = max(t1 - start, 0.0)
            if kind == "h2d":
                stats.note_h2d(dt, b_staged)
                name = "device.h2d"
            else:
                stats.note_dispatch(route, family, dt, b_in, b_staged,
                                    passes)
                name = f"device.{route}"
            if tracer is not None and tracer.active:
                tracer.complete(name, start, t1, kernel=family,
                                bytes=int(b_staged or b_in))
        finally:
            q.task_done()


# ---------------------------------------------------------------------------
# aligned device profiles (TPQ_XPROF): one bounded-window jax.profiler
# capture per process whose TraceAnnotations carry the SAME names as the
# span tracer's stages, so the host Perfetto artifact and the XLA device
# timeline line up one-to-one
# ---------------------------------------------------------------------------

_XPROF_LOCK = threading.Lock()
_XPROF_DONE = False      # one capture per process: xprof dirs are heavy
_XPROF_ACTIVE = False    # cheap hot-path gate for TraceAnnotations


def _xprof_active() -> bool:
    return _XPROF_ACTIVE


def _xprof_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` matching a span-tracer stage name
    while an xprof window is capturing; a no-op context otherwise (the
    annotation objects are only built inside a live capture)."""
    if not _XPROF_ACTIVE:
        return _noop_ctx()
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — profiling never takes the run down
        return _noop_ctx()


class _XprofWindow:
    """Bounded-window device profile capture (``TPQ_XPROF=<dir>``).

    Starts a ``jax.profiler`` trace at scan start and stops it after
    ``TPQ_XPROF_S`` seconds (default 10; checked at row-group granularity)
    or at scan end, whichever comes first — an unbounded xprof over a 1B-row
    scan is gigabytes, a window is what the alignment needs.  One capture
    per process; every later scan is a no-op.  All profiler calls are
    guarded: a backend without profiler support degrades silently.
    """

    def __init__(self):
        from .obs import env_float

        self.dir = os.environ.get("TPQ_XPROF", "")
        self.window_s = env_float("TPQ_XPROF_S", 10.0, lo=0.1)
        self._t0 = None
        self._started = False

    def start(self) -> None:
        global _XPROF_DONE, _XPROF_ACTIVE
        if not self.dir:
            return
        with _XPROF_LOCK:
            if _XPROF_DONE:
                return
            _XPROF_DONE = True
            try:
                import time as _time

                jax.profiler.start_trace(self.dir)
                self._t0 = _time.perf_counter()
                self._started = True
                _XPROF_ACTIVE = True
            except Exception:  # noqa: BLE001
                self._started = False

    def tick(self) -> None:
        """Row-group boundary check: close the window once it has run
        ``window_s`` (the profiler flushes its own buffers on stop)."""
        import time as _time

        if self._started and _time.perf_counter() - self._t0 >= self.window_s:
            self.stop()

    def stop(self) -> None:
        global _XPROF_ACTIVE
        if not self._started:
            return
        self._started = False
        with _XPROF_LOCK:
            _XPROF_ACTIVE = False
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass


class DeviceFileReader:
    """Columnar file reader decoding straight to device arrays.

    The device twin of reader.FileReader: same options (projection, CRC), row
    groups as the work unit, nothing blocks until ``finalize()`` (called by
    ``read_row_group``; pass ``finalize=False`` to pipeline several row groups
    and call it once).

    With ``row_filter`` set, pruning is two-level: row groups whose chunk
    stats prove no match are skipped whole (prune_row_groups), and within
    surviving FLAT row groups, page-header Statistics drop maximal
    provably-false row runs aligned to whole-page boundaries of every
    selected column (prune_pages — skipped pages are never decompressed,
    staged, or decoded; ReaderStats.pages_pruned counts them).  Yielded rows
    are always a SUPERSET of matching rows, identical across columns;
    columns with differing page grids share no interior edges, in which
    case the pruner soundly declines rather than misalign.

    Zero-decode-work policy: a PLAIN fixed-width chunk has no device compute
    — decoding it here is a pure host→HBM transfer, so against a host decode
    + async upload pipeline the information-theoretic ceiling on a
    transfer-bound link is ~1× (both paths move the same bytes; encoded
    columns — dict/RLE/delta — are where the device path wins by shipping
    FEWER bytes and expanding on device).  ``iter_row_groups`` reaches that
    ceiling by streaming staged strips during host decompress (see
    _RowGroupStager) rather than serializing parse→transfer; callers that
    want host-resident arrays for such columns should read them with the
    host FileReader (project them out of the device reader) and skip the
    transfer entirely.
    """

    def __init__(self, source, columns=None, validate_crc=None,
                 profile_dir: "str | None" = None, max_memory: int = 0,
                 row_filter=None, prefetch: int = 0, trace=None,
                 sample_ms=None, hang_s=None, hang_policy=None,
                 store=None, on_data_error=None, quarantine=None,
                 metadata=None, plan=None, dict_cache=None,
                 result_cache=None, cancel=None):
        from .obs import (Sampler, Watchdog, register_flight_registry,
                          resolve_hang_s, resolve_sample_ms, resolve_tracer)
        from .pipeline import PipelineStats
        from .quarantine import resolve_validate
        from .reader import FileReader

        _enable_compile_cache()

        # span tracer (obs.py): None = the TPQ_TRACE process tracer (a
        # disabled no-op without the env); a path = per-reader tracer whose
        # trace file (+ embedded registry) is written at close()
        self._tracer, self._owns_tracer = resolve_tracer(trace)
        validate_crc = resolve_validate(validate_crc)
        self._host = FileReader(source, columns=columns,
                                validate_crc=validate_crc,
                                max_memory=max_memory,
                                row_filter=row_filter,
                                trace=self._tracer, store=store,
                                on_data_error=on_data_error,
                                quarantine=quarantine,
                                metadata=metadata, plan=plan,
                                dict_cache=dict_cache, cancel=cancel)
        # the plan IR (scanplan.py): the footer slice + pruning verdicts +
        # ship-route memo this scan consumes.  A caller-supplied plan (the
        # serve.ScanService cache) is REPLAYED — group pruning is adopted
        # from it (via the host reader), page-pruning header walks are
        # skipped where memoized, and preship starts from the memoized
        # route.  Without one, the reader builds its own, so plan
        # construction always lives in scanplan.py.
        self._plan = self._host._plan
        # decoded-dictionary read-through cache (serve.BoundDictCache duck
        # type: get(rg, column, kind) / put(rg, column, kind, value, nbytes))
        self._dict_cache = dict_cache
        # decoded device-result cache (serve.BoundResultCache bound to the
        # DEVICE decode signature — deliberately NOT forwarded to the host
        # FileReader above: host ColumnData and device arrays are
        # different decode shapes and must never share entries).  An
        # adapter whose signature doesn't match THIS reader's shape, CRC
        # tier, or predicate fingerprint is dropped, not adopted — a
        # validate_crc=True request must never adopt an unvalidated
        # decode, and page-pruned output is only shared under the exact
        # same fingerprint.  A row group whose every selected column is
        # cached skips IO, staging, and every device kernel; misses
        # populate at finalize — the one point that proves the deferred
        # validity checks passed.
        if result_cache is not None:
            from .scanplan import predicate_fingerprint

            sig = getattr(result_cache, "sig", None) or ()
            want = ("dev", "v1" if validate_crc else "v0",
                    predicate_fingerprint(self._host.row_filter))
            if tuple(sig[:3]) != want:
                result_cache = None
        self._result_cache = result_cache
        # rc-pending ledger: id(out dict) -> [rg index, out, dispatched,
        # nbytes]; flushed to the cache by _flush_result_cache (via
        # _finalize_many).  BOUNDED by the cache tier's capacity: a
        # deferred-finalize multi-file scan must not pin every group's
        # decoded output until the end — beyond the bound the OLDEST
        # pending group is simply dropped (a forgone cache fill, never a
        # correctness or memory cost).
        self._rc_pending: dict = {}
        self._rc_pending_bytes = 0
        # data-error containment engine, SHARED with the host half so the
        # budget and quarantine ledger span both decode paths
        self.quarantine = self._host.quarantine
        # the IO backend all chunk bytes enter through (iostore.py) —
        # shared with the host reader so both paths see one retry budget
        self._store = self._host._store
        # chunk-granular host prefetch depth (IO + CRC + decompress + parse
        # of upcoming chunks on a bounded pool, spanning row-group
        # boundaries); 0 = the sequential host phase
        self._prefetch = int(prefetch)
        self._pipe_stats = PipelineStats(prefetch=self._prefetch,
                                         budget_bytes=int(max_memory),
                                         tracer=self._tracer)
        self.metadata = self._host.metadata
        self.schema = self._host.schema
        self.validate_crc = validate_crc
        self.profile_dir = profile_dir  # JAX profiler trace dir (SURVEY §5.1)
        # HBM/host staging budget (SURVEY §5.3): ONE tracker shared with the
        # host FileReader, registered against each page's REAL decompressed
        # size (chunk-level metadata totals are attacker-controlled), so a
        # decompression bomb raises instead of exhausting memory
        self.alloc = self._host.alloc
        self._deferred: list = []
        self._stats = ReaderStats()
        self._stats_lock = __import__("threading").Lock()
        self._t0: float | None = None
        # per-route device completion timing (TPQ_DEVICE_TIMING, default
        # on): one lazy daemon worker times each staged dispatch to
        # block_until_ready, keyed by ship route and kernel family
        self._device_stats = DeviceStats()
        self._device_timer = _DeviceTimer(self._device_stats, self._tracer)
        # HBM residency ledger: staged buffers register at staging
        # (`_device_staged_pending`), move to `_device_outstanding` at
        # dispatch, and release at finalize — the one point that proves
        # every kernel reading the DISPATCHED buffers has completed (the
        # pipelined path stages group N before group N-1 finalizes, so a
        # single counter would release N's live buffer early)
        self._device_staged_pending = 0
        self._device_outstanding = 0
        # bounded-window aligned device profile (TPQ_XPROF)
        self._xprof = _XprofWindow()
        # link-byte ship planner (ship.py): per-reader so env overrides
        # (TPQ_FORCE_ROUTE, TPQ_LINK_MBPS) bind at open time
        self._ship_planner = ShipPlanner()
        self._stats.planner_link_mbps = self._ship_planner.link_mbps
        # live counter sampler (obs.Sampler, TPQ_SAMPLE_MS / sample_ms=):
        # throughput + backpressure curves on the trace; inert (no thread)
        # unless the tracer is enabled AND an interval is set
        # track_id ties each reader's curves to its pipeline's `pipe=` wall
        # counter — scan_files opens several readers on ONE shared tracer,
        # and same-named id-less tracks would interleave into one sawtooth
        self._sampler = Sampler(self._tracer, resolve_sample_ms(sample_ms),
                                track_id=self._pipe_stats._obs_id)
        # the chunk feed's in-flight budget, once a scan creates one — the
        # sampler's budget_waiters track and the watchdog's abort hook both
        # late-bind through it (_chunk_feed sets it)
        self._live_budget = None
        if self._sampler.enabled:
            self._sampler.add_source("reader_progress", self._sample_progress)
            # late-bound like the watchdog lanes below: iter_row_groups
            # replaces _pipe_stats per scan and the sampled track must
            # follow the live object, not the constructor-time one
            self._sampler.add_source("pipeline_lanes",
                                     lambda: self._pipe_stats.sample())
            self._sampler.add_source("alloc_bytes", self._sample_alloc)
            self._sampler.add_source("budget_waiters", self._sample_budget)
            if self._store.stats is not None:
                # retry/backoff curves next to the lanes they stall
                self._sampler.add_source("io_retries",
                                         self._store.stats.progress)
            # quarantined-unit accounting as a live curve: a corruption
            # burst is visible next to the lane it degraded
            self._sampler.add_source("data_errors", self.quarantine.progress)
            if self._result_cache is not None:
                # result-cache hit/miss/eviction flows as a live curve
                # next to the decode lanes they spare
                self._sampler.add_source("result_cache",
                                         self._result_cache.cache.progress)
            if self._device_timer.enabled:
                # the device lane as a curve (slope = live device
                # throughput); on hosts where the timing lane dropped
                # (no backend) the track simply never registers
                self._sampler.add_source("device",
                                         self._device_stats.progress)
            self._sampler.start()
        # hang watchdog (obs.Watchdog, TPQ_HANG_S / hang_s=): fires a
        # flight dump (and, policy "raise", aborts the chunk feed's budget
        # so the submitter raises HangError) when no lane below advances.
        # Lambdas late-bind self._pipe_stats: iter_row_groups replaces it
        # per scan and the heartbeats must follow the live object.
        self._watchdog = Watchdog(resolve_hang_s(hang_s), policy=hang_policy)
        if self._watchdog.enabled:
            self._watchdog.watch("pipeline",
                                 lambda: self._pipe_stats.sample())
            self._watchdog.watch("reader", self._sample_progress)
            if self._store.stats is not None:
                # store heartbeat: the counters FREEZE while a fetch is
                # stalled (a retrying store keeps advancing) — so a
                # network stall fires the dog and the flight dump names
                # the in-flight range (pq_tool autopsy: network-stall)
                self._watchdog.watch("iostore", self._store.stats.progress)
            if getattr(self._store, "supports_async", False):
                # async-routed stores get an engine heartbeat lane too:
                # submissions/completions freeze when every in-flight
                # fetch is stuck on the loop (the dog still only fires
                # when ALL lanes freeze)
                from .iostore_async import engine_for_store

                eng = engine_for_store(self._store)
                if eng is not None:
                    self._watchdog.watch("fetch_engine", eng.stats.progress)
            # raise-policy exit from a stalled fetch: poisoning the store
            # wakes the worker pinned inside the transport, so the HangError
            # (not a belated transport error) reaches the consumer
            self._watchdog.add_abort_hook(self._store.abort)
            # idle consumer gate until the first scan replaces it: both
            # counter lanes above are frozen at 0 while the reader sits
            # un-iterated, and a reader built long before its first
            # iter_row_groups must not read as a hang
            self._watchdog.watch_consumer()
            self._watchdog.start()
        # a wedged process's dump should embed the same registry tree a
        # clean close would have written (weakly held — see obs)
        register_flight_registry(self, "obs_registry")

    def _sample_progress(self) -> dict:
        st = self._stats
        return {"rows": st.rows, "chunks": st.chunks,
                "staged_bytes": st.staged_bytes,
                "compressed_bytes": st.compressed_bytes}

    def _sample_alloc(self) -> dict:
        in_use, peak = self.alloc.snapshot()
        dev_in_use, dev_peak = self.alloc.device_snapshot()
        return {"in_use": in_use, "peak": peak,
                "device_in_use": dev_in_use, "device_peak": dev_peak}

    def _sample_budget(self) -> dict:
        b = self._live_budget
        return b.snapshot() if b is not None else {}

    def close(self):
        self._watchdog.stop()  # before the sampler: no dump mid-teardown
        # before the sampler's final tick and the trace write: every
        # in-flight dispatch must land in the device section first
        self._device_timer.stop()
        self._xprof.stop()
        # deferred-finalize scans (scan_files) release residency here
        self._release_device_outstanding(all_bytes=True)
        self._sampler.stop()  # before the write: the final tick must land
        self._host.close()
        if self._owns_tracer:
            self._tracer.write(registry=self.obs_registry())
            self._owns_tracer = False  # idempotent: scan_files double-closes

    def obs_registry(self):
        """This reader's unified metrics tree (obs.StatsRegistry): decode
        counters + per-route ship decisions with the planner's predictions,
        the pipeline's per-stage histograms, and the alloc high-water mark."""
        from .obs import StatsRegistry

        reg = StatsRegistry()
        reg.add_reader(self._stats)
        reg.add_pipeline(self._pipe_stats)
        reg.note_alloc_peak(self.alloc)
        if self._device_timer.enabled:
            # the versioned `device` section (golden-keyed like io/
            # data_errors); absent entirely when the timing lane dropped,
            # so consumers see "n/a", never zeros masquerading as
            # measures.  Drain first: a mid-session read must not miss
            # dispatches still queued behind the completion worker.
            self._device_timer.drain()
            reg.add_device(self._device_stats)
        if self._store.stats is not None:
            reg.add_io(self._store.stats)
        if len(self.quarantine.log) or self.quarantine.units_skipped:
            reg.add_data_errors(self.quarantine)
        return reg

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def num_row_groups(self) -> int:
        return self._host.num_row_groups

    @staticmethod
    def _walk_headers_file(f, offset: int, size: int, num_values: int):
        """Page headers of a chunk read via seeks (moved to
        scanplan.walk_header_pages — kept as a delegate for callers/tests
        addressing the reader)."""
        from .scanplan import walk_header_pages

        return walk_header_pages(f, offset, size, num_values)

    def _plan_page_pruning(self, rg, leaves, f=None, index=None):
        """Page-level predicate pushdown planning, via the plan IR
        (scanplan.plan_page_pruning) with a per-row-group memo: a replayed
        ScanPlan (serve's PlanCache, or a second scan over one reader)
        skips the header walks entirely and adopts the recorded skip sets.
        The memoized replay returns no filter-chunk buffers — the decode
        loop then reads those chunks itself, exactly as without a filter.
        """
        pred = self._host.row_filter
        if pred is None:
            return None, 0, {}
        from . import scanplan as _sp

        plan = self._plan
        memo_ok = (plan is not None and index is not None
                   and plan.filter_fp is not None
                   and plan.filter_fp == _sp.predicate_fingerprint(pred))
        if memo_ok:
            hint = plan.pruning_hint(index)
            if hint is not None:
                skip, rows_dropped = hint
                return skip, rows_dropped, {}
        if f is None:  # the chunk feed passes a thread-safe pread view
            f = self._host._sr.as_file()  # store-backed, like every read
        skip, rows_dropped, bufs = _sp.plan_page_pruning(
            rg, leaves, self.schema, pred, f)
        if memo_ok:
            plan.note_pruning(index, skip, rows_dropped)
        return skip, rows_dropped, bufs

    @scoped_x64
    def _prepare_row_group(self, index: int, executor=None, collected=None):
        """Host phase: decompress + parse every chunk of the row group,
        registering all byte regions with ONE stager.

        With ``executor`` (the iter_row_groups staging worker) the stager
        streams completed 16 MiB strips to the device while this thread is
        still decompressing later chunks — see _RowGroupStager.

        With ``collected`` (the chunk feed's output — IO + CRC + decompress
        + structure parse already done on the prefetch pool, possibly while
        an EARLIER row group was dispatching) the host phase here collapses
        to stager registration and plan construction.

        No device calls on the common paths (plain/bool/bytes/dict/delta);
        the _finish_host fallback (mixed encodings, FLBA, INT96, delta byte
        arrays) still stages per chunk eagerly here and is therefore NOT
        overlapped by the iter_row_groups pipeline.
        """
        rg = self.metadata.row_groups[index]
        if self._result_cache is not None:
            fed_cached = collected is not None and collected.get("cached")
            if fed_cached or collected is None:
                hit = self._cached_group(index)
                if hit is not None:
                    # warm group: no IO, no staging, no device dispatch —
                    # _dispatch_row_group sees zero plans and passes
                    # straight through
                    return hit, [], None
                if fed_cached:
                    # evicted between the feed's probe and here: decode
                    # fresh on the sequential path (the feed read nothing)
                    collected = None
        import time as _time

        t0 = _time.perf_counter()
        if self._t0 is None:
            self._t0 = t0
        leaves = {l.path: l for l in self.schema.selected_leaves()}
        out: dict[str, DeviceColumnData] = {}
        # store-backed view: the sequential path's bytes enter through the
        # same fault-tolerant backend as the prefetch pool's
        f = self._host._sr.as_file()
        self.alloc.reset()
        if collected is None:
            skip_pages, rows_dropped, planned_bufs = self._plan_page_pruning(
                rg, leaves, index=index)
        else:
            skip_pages, planned_bufs = None, {}
            rows_dropped = collected["rows_dropped"]
        stager = _RowGroupStager(executor)
        plans: list[tuple[str, object]] = []
        for path, leaf, chunk, md, offset in row_group_chunks(rg, leaves):
            if collected is not None:
                entry = collected["chunks"].get(path)
                if entry is None:
                    # selection changed between feed and prepare (both run
                    # in the consumer thread, so this is a caller bug)
                    raise ParquetError(
                        f"prefetched row group {index} missing chunk "
                        f"{'.'.join(path)}"
                    )
                md, asm = entry
                if isinstance(asm, _FailedChunk):
                    # a quarantined chunk from the prefetch feed: re-raise
                    # its (already annotated + recorded) error here so the
                    # consumer-side containment in _scan_pipeline handles
                    # the sequential and pipelined paths identically
                    raise asm.exc
                self._stats.chunks += 1
                self._stats.compressed_bytes += md.total_compressed_size
                self.alloc.register(md.total_compressed_size)
            else:
                ctx = {"file": self._host._source_name, "row_group": index,
                       "column": ".".join(path), "chunk_offset": offset}
                buf = planned_bufs.get(path)
                if buf is None:
                    f.seek(offset)
                    buf = f.read(md.total_compressed_size)
                require_full(buf, offset, md.total_compressed_size,
                             context=f"column {'.'.join(path)}")
                self._stats.chunks += 1
                self._stats.compressed_bytes += md.total_compressed_size
                self.alloc.register(md.total_compressed_size)
                asm = _collect_chunk(
                    buf, md.codec, md.num_values, leaf, self._deferred,
                    validate_crc=self.validate_crc, alloc=self.alloc,
                    statistics=md.statistics,
                    skip_pages=(skip_pages or {}).get(path),
                    context=ctx, dict_cache=self._dict_cache,
                )
                if asm is not None:
                    # replay the plan IR's memoized route (scanplan.py):
                    # preship starts from the recorded choice instead of
                    # re-ranking — and, on a plain memo, skips the failed
                    # narrow/recompress probes a first pass already paid
                    asm.preship(self._ship_planner, self._pipe_stats,
                                route_hint=(
                                    self._plan.route_hint(index,
                                                          ".".join(path))
                                    if self._plan is not None else None))
            if asm is not None:
                self._stats.pages += len(asm.pages)
                self._stats.pages_pruned += asm.pages_pruned
            name = ".".join(path)
            if asm is None or not asm.pages:
                # empty chunk OR fully pruned: placeholder column (still
                # count the pruned pages — a fully-pruned chunk is the
                # pushdown's best case, not a zero)
                out[name] = DeviceColumnData(
                    values=jnp.asarray(np.zeros(0, dtype=np.int64)),
                    max_def=leaf.max_def, max_rep=leaf.max_rep,
                    num_leaf_slots=0,
                )
                continue
            plan = asm.finish(stager)
            plans.append((name, plan))
            self._stats.pages_device_expanded += asm.pages_kept_compressed
            tr = self._pipe_stats.tracer
            self._stats.fused_fallbacks += asm.fused_fallbacks
            logical_sum = shipped_sum = 0
            best_route, best_bytes = None, -1
            for (route, logical, shipped, predicted, predicted_dev,
                 predicted_unfused_dev) in asm.ship_records:
                self._stats.count_route(route, logical, shipped, predicted,
                                        predicted_dev,
                                        predicted_unfused_dev)
                logical_sum += logical
                shipped_sum += shipped
                if shipped > best_bytes:
                    best_route, best_bytes = route, shipped
                if tr is not None and tr.active:
                    # one instant per shipped stream: pq_tool trace folds
                    # these into the per-route predicted-vs-measured table
                    tr.instant("ship", route=route, column=name,
                               logical=logical, shipped=shipped,
                               predicted_s=round(predicted, 9),
                               predicted_device_s=round(predicted_dev, 9))
            # device-timing attribution: the column's dispatch is timed
            # under its dominant (most-shipped-bytes) ship route
            plan.route = best_route or ROUTE_PLAIN
            plan.bytes_in = logical_sum
            plan.bytes_staged = shipped_sum
            if self._plan is not None:
                # memoize the decision into the plan IR: a replay (this
                # reader's next scan, or the serve cache's next request
                # over the same plan) starts preship from it
                self._plan.note_route(index, name, plan.route,
                                      _kernel_family(plan.key))
        # every selected leaf must have a chunk in the row group (host
        # FileReader parity — reader.py read_row_group's missing check)
        seen = set(out) | {name for name, _ in plans}
        missing = {".".join(p) for p in leaves} - seen
        if missing:
            raise ParquetError(
                f"row group {index} missing columns {sorted(missing)}"
            )
        self._stats.row_groups += 1
        self._stats.rows += (rg.num_rows or 0) - rows_dropped
        self._stats.staged_bytes += stager.total
        now = _time.perf_counter()
        self._stats.host_seconds += now - t0
        self._stats.wall_seconds = now - self._t0
        tr = self._pipe_stats.tracer
        if tr is not None and tr.active:
            tr.complete("prepare", t0, now, rg=index, bytes=stager.total)
        if self._result_cache is not None:
            # miss path: remember this group's output dict (dispatch fills
            # it in place); _flush_result_cache publishes it only after
            # finalize proves the deferred checks passed AND the group was
            # actually dispatched (a prepared-but-never-dispatched dict
            # still holds placeholders, not results)
            self._rc_pending[id(out)] = [index, out, False, 0]
        return out, plans, stager

    def _cached_group(self, index: int) -> "dict | None":
        """All-or-nothing decoded-result probe for row group ``index``:
        every selected column cached under this reader's decode signature,
        or None.  A hit counts into the reader's row/group accounting
        (rows from the widest column's leaf-slot count — accounting only)
        so throughput math keeps describing what was SERVED."""
        rc = self._result_cache
        names = [".".join(l.path) for l in self.schema.selected_leaves()]
        if not names:
            return None
        got = rc.lookup_group(index, names)
        if got is None:
            return None
        import time as _time

        now = _time.perf_counter()
        if self._t0 is None:
            self._t0 = now
        self._stats.row_groups += 1
        self._stats.rows += max(
            (int(getattr(c, "num_leaf_slots", 0) or 0) for c in got.values()),
            default=0)
        self._stats.wall_seconds = now - self._t0
        tr = self._pipe_stats.tracer
        if tr is not None and tr.active:
            tr.instant("result_cache_hit", rg=index, columns=len(got))
        return dict(got)

    def _flush_result_cache(self) -> None:
        """Publish dispatched groups' decoded columns to the result cache.
        Called after the deferred validity checks pass (finalize /
        _finalize_many) — never before: a value that would fail
        finalization must never be servable."""
        rc = self._result_cache
        if rc is None or not self._rc_pending:
            return
        pending, self._rc_pending = self._rc_pending, {}
        self._rc_pending_bytes = 0
        from .serve.result_cache import device_column_nbytes

        for index, out, dispatched, _nbytes in pending.values():
            if not dispatched:
                continue
            for name, col in out.items():
                rc.put(index, name, col, device_column_nbytes(col))

    def _note_staged(self, stager, buf_dev, t0: float) -> None:
        """One staged row-group buffer just shipped: account its HBM
        residency and hand it to the completion timer as an ``h2d``
        transfer.  ``t0`` must be the POST-stage timestamp — ``stage()``
        is host-blocking, so an interval anchored before it would contain
        the whole host staging wall and the ``h2d`` lane would
        structurally dominate the link lane it is meant to sit next to.
        The bytes land in ``_device_staged_pending`` (not yet dispatched)
        and move to ``_device_outstanding`` at dispatch — finalize proves
        completion only for DISPATCHED groups, and the pipelined path
        stages group N before group N-1 finalizes."""
        n = int(stager.total)
        if n:
            self.alloc.register_device(n)
            with self._stats_lock:
                self._device_staged_pending += n
        self._device_timer.submit("h2d", "h2d", "h2d", buf_dev, t0,
                                  bytes_staged=n)

    def _note_dispatched(self, stager) -> None:
        """The group's staged bytes are now consumed by in-flight kernels:
        eligible for release at the next finalize."""
        n = int(stager.total)
        if n:
            with self._stats_lock:
                self._device_staged_pending -= n
                self._device_outstanding += n

    def _release_device_outstanding(self, all_bytes: bool = False) -> None:
        """Release the HBM ledger for groups whose kernels finalize just
        proved complete; ``all_bytes`` (close) also drops still-pending
        staged buffers — the scan is over either way."""
        with self._stats_lock:
            n, self._device_outstanding = self._device_outstanding, 0
            if all_bytes:
                n += self._device_staged_pending
                self._device_staged_pending = 0
        if n:
            self.alloc.release_device(n)

    @scoped_x64
    def _dispatch_row_group(self, prepared, buf_dev=None):
        import time as _time

        out, plans, stager = prepared
        # the request trace rides the reader's cancel token (the serve tier
        # sets it); the device pass is one span per dispatched group
        _cancel = getattr(self._host, "_cancel", None)
        _rtrace = getattr(_cancel, "trace", None) if _cancel is not None \
            else None
        if _rtrace is None:
            from .obs import current_request_trace

            _rtrace = current_request_trace()
        if plans:
            _tr0 = _time.perf_counter() if _rtrace is not None else 0.0
            if buf_dev is None:
                t0 = _time.perf_counter()
                with self._pipe_stats.timed("stage", bytes=stager.total), \
                        _xprof_annotation("stage"):
                    buf_dev = stager.stage()
                t_staged = _time.perf_counter()
                with self._stats_lock:
                    self._stats.stage_seconds += t_staged - t0
                self._note_staged(stager, buf_dev, t_staged)
            t1 = _time.perf_counter()
            with self._pipe_stats.timed("dispatch"), \
                    _xprof_annotation("dispatch"):
                out.update(_run_plans(plans, buf_dev, self._device_timer))
            with self._stats_lock:
                self._stats.dispatch_seconds += _time.perf_counter() - t1
            self._note_dispatched(stager)
            if _rtrace is not None:
                _rtrace.add_timed("device", _tr0, _time.perf_counter(),
                                  plans=len(plans),
                                  staged_bytes=int(stager.total))
        if self._result_cache is not None:
            ent = self._rc_pending.get(id(out))
            if ent is not None:
                # the group's columns are now real decoded results (or it
                # had no device work at all) — eligible to publish once
                # finalize proves the deferred checks.  Pending residency
                # is bounded by the tier's capacity: past it the oldest
                # pending group is dropped unpublished, so a streaming
                # consumer's memory profile stays within cache-budget of
                # the cache-off scan even when finalize is deferred to
                # the end of a multi-file sweep.
                from .serve.result_cache import device_column_nbytes

                ent[2] = True
                ent[3] = sum(device_column_nbytes(c) for c in out.values())
                self._rc_pending_bytes += ent[3]
                # 2x the tier capacity: bounded pinning, while the flush
                # can still OVERFILL the tier enough to exercise eviction
                # (a bound at exactly the capacity would starve it)
                cap = 2 * self._result_cache.cache.tier_capacity(
                    self._result_cache.tier)
                while (self._rc_pending_bytes > cap
                       and len(self._rc_pending) > 1):
                    oldest = next(iter(self._rc_pending))
                    if oldest == id(out):
                        break
                    dropped = self._rc_pending.pop(oldest)
                    self._rc_pending_bytes -= dropped[3]
        now = _time.perf_counter()
        if self._t0 is not None:
            self._stats.wall_seconds = now - self._t0
        self._pipe_stats.count_row_group()
        self._pipe_stats.touch_wall()
        return out

    def stats(self) -> ReaderStats:
        """Decode counters so far (rows/s, bytes/s, pages/chunk, HBM staged)."""
        return self._stats

    def pipeline_stats(self):
        """Per-stage pipeline timing (io / decompress / stage / dispatch /
        finalize) plus stall time and the in-flight high-water mark — see
        pipeline.PipelineStats.  The io/decompress stages are only populated
        when ``prefetch`` > 0 routed the host phase through the chunk pool;
        stage/dispatch/finalize accumulate on every path."""
        return self._pipe_stats

    @scoped_x64
    def read_row_group(self, index: int, finalize: bool = True):
        collected = None
        if self._prefetch > 0:
            feed = _chunk_feed(iter([(self, None, index)]), self._prefetch,
                               self.alloc.max_size,
                               cancel=self._host._cancel)
            try:
                _r, _p, _i, collected = next(feed)
            finally:
                feed.close()
        out = self._dispatch_row_group(
            self._prepare_row_group(index, collected=collected))
        if finalize:
            self.finalize()
        return out

    @scoped_x64
    def finalize(self) -> None:
        """Run deferred validity checks (one device sync for all chunks).
        The sync also proves every kernel reading the staged buffers has
        completed, so the HBM residency ledger releases them here."""
        with self._pipe_stats.timed("finalize"), \
                _xprof_annotation("finalize"):
            _finalize_many([self])
        self._release_device_outstanding()
        self._pipe_stats.touch_wall()

    def iter_batches(self, batch_size: int, columns=None):
        """Yield fixed-size device batches {column: jax.Array[batch_size, ...]}.

        The training-pipeline view: every yielded batch has the SAME static
        shape, so a consuming jitted step compiles once.  Rows flow across row
        group boundaries through fixed-capacity device buffers (power-of-two
        capacity, rows appended with dynamic_update_slice, batches cut with
        dynamic_slice at traced offsets), so the executable set is bounded by
        {capacity} x {row-group size} x {batch_size} — no per-remainder
        recompiles.  The final short remainder is NOT yielded (classic
        drop_remainder semantics; the row count is known from the footer).

        Fixed-width, null-free, non-repeated columns only: ragged byte arrays
        have no static row shape.  Dictionary columns are materialized to
        values on device.
        """
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        want = None if columns is None else set(columns)
        bufs: dict[str, jax.Array] = {}
        cap = 0
        start = end = 0  # valid rows [start, end), shared by all columns
        first = True
        for cols in self.iter_row_groups():
            ready: list[dict] = []
            # trace everything under a scoped x64 context; yields happen
            # outside it so the consumer's dtype semantics are untouched
            # (a decorator on a generator would only scope its construction)
            with K.enable_x64():
                arrays = {}
                for name, col in cols.items():
                    if want is not None and name not in want:
                        continue
                    if isinstance(col, DeviceDictColumn):
                        col = col.materialize()
                    if col.values is None:
                        raise TypeError(
                            f"iter_batches needs fixed-width columns; "
                            f"{name!r} is ragged (offsets/heap)"
                        )
                    if col.max_rep > 0:
                        raise TypeError(
                            f"iter_batches needs flat columns; {name!r} is "
                            f"repeated"
                        )
                    if col.num_values != col.num_leaf_slots:
                        raise TypeError(
                            f"iter_batches needs null-free columns; {name!r} "
                            f"has "
                            f"{col.num_leaf_slots - col.num_values} "
                            f"nulls"
                        )
                    arrays[name] = (col.values, col.num_values)
                if want is not None:
                    missing = want - set(arrays)
                    if missing:
                        raise KeyError(
                            f"iter_batches: no such column(s) {sorted(missing)}"
                        )
                if not arrays:
                    continue
                ns = {n for _, n in arrays.values()}
                if len(ns) != 1:
                    raise ParquetError(
                        f"iter_batches: column row counts differ: {sorted(ns)}"
                    )
                n_new = ns.pop()
                if n_new == 0:
                    continue  # zero-row group: placeholder columns, skip
                # arrays may be bucket-padded past n_new; appends write the
                # full padded rows (tail garbage lands past `end`, where the
                # next append or the drop_remainder tail covers it), so all
                # capacity math uses the padded length
                pad_len = max(int(v.shape[0]) for v, _ in arrays.values())
                if first:
                    cap = _bucket(pad_len + batch_size)
                    bufs = {k: _fit_rows_jit(v, size=cap)
                            for k, (v, _) in arrays.items()}
                    start, end = 0, n_new
                    first = False
                else:
                    if end + pad_len > cap and start:  # compact [start, end) to 0
                        bufs = {k: _roll_rows_jit(v, np.int64(-start))
                                for k, v in bufs.items()}
                        end -= start
                        start = 0
                    if end + pad_len > cap:  # still short: grow capacity
                        cap = _bucket(end + pad_len + batch_size)
                        bufs = {k: _fit_rows_jit(v, size=cap)
                                for k, v in bufs.items()}
                    bufs = {
                        k: _update_rows_jit(bufs[k], v, np.int64(end))
                        for k, (v, _) in arrays.items()
                    }
                    end += n_new
                # the carry is device memory held across row groups: count it
                # against this row group's budget window (alloc resets per
                # group in _prepare_row_group)
                self.alloc.register(
                    sum(int(np.prod(v.shape)) * v.dtype.itemsize
                        for v in bufs.values())
                )
                while end - start >= batch_size:
                    ready.append({
                        k: _dynslice_jit(v, np.int64(start), size=batch_size)
                        for k, v in bufs.items()
                    })
                    start += batch_size
            yield from ready

    def iter_row_groups(self, finalize_each: bool = False):
        """Iterate row groups with a one-deep transfer pipeline.

        Staging (host→device transfer) of row group N runs on a worker thread
        while the main thread decompresses and parses row group N+1 —
        transfers serialize on the device queue, so overlapping them with
        host work is the difference between sum and max of the two
        phases.  The stager buffers are plain uint8, so the worker thread
        needs no x64 scope.
        """
        from concurrent.futures import ThreadPoolExecutor
        import contextlib

        from .pipeline import PipelineStats

        # fresh counters per scan: the wall clock anchors at the scan's
        # first touch, so overlap_efficiency never absorbs idle time
        # between two scans on one reader (pipeline_stats() reports the
        # current/most recent scan)
        self._pipe_stats = PipelineStats(prefetch=self._prefetch,
                                         budget_bytes=self.alloc.max_size,
                                         tracer=self._tracer)
        # fresh per-scan retry budget / coalescing state / abort poison on
        # BOTH paths (the prefetch feed also calls this — idempotent at
        # scan start; the prefetch=0 path has no other reset point), with
        # the request's deadline/cancel riding the scan token
        self._host._sr.set_scan(
            self._store.begin_scan(cancel=self._host._cancel))
        indices = [i for i in range(self.num_row_groups)
                   if self._host.row_group_selected(i)]
        self.quarantine.begin_scan(len(indices))
        if not indices:
            self.finalize()
            return
        trace = (jax.profiler.trace(self.profile_dir) if self.profile_dir
                 else contextlib.nullcontext())
        # aligned device profile (TPQ_XPROF): a bounded window of the XLA
        # timeline whose TraceAnnotations match the span tracer's stage
        # names; profile_dir (the explicit kwarg) takes precedence — the
        # two capture APIs must not nest
        xprof = None if self.profile_dir else self._xprof
        if xprof is not None:
            xprof.start()
        try:
            with trace, ThreadPoolExecutor(1) as ex:
                for _, out in _scan_pipeline(
                    ((self, None, i) for i in indices), ex,
                    finalize_each=finalize_each,
                    prefetch=self._prefetch,
                    budget_bytes=self.alloc.max_size,
                    watchdog=self._watchdog,
                    quarantine=self.quarantine,
                    cancel=self._host._cancel,
                ):
                    yield out
                    if xprof is not None:
                        xprof.tick()
        finally:
            if xprof is not None:
                xprof.stop()


def _finalize_many(readers) -> None:
    """Run every reader's deferred validity checks with ONE device sync.

    Every device->host transfer pays a fixed round trip regardless of
    size — and worse, a D2H sync of computed results mid-pipeline stalls
    the async queue behind it.  Stacking every deferred scalar across all
    readers costs one round trip total, and callers place it after the last
    dispatch so nothing downstream is poisoned."""
    deferred = [d for r in readers for d in r._deferred]
    if deferred:
        host_max = np.asarray(_stack_jit([m for m, _, _ in deferred]))
        for mx, (_, dict_len, path) in zip(host_max, deferred):
            if int(mx) >= dict_len:
                raise ParquetError(
                    f"dictionary index {int(mx)} out of range ({dict_len}) "
                    f"in column {path}"
                )
        for r in readers:
            r._deferred = []
    # the checks passed (or there were none): dispatched groups' decoded
    # columns are now provably valid — publish them to the result cache
    for r in readers:
        r._flush_result_cache()


def _timed_stage(reader: DeviceFileReader, stager: _RowGroupStager):
    """Stage on the worker, attributing wall time to the owning reader's
    ``stage_seconds`` lane (the worker and dispatching threads write
    concurrently; += is not atomic across bytecodes, hence the lock.
    Distinct lanes — not the old shared ``device_seconds`` scalar — so the
    two threads' concurrent intervals can never double-count wall time)."""
    import time as _time

    t0 = _time.perf_counter()
    with reader._pipe_stats.timed("stage", bytes=stager.total), \
            _xprof_annotation("stage"):
        buf_dev = stager.stage()
    t_staged = _time.perf_counter()
    with reader._stats_lock:
        reader._stats.stage_seconds += t_staged - t0
    # post-stage timestamp: the h2d lane times the ASYNC transfer tail,
    # never the host staging wall the `stage` lane already measured
    reader._note_staged(stager, buf_dev, t_staged)
    return buf_dev


class _FailedChunk:
    """In-band marker for a quarantined chunk riding the ordered chunk
    feed (a worker raise would kill the whole multi-file pool).  Carries
    the annotated exception; ``_prepare_row_group`` re-raises it so the
    consumer-side containment in ``_scan_pipeline`` records exactly one
    quarantine entry per failed unit on every path."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _chunk_feed(work, prefetch: int, budget_bytes: int = 0, watchdog=None,
                cancel=None):
    """Chunk-granular prefetch over the ``(reader, path, index)`` stream.

    The host half of the overlapped pipeline (ISSUE 1 tentpole): IO + CRC +
    decompression + structure parse of upcoming chunks runs on a bounded
    pool of ``prefetch`` threads — work items FLATTENED across row-group
    and file boundaries, so the pool never drains while the main thread
    registers/stages/dispatches the current group.  Yields
    ``(reader, path, index, collected)`` in work order, where ``collected``
    is the dict ``_prepare_row_group(collected=...)`` consumes
    ({column_path: (md, _ChunkAssembler)} plus the pruning row count).

    Structurally this mirrors FileReader._decode_row_groups (reader.py) —
    same flatten/regroup protocol, sentinel convention, and cost formula;
    a change to one should be checked against the other.  They stay
    separate because the payloads differ (parsed assemblers + pruning
    plans + per-reader stats attribution here, finished ColumnData there).

    Page-pruning planning runs in the CONSUMER thread as items are pulled
    (it must precede its group's reads); its header walks go through the
    SharedReader's pread view, so they never race the pool's reads on the
    shared descriptor.  In-flight decompressed bytes are bounded by an
    InFlightBudget over ``budget_bytes`` — backpressure, not OOM.  Worker
    chunks register against fresh per-chunk AllocTrackers (the
    decompression-bomb guard keeps its teeth without sharing the reader's
    per-row-group counter across threads).
    """
    from .alloc import AllocTracker, InFlightBudget
    from .iostore import CoalescedFetcher
    from .iostore_async import engine_for_store
    from .pipeline import prefetch_map

    budget = InFlightBudget(budget_bytes)
    if watchdog is not None and watchdog.enabled:
        # the raise-policy exit from a wedge: aborting the budget wakes the
        # submitter blocked in acquire() with HangError (obs.Watchdog)
        watchdog.add_abort_hook(budget.abort)
    fed: set = set()  # readers whose _live_budget points at this feed
    srs: dict = {}  # reader id -> its host's store-backed SharedReader
    pending: dict[tuple, dict] = {}
    current = {"stats": None}  # stats of the reader whose item is submitting
    depth_owner = {"stats": None}  # last stats whose queue_depth gauge we set
    feedbox = {"eng": None}  # the async fetch engine, once any store routes

    class _Feed:
        """Late-binding feed gate for prefetch_map: a multi-file work
        stream mixes engine-routed and plain stores, and the engine only
        becomes known when gen_items first plans a routed group — until
        then the feed reports no lookahead appetite (plain threaded
        behavior), after which in-flight IO is bounded by the engine cap
        instead of the decode window."""

        @property
        def max_inflight(self):
            eng = feedbox["eng"]
            return eng.max_inflight if eng is not None else 0

        @staticmethod
        def want_more():
            eng = feedbox["eng"]
            return eng is not None and eng.want_more()

    class _StatsFwd:
        """Route prefetch_map's stall/peak accounting to the owning reader.

        Submission happens in the consumer thread right after gen_items
        yields an item, so ``current`` always names the reader whose chunk
        is paying the budget wait.  The queue-depth gauge is point-in-time
        state, not a flow: when the window's ownership moves to the next
        reader, the previous owner's gauge must drop to 0 — otherwise its
        sampler (and the final stop() tick at close) records a phantom
        backlog frozen at whatever depth it last saw, and prefetch_map's
        end-of-run reset only ever reaches the LAST reader."""

        @staticmethod
        def add_stall(seconds, t0=None):
            st = current["stats"]
            if st is not None:
                st.add_stall(seconds, t0)

        @staticmethod
        def set_queue_depth(n):
            st = current["stats"]
            prev = depth_owner["stats"]
            if prev is not None and prev is not st:
                prev.set_queue_depth(0)
            depth_owner["stats"] = st
            if st is not None:
                st.set_queue_depth(n)

        @staticmethod
        def note_peak(b):
            st = current["stats"]
            if st is not None:
                st.note_peak(b)

    def gen_items():
        for r, path, i in work:
            current["stats"] = r._pipe_stats
            r._live_budget = budget  # sampler budget_waiters track late-binds
            fed.add(r)
            sr = srs.get(id(r))
            if sr is None:
                # the host reader's own store-backed view — one wrapper
                # per (file, store) pair, never a divergent copy
                sr = srs[id(r)] = r._host._sr
                # fresh per-scan retry budget + coalescing state, scoped
                # to this scan's token (the reader's request deadline/
                # cancel rides it into every store read)
                sr.set_scan(sr.store.begin_scan(cancel=r._host._cancel))
            rc = r._result_cache
            if rc is not None and rc.has_group(
                    i, [".".join(l.path) for l in r.schema.selected_leaves()],
                    count_misses=True):
                # decoded-result hit: the feed reads NOTHING for this
                # group (no pruning walk, no chunk IO) — prepare re-probes
                # authoritatively and falls back to a sequential decode in
                # the rare evicted-in-between race
                pending[(id(r), i)] = {"r": r, "path": path, "i": i,
                                       "todo": 1, "chunks": {},
                                       "rows_dropped": 0, "cached": True}
                yield (r, None, i, None, None, None, None, None, None, None)
                continue
            rg = r.metadata.row_groups[i]
            leaves = {l.path: l for l in r.schema.selected_leaves()}
            skip_pages, rows_dropped, planned_bufs = r._plan_page_pruning(
                rg, leaves, f=sr.as_file(), index=i)
            items = []
            ranges = []
            for p, leaf, _chunk, md, offset in row_group_chunks(rg, leaves):
                items.append([r, sr, i, p, leaf, md, offset,
                              (skip_pages or {}).get(p),
                              planned_bufs.get(p), None])
                if planned_bufs.get(p) is None:
                    # chunks the pruning planner already read never join a
                    # coalesced span (their bytes are in hand)
                    ranges.append((offset, md.total_compressed_size))
            # range coalescing (iostore.py): this group's chunk reads merge
            # into fewer, larger, individually-retryable fetches, fanned
            # out on the prefetch pool (the first worker to touch a span
            # fetches it) — only for stores that ask for it
            st = sr.store
            tok = sr._scan
            eng = engine_for_store(st)
            if eng is not None:
                feedbox["eng"] = eng
            use_coalesce = (st.prefers_coalescing
                            and not (tok.coalesce_disabled if tok is not None
                                     else st.coalesce_disabled)
                            and len(ranges) > 1)
            if ranges and (use_coalesce or eng is not None):
                # engine mode submits the group's fetches NOW (merged
                # spans, or singles once the ladder disables merging)
                fetcher = CoalescedFetcher(st, ranges, scan=tok, engine=eng,
                                           coalesce=use_coalesce)
                for it in items:
                    if it[8] is None:
                        it[9] = fetcher
            key = (id(r), i)
            pending[key] = {"r": r, "path": path, "i": i,
                            "todo": max(len(items), 1), "chunks": {},
                            "rows_dropped": rows_dropped}
            if not items:
                items.append([r, None, i, None, None, None, None, None,
                              None, None])
            yield from map(tuple, items)

    def cost(item):
        md = item[5]
        if md is None:
            return 0
        comp = max(md.total_compressed_size or 0, 0)
        return comp + max(md.total_uncompressed_size or 0, comp)

    def collect(item):
        r, sr, i, p, leaf, md, offset, skip, buf0, fetcher = item
        if md is None:
            return (id(r), i), None, None
        stats = r._pipe_stats
        ctx = {"file": r._host._source_name, "row_group": i,
               "column": ".".join(p), "chunk_offset": offset}
        try:
            tracker = AllocTracker(r.alloc.max_size)
            tracker.register(md.total_compressed_size)
            if buf0 is not None:
                buf = buf0  # the pruning planner already paid this chunk's IO
            else:
                with stats.timed("io"):
                    buf = (fetcher.read(offset, md.total_compressed_size)
                           if fetcher is not None
                           else sr.pread(offset, md.total_compressed_size))
            require_full(buf, offset, md.total_compressed_size,
                         context=f"column {'.'.join(p)}")
            with stats.timed("decompress"):
                asm = _collect_chunk(
                    buf, md.codec, md.num_values, leaf, r._deferred,
                    validate_crc=r.validate_crc, alloc=tracker,
                    statistics=md.statistics, skip_pages=skip,
                    context=ctx, dict_cache=r._dict_cache,
                )
        except ParquetError as e:
            # containment seam (quarantine.py): wrap instead of raise so
            # the feed keeps flowing; the consumer notes the record
            q = r.quarantine
            from .errors import DataIntegrityError
            from .quarantine import annotate_data_error

            if not q.contains or isinstance(e, DataIntegrityError):
                raise
            return (id(r), i), p, (md, _FailedChunk(
                annotate_data_error(e, **{k: v for k, v in ctx.items()
                                          if k != "chunk_offset"})))
        # ship planning on the SAME worker thread (outside the decompress
        # timer: its compression seconds land in the `recompress` stage) —
        # the link-recompression work overlaps the consumer's stage/dispatch
        if asm is not None:
            asm.preship(r._ship_planner, stats,
                        route_hint=(r._plan.route_hint(i, ".".join(p))
                                    if r._plan is not None else None))
        stats.count_chunk()
        return (id(r), i), p, (md, asm)

    try:
        for key, p, payload in prefetch_map(gen_items(), collect, prefetch,
                                            budget=budget, cost=cost,
                                            stats=_StatsFwd(),
                                            cancel=cancel, feed=_Feed()):
            slot = pending[key]
            if p is not None:
                slot["chunks"][p] = payload
            slot["todo"] -= 1
            if slot["todo"] == 0:
                del pending[key]
                r = slot["r"]
                r._pipe_stats.note_peak(budget)
                r._pipe_stats.touch_wall()
                yield r, slot["path"], slot["i"], {
                    "chunks": slot["chunks"],
                    "rows_dropped": slot["rows_dropped"],
                    "cached": slot.get("cached", False),
                }
    finally:
        # un-bind the dead feed's budget: a later flight dump (or a reused
        # reader's sampler) must not report this scan's stale zero-waiter
        # budget as live state — and the reader-lifetime watchdog must not
        # pin (or abort) this scan's budget after the feed is gone
        if watchdog is not None and watchdog.enabled:
            watchdog.remove_abort_hook(budget.abort)
        for r in fed:
            if r._live_budget is budget:
                r._live_budget = None


def _scan_pipeline(work, ex, finalize_each: bool = False,
                   close_finished: bool = False,
                   defer_finalize: bool = False,
                   prefetch: int = 0, budget_bytes: int = 0,
                   watchdog=None, quarantine=None, cancel=None):
    """The one-deep prepare/stage/dispatch pipeline shared by
    ``DeviceFileReader.iter_row_groups`` (one reader) and :func:`scan_files`
    (many).  ``work`` yields ``(reader, path, row_group_index)``; this yields
    ``(path, columns)`` per row group.

    With ``prefetch`` > 0 the host phase (chunk IO + decompress + parse) is
    pulled out of ``_prepare_row_group`` onto :func:`_chunk_feed`'s pool:
    chunks of row group N+1 (and beyond, budget permitting) decompress on
    worker threads while group N stages and dispatches — the chunk-granular
    overlap on top of the existing group-granular stage/dispatch overlap.
    An eager error from a prefetched chunk may then preempt the preceding
    yield by up to the feed's depth (the sequential path's by exactly one).

    Ordering contract: a row group is always YIELDED before its reader's
    deferred checks can raise (finalize runs after the yield, either at a
    file boundary or at the end), matching iter_row_groups' yield-then-raise
    semantics.  With ``close_finished`` a reader is closed as soon as its
    last row group is delivered, bounding open file descriptors to one (all
    of a reader's chunk reads precede its last group's yield, so the feed
    never touches a closed descriptor).
    """
    if prefetch > 0:
        stream = _chunk_feed(work, prefetch, budget_bytes, watchdog=watchdog,
                             cancel=cancel)
    else:
        stream = ((r, path, i, None) for r, path, i in work)
    # consumer gate: the watchdog may only fire while the consumer is
    # genuinely blocked in here producing — a consumer pausing between row
    # groups freezes every other lane (full prefetch window) and must not
    # read as a hang (obs.ConsumerLane)
    lane = (watchdog.watch_consumer()
            if watchdog is not None and watchdog.enabled else None)
    from .errors import DataIntegrityError

    dead: set = set()  # readers quarantined whole (policy skip_file)
    try:
        if lane is not None:
            lane.producing()
        prev = None  # (reader, path, prepared, staging future)
        for r, path, i, collected in stream:
            if watchdog is not None:
                watchdog.check()  # surface a fired raise-policy HangError
                # even when no budget wait existed to interrupt (prefetch=0)
            q = quarantine if quarantine is not None else r.quarantine
            if id(r) in dead:
                # collateral skip: a later unit of a skip_file-quarantined
                # file — accounted, never decoded, never a new record
                q.note_unit_skipped(
                    int(r.metadata.row_groups[i].num_rows or 0))
                continue
            try:
                prepared = r._prepare_row_group(i, executor=ex,
                                                collected=collected)
            except ParquetError as e:
                # containment seam (quarantine.py): record + skip the unit
                # instead of aborting the scan; DataIntegrityError (budget
                # exhausted) always propagates
                if not q.contains or isinstance(e, DataIntegrityError):
                    raise
                q.note(e, file=r._host._source_name, row_group=i)
                q.note_unit_skipped(
                    int(r.metadata.row_groups[i].num_rows or 0))
                if q.policy == "skip_file":
                    q.note_file_skipped()
                    dead.add(id(r))
                continue
            fut = (ex.submit(_on_caller_device(_timed_stage), r, prepared[2])
                   if prepared[1] else None)
            if prev is not None:
                pr, pp, pprep, pfut = prev
                out = pr._dispatch_row_group(
                    pprep, pfut.result() if pfut else None
                )
                if lane is not None:
                    lane.idle()
                yield pp, out
                if lane is not None:
                    lane.producing()
                if finalize_each or pr is not r:
                    if not defer_finalize:
                        # a mid-pipeline finalize is a D2H sync that stalls
                        # the async queue; multi-file scans defer it to one
                        # combined end-of-scan check (_finalize_many)
                        pr.finalize()
                    if close_finished and pr is not r:
                        pr.close()
            prev = (r, path, prepared, fut)
        if prev is not None:
            pr, pp, pprep, pfut = prev
            out = pr._dispatch_row_group(
                pprep, pfut.result() if pfut else None
            )
            if lane is not None:
                lane.idle()
            yield pp, out
            if lane is not None:
                lane.producing()
            if not defer_finalize:
                pr.finalize()
    finally:
        # the scan is over (or dead): leave the lane advancing so a
        # reader's long-lived watchdog never mistakes post-scan idleness
        # (or a consumer that abandoned us) for a wedge
        if lane is not None:
            lane.idle()


def scan_files(paths, columns=None, validate_crc=None,
               max_memory: int = 0, row_filter=None, with_path: bool = False,
               prefetch: int = 0, trace=None, sample_ms=None, hang_s=None,
               hang_policy=None, store=None, on_data_error=None,
               quarantine=None, plan_cache=None):
    """Scan several files' row groups through ONE continuous transfer pipeline.

    ``prefetch=K`` additionally runs chunk IO + decompression K-deep on a
    worker pool spanning row-group AND file boundaries (see _chunk_feed), so
    the host phase of file N+1's first group overlaps file N's tail
    transfers — the same lookahead the group-granular pipeline below already
    provides for staging, extended to the host's half of the work.  The
    feed's lookahead opens upcoming files a little earlier, so the open-fd
    bound becomes O(prefetch) instead of one.

    ``store=`` selects the IO backend per file (iostore.py): pass a
    FACTORY callable (``lambda f: MyRangeStore(...)``) so each file gets
    its own store — a single shared instance would mix files' bytes.

    ``plan_cache=`` (a :class:`tpu_parquet.serve.PlanCache`) makes every
    file's footer, ScanPlan IR, and decoded dictionaries read through
    shared cached state — a re-scanned file re-parses nothing, and route/
    pruning memos accumulate across scans.

    The multi-file dataset form of ``DeviceFileReader.iter_row_groups``
    (BASELINE config 5 is a multi-file row-group scan): per-file iteration
    drains the transfer pipeline at every file boundary — the last row
    group's staging ships with nothing overlapping it, and the next file's
    footer parse waits for it.  Here one staging worker spans the whole
    dataset, so file N+1's footer/decompress overlaps file N's tail
    transfers exactly like adjacent row groups within a file.

    Yields one ``{column: DeviceColumnData}`` dict per row group (in file
    order); ``with_path=True`` yields ``(path, cols)`` pairs.  Deferred
    dictionary range checks run ONCE, after the last file's last group is
    yielded (a per-file-boundary check would be a mid-pipeline D2H sync
    that stalls the async queue — measured ~50ms per boundary); eager
    per-chunk errors raise from the pipelined prepare and may preempt the
    preceding group's yield by one (the pipeline's depth), exactly as
    within one file.  Finished files close at the boundary (open
    descriptors stay bounded for arbitrarily many shards — the deferred
    scalars are device arrays, not file state), and every reader is closed
    on exit even on error.

    .. warning:: Consumers that abandon the scan early (``break``,
       ``islice``) and let the generator be closed by GC lose the deferred
       range-check exception (``GeneratorExit`` semantics swallow it); the
       corruption is still reported via ``logging.error`` on the
       ``tpu_parquet.device_reader`` logger.  Close the generator
       explicitly (or iterate to exhaustion) to get the ``ParquetError``.
    """
    from concurrent.futures import ThreadPoolExecutor

    from .obs import Watchdog, resolve_hang_s, resolve_tracer
    from .quarantine import Quarantine
    from .write.manifest import expand_dataset

    # a manifest path (or a directory holding tpq_manifest.json — the
    # sharded writer's multi-file layout) expands to its member list, so
    # a written-then-compacted dataset scans as ONE dataset
    paths, _manifest = expand_dataset(paths)

    # one tracer spans the whole scan (per-file tracers would shred the
    # timeline Perfetto is supposed to show); with a path, the trace + the
    # merged registry of every reader are written when the scan ends
    tracer, owns_tracer = resolve_tracer(trace)
    # ONE containment engine spans the whole scan: the error budget and the
    # quarantine ledger are per-SCAN facts, not per-file ones (the unit
    # total is unknown up front, so only the absolute budget binds)
    q = quarantine if quarantine is not None else Quarantine(on_data_error)
    q.begin_scan()
    readers: list[DeviceFileReader] = []

    # ONE watchdog spans the whole scan (per-reader watchdogs would call a
    # reader idle just because its neighbor has the pipeline's turn);
    # child readers are armed with an explicit hang_s=0 below so the env
    # cannot raise N redundant watchdog threads for one scan
    watchdog = Watchdog(resolve_hang_s(hang_s), policy=hang_policy)
    if watchdog.enabled:
        def _lanes():
            out: dict = {}
            for r in list(readers):
                for k, v in r._pipe_stats.sample().items():
                    out[k] = out.get(k, 0) + v
            return out

        watchdog.watch("pipeline", _lanes)
        watchdog.watch("reader", lambda: {
            "rows": sum(r._stats.rows for r in list(readers)),
            "chunks": sum(r._stats.chunks for r in list(readers)),
            "staged_bytes": sum(r._stats.staged_bytes
                                for r in list(readers)),
        })

        def _io_lanes():
            out: dict = {}
            for r in list(readers):
                st = r._store.stats
                if st is None:
                    continue
                for k, v in st.progress().items():
                    out[k] = out.get(k, 0) + v
            return out

        # store heartbeat across every file's store: frozen fetch counters
        # + frozen pipeline = a network stall the dump can name
        watchdog.watch("iostore", _io_lanes)
        watchdog.start()

    def work():
        for path in paths:
            # with a serve.PlanCache, the footer, the ScanPlan IR, and the
            # decoded-dictionary cache all read through shared state — a
            # re-scanned file re-parses nothing (ROADMAP item 4's owed
            # footer cache, generalized)
            kw = (plan_cache.reader_kwargs(path, columns=columns,
                                           row_filter=row_filter,
                                           device=True,
                                           validate_crc=validate_crc)
                  if plan_cache is not None else {})
            r = DeviceFileReader(
                path, columns=columns, validate_crc=validate_crc,
                max_memory=max_memory, row_filter=row_filter, trace=tracer,
                sample_ms=sample_ms, hang_s=0, store=store, quarantine=q,
                **kw,
            )
            readers.append(r)
            if watchdog.enabled:
                # like the per-reader wiring: a fired watchdog must wake
                # fetches stalled inside any file's store (no-op for local)
                watchdog.add_abort_hook(r._store.abort)
            for i in range(r.num_row_groups):
                if r._host.row_group_selected(i):
                    yield r, path, i

    # aligned device profile (TPQ_XPROF): the multi-file scan owns ONE
    # bounded window spanning file boundaries — per-reader windows would
    # never start (scan_files drives _scan_pipeline directly, not
    # iter_row_groups)
    xprof = _XprofWindow()
    xprof.start()
    try:
        with ThreadPoolExecutor(1) as ex:
            for pp, out in _scan_pipeline(work(), ex, close_finished=True,
                                          defer_finalize=True,
                                          prefetch=int(prefetch),
                                          budget_bytes=int(max_memory),
                                          watchdog=watchdog, quarantine=q):
                yield (pp, out) if with_path else out
                xprof.tick()
        _finalize_many(readers)
    finally:
        xprof.stop()
        watchdog.stop()
        try:
            # idempotent re-check: covers consumers that abandon the scan
            # early (break/islice) — their consumed-but-unchecked files
            # still validate when the generator closes.  (A GC-time close
            # swallows exceptions by Python semantics — see the docstring
            # warning — so corrupt indices are ALSO logged before raising.)
            try:
                _finalize_many(readers)
            except ParquetError as e:
                import logging

                logging.getLogger(__name__).error(
                    "scan_files deferred validation failed "
                    "(swallowed if this close is GC-driven): %s", e)
                raise
        finally:
            for r in readers:
                r.close()
            if owns_tracer and readers:
                reg = readers[0].obs_registry()
                for r in readers[1:]:
                    reg.merge_from(r.obs_registry())
                tracer.write(registry=reg)
