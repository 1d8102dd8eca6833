"""Device (JAX/XLA) decode kernels — the TPU compute path.

Each kernel is the device twin of a NumPy host kernel in ``tpu_parquet/kernels``;
the host versions are the correctness reference, these are what runs under ``jit``
on TPU.  The split follows SURVEY.md §7.2-P2: the *structure* of a stream (run
headers, delta block headers — metadata-sized, sequential varints) is parsed on the
host; the *bulk* transform (bit extraction, run expansion, prefix sums, gathers) is
a shape-static XLA program over the raw page bytes shipped to HBM.

Key trick shared by the RLE-hybrid and DELTA_BINARY_PACKED kernels: a vectorized
"extract w bits at bit-position p" primitive (`extract_bits`) where both p and w may
be per-value *arrays*.  Each value gathers the ≤5/≤9 bytes that can cover it,
combines them into a wide integer, shifts and masks.  This replaces the reference's
98 width-specialized unrolled functions (bitbacking32.go / bitpacking64.go) and its
value-at-a-time run loops (hybrid_decoder.go:81-113) with gathers the VPU executes
8x128 lanes at a time.

All functions here are jit-compatible with static output shapes: ``count`` and
padded run-table sizes are Python ints at trace time, so XLA sees fixed shapes and
the per-(page-geometry) executable is cached.  int64 work uses 32-bit lane pairs
where possible; full-width paths need 64-bit lanes, which every public entry
point enables for the duration of the call via ``scoped_x64`` (the global
``jax_enable_x64`` setting of the importing application is never modified).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The device decode path needs 64-bit lanes (INT64 columns, byte offsets), but
# flipping ``jax_enable_x64`` process-wide at import time would change dtype
# semantics for any co-resident JAX program (a training pipeline importing this
# library).  Instead every public kernel and reader entry point is wrapped in
# ``scoped_x64`` below, which enters ``jax.enable_x64()`` only for the duration
# of the call: traces happen with 64-bit lanes on, returned arrays keep their
# 64-bit dtypes, and the caller's global x64 setting is never touched.

enable_x64 = jax.enable_x64


def scoped_x64(fn):
    """Run ``fn`` with ``jax_enable_x64`` active, without touching global state.

    Applied to every public device-path entry point so that jit traces see
    64-bit dtypes while the importing application keeps its own x64 setting
    (the reference's int64 columns are not optional — hybrid_decoder.go,
    deltabp_decoder.go:176-333 are 64-bit paths).  Re-entrant: nesting under an
    already-active context (an outer decorated caller) is a cheap no-op flip.
    """

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with enable_x64():
            return fn(*args, **kwargs)

    return wrapper


def named_kernel(family):
    """Wrap a kernel so its traced ops carry a ``tpq.<family>`` name scope.

    The names land in the XLA HLO metadata, so a ``TPQ_XPROF`` device
    profile's op timeline is attributable to the SAME kernel families the
    completion-timing lane reports (snappy_resolve / unpack / gather /
    narrow / levels — device_reader._KERNEL_FAMILIES).  Pure trace-time
    metadata: zero runtime cost in the compiled executable.
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(f"tpq.{family}"):
                return fn(*args, **kwargs)

        return wrapper

    return deco


__all__ = [
    "scoped_x64",
    "named_kernel",
    "extract_bits",
    "unpack_bits",
    "expand_rle_hybrid",
    "expand_rle_hybrid_vw",
    "delta_reconstruct",
    "dict_gather",
    "dict_gather_bytes",
    "ragged_take",
    "levels_to_validity",
    "scatter_defined",
    "row_starts_from_rep",
    "plain_decode_fixed",
    "byte_stream_split_decode",
    "snappy_resolve",
]


# ---------------------------------------------------------------------------
# Bit extraction primitive
# ---------------------------------------------------------------------------

@scoped_x64
def extract_bits(buf: jax.Array, bit_pos: jax.Array, width: jax.Array, max_width: int):
    """Extract unsigned bit fields from an LSB-first byte stream.

    ``buf``      uint8[n] — must be padded with >= (max_width+14)//8 slack bytes
                 so the trailing gathers stay in bounds (host pads; see
                 ``jax_decode.pad_buffer``).
    ``bit_pos``  int32/int64[count] — starting bit of each field.
    ``width``    scalar or per-value array — field width in bits (<= max_width).
    ``max_width`` static upper bound on width; selects the gather footprint.

    Returns uint32[count] when max_width <= 32, else uint64[count].
    """
    bit_pos = bit_pos.astype(jnp.int64)
    byte0 = bit_pos >> 3
    shift = (bit_pos & 7).astype(jnp.uint32)
    nbytes = (max_width + 7 + 7) // 8  # widest field + worst-case 7-bit shift
    # bucketed decode shapes may carry tail positions past the real stream;
    # clamp the gather base so every lane stays in bounds (tail lanes read
    # garbage that callers mask or slice away)
    byte0 = jnp.minimum(byte0, max(buf.shape[0] - 9, 0))
    if max_width <= 25:
        # fits in one uint32 accumulation (25 + 7 = 32)
        acc = jnp.zeros(bit_pos.shape, dtype=jnp.uint32)
        for k in range(nbytes):
            b = buf[byte0 + k].astype(jnp.uint32)
            acc = acc | (b << jnp.uint32(8 * k))
        out = acc >> shift
        w = jnp.asarray(width, dtype=jnp.uint32)
        mask = jnp.where(
            w >= 32, jnp.uint32(0xFFFFFFFF), (jnp.uint32(1) << w) - jnp.uint32(1)
        )
        return out & mask
    if max_width <= 57:
        acc = jnp.zeros(bit_pos.shape, dtype=jnp.uint64)
        for k in range(nbytes):
            b = buf[byte0 + k].astype(jnp.uint64)
            acc = acc | (b << jnp.uint64(8 * k))
        out = acc >> shift.astype(jnp.uint64)
        w = jnp.asarray(width, dtype=jnp.uint64)
        mask = jnp.where(
            w >= 64,
            jnp.uint64(0xFFFFFFFFFFFFFFFF),
            (jnp.uint64(1) << w) - jnp.uint64(1),
        )
        out = out & mask
        return out if max_width > 32 else out.astype(jnp.uint32)
    # 58..64: the field may span 9 bytes; accumulate low 8 bytes then OR the
    # straggler's bits above (64 - shift).
    acc = jnp.zeros(bit_pos.shape, dtype=jnp.uint64)
    for k in range(8):
        b = buf[byte0 + k].astype(jnp.uint64)
        acc = acc | (b << jnp.uint64(8 * k))
    sh = shift.astype(jnp.uint64)
    out = acc >> sh
    b8 = buf[byte0 + 8].astype(jnp.uint64)
    # when shift == 0 the straggler contributes nothing (and << 64 is UB-ish);
    # mask it out explicitly.
    high = jnp.where(sh > 0, b8 << (jnp.uint64(64) - sh), jnp.uint64(0))
    out = out | high
    w = jnp.asarray(width, dtype=jnp.uint64)
    mask = jnp.where(
        w >= 64, jnp.uint64(0xFFFFFFFFFFFFFFFF), (jnp.uint64(1) << w) - jnp.uint64(1)
    )
    return out & mask


@named_kernel("unpack")
@scoped_x64
def unpack_bits(buf: jax.Array, width: int, count: int):
    """Device twin of kernels.bitpack.unpack: fixed-width LSB-first unpack."""
    if width == 0:
        dt = jnp.uint32 if width <= 32 else jnp.uint64
        return jnp.zeros(count, dtype=dt)
    pos = jnp.arange(count, dtype=jnp.int64) * width
    return extract_bits(buf, pos, width, width)


# ---------------------------------------------------------------------------
# RLE / bit-packed hybrid expansion
# ---------------------------------------------------------------------------

@named_kernel("unpack")
@scoped_x64
def expand_rle_hybrid(
    buf: jax.Array,
    run_ends: jax.Array,
    run_is_rle: jax.Array,
    run_values: jax.Array,
    run_bit_starts: jax.Array,
    width: int,
    count: int,
    n_valid=None,
):
    """Expand a parsed RLE/bit-packed hybrid stream to ``count`` values.

    ``count`` may be a *bucketed* static size larger than the stream's real
    value count; pass the real count as the traced scalar ``n_valid`` and the
    tail lanes come back zeroed (so e.g. deferred dictionary-index range
    checks never see tail garbage).  One executable then serves every stream
    whose count lands in the same bucket.

    Host side (jax_decode.parse_hybrid_device) walks the run headers — a few bytes
    per run — and hands over per-run metadata (padded to a static run count):

    ``run_ends``       int64[R] cumulative value count at the end of each run
                       (padding runs repeat the final end).
    ``run_is_rle``     bool[R]
    ``run_values``     uint32[R] the repeated value for RLE runs (0 for BP).
    ``run_bit_starts`` int64[R] bit offset of the run's packed payload in ``buf``,
                       minus run_start*width so position math is uniform (0 for RLE).
    ``width``          static bit width of the stream.

    Replaces hybridDecoder.next (hybrid_decoder.go:81-113): every output position
    finds its run with one searchsorted, then either broadcasts the RLE value or
    bit-extracts its element — no sequential state.
    """
    pos = jnp.arange(count, dtype=jnp.int64)
    r = jnp.searchsorted(run_ends, pos, side="right").astype(jnp.int32)
    r = jnp.minimum(r, run_ends.shape[0] - 1)
    is_rle = run_is_rle[r]
    rle_val = run_values[r]
    if width == 0:
        return jnp.zeros(count, dtype=jnp.uint32)
    bit_pos = run_bit_starts[r] + pos * width
    # clamp BP gathers for RLE positions to 0 so they stay in bounds
    bit_pos = jnp.where(is_rle, 0, bit_pos)
    bp_val = extract_bits(buf, bit_pos, width, width)
    out = jnp.where(is_rle, rle_val.astype(bp_val.dtype), bp_val)
    if n_valid is not None:
        out = jnp.where(pos < n_valid, out, jnp.zeros((), dtype=out.dtype))
    return out


@named_kernel("unpack")
@scoped_x64
def expand_rle_hybrid_vw(
    buf: jax.Array,
    run_ends: jax.Array,
    run_is_rle: jax.Array,
    run_values: jax.Array,
    run_bit_starts: jax.Array,
    run_widths: jax.Array,
    max_width: int,
    count: int,
    n_valid=None,
):
    """Variable-width :func:`expand_rle_hybrid`: each run carries its own bit
    width (``run_widths`` uint32[R], 0 for RLE runs).

    A dictionary-encoded column chunk is one hybrid stream per page, and the
    index width legally GROWS page to page as the dictionary fills (pyarrow
    writes exactly that).  Treating the width as per-run data instead of a
    static lets one executable decode the whole chunk's merged run table —
    per-value dynamic widths are what :func:`extract_bits` is built for.
    ``max_width`` is the static gather-footprint bound (round it to a
    multiple of 8 to share executables).
    """
    pos = jnp.arange(count, dtype=jnp.int64)
    r = jnp.searchsorted(run_ends, pos, side="right").astype(jnp.int32)
    r = jnp.minimum(r, run_ends.shape[0] - 1)
    is_rle = run_is_rle[r]
    rle_val = run_values[r]
    w = run_widths[r].astype(jnp.int64)
    bit_pos = run_bit_starts[r] + pos * w
    bit_pos = jnp.where(is_rle, 0, bit_pos)
    bp_val = extract_bits(buf, bit_pos, w.astype(jnp.uint32), max_width)
    out = jnp.where(is_rle, rle_val.astype(bp_val.dtype), bp_val)
    if n_valid is not None:
        out = jnp.where(pos < n_valid, out, jnp.zeros((), dtype=out.dtype))
    return out


# ---------------------------------------------------------------------------
# DELTA_BINARY_PACKED reconstruction
# ---------------------------------------------------------------------------

@named_kernel("unpack")
@scoped_x64
def delta_reconstruct(
    buf: jax.Array,
    first_value: jax.Array,
    mini_bit_starts: jax.Array,
    mini_widths: jax.Array,
    mini_min_delta: jax.Array,
    values_per_mini: int,
    count: int,
    bits: int,
    max_width: int | None = None,
):
    """Reconstruct a DELTA_BINARY_PACKED column from packed miniblock bytes.

    Host (jax_decode.parse_delta_device) reads the block/miniblock headers — a
    handful of varints per 128 values — and passes per-*miniblock* tables:

    ``mini_bit_starts`` int64[M] bit offset of each miniblock's packed deltas.
    ``mini_widths``     int32[M] per-miniblock delta bit width (<= bits).
    ``mini_min_delta``  int64/uint64[M] the block's min_delta (repeated per mini).

    Device does: per-delta dynamic-width bit extract → + min_delta → cumsum with
    the zigzag first value as seed.  Arithmetic wraps modulo 2**bits via unsigned
    lanes, matching the Go reference's overflow semantics (deltabp_decoder.go).
    Replaces the value-at-a-time loops of deltabp_decoder.go:13-333.

    ``max_width`` (static) bounds the per-delta gather footprint: passing the
    stream's real max miniblock width cuts the byte gathers from 9 to
    ceil((w+14)/8) for typical small-delta data.
    """
    n_deltas = count - 1
    out_u = jnp.uint32 if bits == 32 else jnp.uint64
    out_s = jnp.int32 if bits == 32 else jnp.int64
    first_u = jnp.asarray(first_value).astype(jnp.int64).astype(out_u)
    if n_deltas <= 0:
        return jnp.full((count,), first_u, dtype=out_u).astype(out_s)
    i = jnp.arange(n_deltas, dtype=jnp.int64)
    m = i // values_per_mini
    within = i % values_per_mini
    w = mini_widths[m]
    bit_pos = mini_bit_starts[m] + within * w.astype(jnp.int64)
    mw = bits if max_width is None else max(int(max_width), 1)
    raw = extract_bits(buf, bit_pos, w, mw).astype(out_u)
    deltas = raw + mini_min_delta[m].astype(out_u)
    acc = jnp.cumsum(deltas, dtype=out_u)
    vals = jnp.concatenate([first_u[None], first_u + acc])
    return vals.astype(out_s)


# ---------------------------------------------------------------------------
# Dictionary / ragged gathers
# ---------------------------------------------------------------------------

@named_kernel("gather")
@scoped_x64
def dict_gather(dictionary: jax.Array, indices: jax.Array):
    """Fixed-width dictionary expansion (type_dict.go:10-60 read path).

    Use only for integer dictionaries; float dictionaries must go through
    :func:`dict_gather_bytes` — TPU emulates f64 as float32 pairs, f64-typed
    gathers can round, and XLA's X64-elimination pass implements bitcasts *into*
    wide types from u8 rows but not out of them.
    """
    return jnp.take(dictionary, indices.astype(jnp.int32), axis=0)


@named_kernel("gather")
@scoped_x64
def dict_gather_bytes(dict_u8_rows: jax.Array, indices: jax.Array, dtype: str):
    """Gather dictionary rows as raw bytes, then bitcast into ``dtype``.

    ``dict_u8_rows`` is uint8[K, itemsize] (a free numpy view host-side).  The
    byte gather moves bits verbatim — NaN payloads, -0.0, subnormals survive —
    and the final u8[...,itemsize]→dtype bitcast is the pattern the TPU X64
    rewriter supports (same as plain_decode_fixed).
    """
    rows = jnp.take(dict_u8_rows, indices.astype(jnp.int32), axis=0)
    n, total = rows.shape
    if dtype == "float64":
        # uint32 word pairs, not f64 — see plain_decode_fixed
        return jax.lax.bitcast_convert_type(
            rows.reshape(n, 2, 4), jnp.uint32
        ).reshape(n, 2)
    dt = _PLAIN_DTYPES[dtype]
    itemsize = jnp.dtype(dt).itemsize
    if total == itemsize:
        return jax.lax.bitcast_convert_type(rows, dt).reshape(n)
    # multi-word values (e.g. INT96 as 3×uint32): keep the trailing word axis
    return jax.lax.bitcast_convert_type(
        rows.reshape(n, total // itemsize, itemsize), dt
    ).reshape(n, total // itemsize)


@named_kernel("gather")
@scoped_x64
def ragged_take(
    offsets: jax.Array, heap: jax.Array, indices: jax.Array, out_heap_size: int
):
    """Gather rows of a ragged (offsets, heap) byte column — string dict decode.

    ``out_heap_size`` is static (host computes sum of selected lengths).  Returns
    (new_offsets int64[m+1], new_heap uint8[out_heap_size]).  Output byte j maps to
    output row r = searchsorted(new_offsets, j) and source byte
    src_start[r] + (j - new_start[r]) — two gathers, no per-row loop.
    """
    idx = indices.astype(jnp.int64)
    lens = offsets[idx + 1] - offsets[idx]
    new_off = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int64), jnp.cumsum(lens, dtype=jnp.int64)]
    )
    j = jnp.arange(out_heap_size, dtype=jnp.int64)
    r = jnp.searchsorted(new_off, j, side="right") - 1
    r = jnp.clip(r, 0, idx.shape[0] - 1)
    src = offsets[idx[r]] + (j - new_off[r])
    src = jnp.clip(src, 0, heap.shape[0] - 1) if heap.shape[0] else src * 0
    new_heap = heap[src] if heap.shape[0] else jnp.zeros(0, dtype=jnp.uint8)
    return new_off, new_heap


# ---------------------------------------------------------------------------
# Dremel level reconstruction (prefix scans)
# ---------------------------------------------------------------------------

@named_kernel("levels")
@scoped_x64
def levels_to_validity(def_levels: jax.Array, max_def: int):
    """validity[i] = slot i holds a real leaf value (def == max_def)."""
    return def_levels == max_def


@scoped_x64
def scatter_defined(values: jax.Array, validity: jax.Array, fill):
    """Expand dense defined values to one-per-slot with ``fill`` at null slots.

    The data-parallel replacement for the reference's assembly loop
    (data_store.go:262-309): position of slot i inside ``values`` is the exclusive
    prefix count of validity — one cumsum + one gather.
    """
    vidx = jnp.cumsum(validity.astype(jnp.int32)) - 1
    vidx = jnp.clip(vidx, 0, max(values.shape[0] - 1, 0))
    if values.shape[0] == 0:
        return jnp.full(validity.shape, fill, dtype=values.dtype)
    expanded = jnp.take(values, vidx, axis=0)
    fill_arr = jnp.asarray(fill, dtype=values.dtype)
    return jnp.where(
        validity.reshape(validity.shape + (1,) * (values.ndim - 1)),
        expanded,
        fill_arr,
    )


@named_kernel("levels")
@scoped_x64
def row_starts_from_rep(rep_levels: jax.Array):
    """Row-boundary mask from repetition levels: a slot with rep==0 starts a row.

    row_index = inclusive prefix count of starts - 1; the scan that replaces the
    reference's getNextData row walk (schema.go:216-312).
    """
    starts = rep_levels == 0
    row_index = jnp.cumsum(starts.astype(jnp.int64)) - 1
    return starts, row_index


# ---------------------------------------------------------------------------
# PLAIN / BYTE_STREAM_SPLIT
# ---------------------------------------------------------------------------

_PLAIN_DTYPES = {
    "int32": jnp.int32,
    "int64": jnp.int64,
    "uint32": jnp.uint32,
    "float32": jnp.float32,
    "float64": jnp.float64,
}


@named_kernel("plain")
@scoped_x64
def plain_decode_fixed(buf: jax.Array, dtype: str, count: int):
    """PLAIN decode of a fixed-width type: reshape + bitcast, zero compute.

    (type_int32.go / type_int64.go / type_float.go / type_double.go read paths.)

    DOUBLE columns return uint32[count, 2] little-endian word pairs, NOT f64:
    TPU emulates f64 as float32 pairs (~48 mantissa bits), so a materialized f64
    array silently rounds the low bits of real data.  int64 emulation is exact
    (true 32-bit word pairs), so INT64 stays native.  Host-side view back to f64
    is free (DeviceColumnData.to_host).
    """
    if dtype == "float64":
        raw = buf[: count * 8].reshape(count, 2, 4)
        return jax.lax.bitcast_convert_type(raw, jnp.uint32).reshape(count, 2)
    dt = _PLAIN_DTYPES[dtype]
    nbytes = jnp.dtype(dt).itemsize
    raw = buf[: count * nbytes].reshape(count, nbytes)
    return jax.lax.bitcast_convert_type(raw, dt).reshape(count)


@named_kernel("plain")
@scoped_x64
def byte_stream_split_decode(buf: jax.Array, dtype: str, count: int):
    """BYTE_STREAM_SPLIT: de-interleave K byte streams then bitcast.

    DOUBLE returns uint32[count, 2] word pairs (see plain_decode_fixed).
    """
    if dtype == "float64":
        mat = buf[: count * 8].reshape(8, count).T.reshape(count, 2, 4)
        return jax.lax.bitcast_convert_type(mat, jnp.uint32).reshape(count, 2)
    dt = _PLAIN_DTYPES[dtype]
    nbytes = jnp.dtype(dt).itemsize
    mat = buf[: count * nbytes].reshape(nbytes, count).T
    return jax.lax.bitcast_convert_type(mat, dt).reshape(count)


@named_kernel("snappy_resolve")
def snappy_resolve(ends, asrc, offs, islit, *, out_pad: int, iters: int):
    """Resolve snappy op tables into a per-output-byte SOURCE MAP.

    The shared device half of every compressed-shipping route (PLAIN
    fixed-width, narrow+snappy, byte-array heaps, dictionary tables — see
    ``ship.py``): the host's tag walk (``native.snappy_plan``, packed by
    ``device_reader._plan_snappy_ops``) describes each op's output extent;
    this maps every position of the decompressed OUTPUT SPACE to the staged
    buffer index holding its byte, without materializing the output:

    1. per output byte, find its op (one searchsorted over ``ends``) and
       compute a source: literal bytes point into the staged compressed
       stream (>= 0); copy bytes encode their output-space source as
       ``-(pos)-1`` using the periodic form
       ``dst_start - offset + (i mod offset)``, which maps overlapping
       (RLE-style) copies straight past their own op;
    2. resolve copy chains by pointer doubling: ``iters`` rounds of
       ``S = where(S >= 0, S, S[-S-1])`` — after ceil(log2(depth)) rounds
       every byte points at a literal (the host computed the exact max
       chain depth during the tag walk, so ``iters`` is a static bound,
       no syncs).

    All math is int32 (planners enforce the 2 GiB ceiling); positions past
    the real output resolve through padded literal ops (source 0).  Returns
    int32[out_pad] of staged-buffer byte indices.  Traced inside consuming
    jits — not jitted here.
    """
    n_ops = ends.shape[0]
    j = jnp.arange(out_pad, dtype=jnp.int32)
    op = jnp.clip(jnp.searchsorted(ends, j, side="right").astype(jnp.int32),
                  0, n_ops - 1)
    start = jnp.where(op > 0, ends[jnp.maximum(op - 1, 0)], 0)
    within = j - start
    S = jnp.where(
        islit[op] != 0,
        asrc[op] + within,
        -(asrc[op] + within % jnp.maximum(offs[op], 1)) - 1,
    )
    for _ in range(iters):
        t = jnp.clip(-S - 1, 0, out_pad - 1)
        S = jnp.where(S >= 0, S, S[t])
    return S
