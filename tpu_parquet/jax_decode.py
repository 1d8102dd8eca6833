"""Device-path chunk decoding: host structure parse → XLA bulk decode.

This is the TPU twin of chunk_decode.py.  The host walks page headers and the
sequential, metadata-sized parts of each encoding (run headers, delta block
headers) with NumPy; the bulky transforms run as jitted XLA programs from
jax_kernels.py over the raw page bytes staged to device memory.  Decoded columns
are jax Arrays that stay on device (SURVEY.md §7.1 design stance).

Shapes are static per (geometry) so XLA executables are cached across pages:
run tables are padded to power-of-two buckets, byte buffers to 64-byte multiples.
The first page of a new geometry pays a compile; every later page of the same
shape reuses it — the pipelining SURVEY.md §7.4.7 names as the real perf lever.

Encoding coverage mirrors chunk_reader.go:106-159 where the transform is
parallelizable; inherently sequential byte-level paths (PLAIN BYTE_ARRAY length
walking, DELTA_BYTE_ARRAY prefix stitching) parse on host and ship (offsets, heap)
to device, per SURVEY.md §7.4.2/§7.4.4.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import jax_kernels as K
from .jax_kernels import scoped_x64
from .column import ByteArrayData
from .compress import decompress_block
from .footer import ParquetError
from .format import Encoding, PageType, Type, parse_encoding
from .kernels import bitpack, rle
from .kernels.rle import RLEError, _read_uvarint
from .kernels import delta as delta_host
from .kernels.delta import DeltaError
from .chunk_decode import PageSlice, validate_chunk_meta, walk_pages, _check_crc
from .schema.core import SchemaNode

__all__ = [
    "DeviceColumnData",
    "DeviceChunkDecoder",
    "parse_hybrid_meta",
    "parse_delta_meta",
    "decode_hybrid_device",
    "decode_delta_device",
    "pad_buffer",
]

_SLACK = 16  # extract_bits worst-case gather overrun (9 bytes) + alignment


def _bucket(n: int, floor: int = 8) -> int:
    """Round up to a power of two (>= floor) to bound the jit cache."""
    b = floor
    while b < n:
        b <<= 1
    return b


def _bucket_bytes(n: int, floor: int = 64) -> int:
    """Round a byte-buffer size up to 8 steps per power-of-two octave.

    Value buffers are the dominant host→device transfer; pure power-of-two
    padding wastes up to 2x link bandwidth on them (an 80 MB chunk would
    ship as 128 MB).  Eight sizes per octave caps the waste at 12.5% while
    still bounding the number of distinct executable shapes.
    """
    b = _bucket(n, floor)
    if b <= floor:
        return b
    step = b >> 3
    return ((n + step - 1) // step) * step


def _bucket_count(n: int) -> int:
    """Bucket a value count: 8 steps per power-of-two octave (<= 12.5% pad).

    The decode kernels take their output size as a *static* shape, so every
    distinct count otherwise compiles a fresh executable — and each compile
    costs seconds to tens of seconds, dominating first-open wall clock (the
    row groups of one file rarely share exact value counts).
    Decoding into the bucketed size (tail lanes masked or sliced off on host)
    collapses that diversity to <= 8 shapes per octave per kernel family.
    """
    return _bucket_bytes(max(n, 1), 8)


def pad_buffer(raw: bytes | np.ndarray) -> jax.Array:
    """Stage a byte buffer on device, padded so bit-extract gathers stay in bounds."""
    arr = np.frombuffer(raw, dtype=np.uint8) if isinstance(raw, (bytes, bytearray, memoryview)) else raw
    n = len(arr)
    padded = _bucket_bytes(n + _SLACK, 64)
    out = np.empty(padded, dtype=np.uint8)
    out[:n] = arr
    out[n:] = 0
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# RLE/bit-packed hybrid: host run-header parse → device expansion
# ---------------------------------------------------------------------------

@dataclass
class HybridMeta:
    """Padded per-run tables for jax_kernels.expand_rle_hybrid."""

    run_ends: np.ndarray       # int64[R] cumulative counts (padded: repeat last)
    run_is_rle: np.ndarray     # bool[R]
    run_values: np.ndarray     # uint32[R]
    run_bit_starts: np.ndarray  # int64[R] payload bit start minus start*width
    count: int
    consumed: int              # bytes consumed from the stream
    n_runs: int = 0            # real (unpadded) run count
    max_value: Optional[int] = None  # stream max (native walk only, on request)
    eq_count: Optional[int] = None   # values == eq_target (native walk only)


from .native import NATIVE_ERRORS as _NATIVE_ERRORS


def parse_hybrid_meta(
    buf: bytes, width: int, count: int, pos: int = 0, end: Optional[int] = None,
    compute_max: bool = False, eq_target: Optional[int] = None,
) -> HybridMeta:
    """Walk run headers only (no payload unpacking) — cheap, O(runs) bytes.

    Mirrors the header walk of hybrid_decoder.go:115-165 but records (kind, span,
    payload offset) instead of decoding; the payload stays untouched for the
    device kernel.  ``end`` bounds the stream (v1 length prefix): runs may not
    extend past it, matching the host decoder's size validation.

    ``compute_max`` additionally reports the stream's maximum value when the
    native walk is available (``max_value``; None otherwise) — dictionary
    callers use it to range-check indices on host with zero device syncs.
    ``eq_target`` likewise reports ``eq_count``, the number of stream values
    equal to the target — def-level callers pass max_def and get the page's
    defined count without ever materializing the decoded levels.

    The walk itself runs in C when the native library is available
    (native/meta_parse.cpp, identical semantics); this Python loop is the
    reference implementation and the no-toolchain fallback.
    """
    if width < 0 or width > 32:
        raise RLEError(f"invalid hybrid bit width {width} for device path")
    n = len(buf) if end is None else min(end, len(buf))
    if count > 0:
        got = _native_hybrid_meta(buf, n, pos, width, count, compute_max,
                                  eq_target)
        if got is not None:
            return got
    return _parse_hybrid_meta_py(buf, width, count, pos, n)


def _native_hybrid_meta(buf, n, pos, width, count, compute_max=False,
                        eq_target=None) -> Optional[HybridMeta]:
    from . import native

    res = native.hybrid_meta_retry(buf, n, pos, width, count,
                                   want_max=compute_max, eq_target=eq_target)
    if res is None:
        return None
    if isinstance(res, int):
        if res == -10:  # cap retry exhausted: let the Python walk diagnose
            return None
        raise RLEError(_NATIVE_ERRORS.get(res, f"hybrid parse error {res}"))
    n_runs, consumed, ends, kinds, vals, starts, max_value, eq_count = res
    rp = _bucket(max(n_runs, 1))
    run_ends = np.full(rp, count, dtype=np.int64)
    run_is_rle = np.zeros(rp, dtype=bool)
    run_values = np.zeros(rp, dtype=np.uint32)
    run_bit_starts = np.zeros(rp, dtype=np.int64)
    run_ends[:n_runs] = ends
    run_is_rle[:n_runs] = kinds.astype(bool)
    run_values[:n_runs] = vals
    run_bit_starts[:n_runs] = starts
    return HybridMeta(
        run_ends, run_is_rle, run_values, run_bit_starts, count, consumed,
        n_runs=n_runs, max_value=max_value, eq_count=eq_count,
    )


def _parse_hybrid_meta_py(
    buf: bytes, width: int, count: int, pos: int, n: int
) -> HybridMeta:
    ends, kinds, vals, starts = [], [], [], []
    total = 0
    value_bytes = (width + 7) // 8
    while total < count:
        if pos >= n:
            raise RLEError(f"hybrid stream exhausted: wanted {count}, got {total}")
        h, pos = _read_uvarint(buf, pos)
        if h & 1:
            groups = h >> 1
            nvals = groups * 8
            if nvals == 0:
                continue
            nbytes = groups * width
            if pos + nbytes > n:
                raise RLEError("truncated bit-packed run")
            take = min(nvals, count - total)
            kinds.append(False)
            vals.append(0)
            starts.append(pos * 8 - total * width)
            pos += nbytes
            total += take
        else:
            repeats = h >> 1
            if repeats == 0:
                continue
            repeats = min(repeats, count - total)
            if pos + value_bytes > n:
                raise RLEError("truncated RLE run value")
            v = int.from_bytes(buf[pos : pos + value_bytes], "little") if value_bytes else 0
            pos += value_bytes
            kinds.append(True)
            vals.append(v & 0xFFFFFFFF)
            starts.append(0)
            total += repeats
        ends.append(total)

    r = max(len(ends), 1)
    rp = _bucket(r)
    run_ends = np.full(rp, count, dtype=np.int64)
    run_is_rle = np.zeros(rp, dtype=bool)
    run_values = np.zeros(rp, dtype=np.uint32)
    run_bit_starts = np.zeros(rp, dtype=np.int64)
    if ends:
        run_ends[: len(ends)] = ends
        run_is_rle[: len(ends)] = kinds
        run_values[: len(ends)] = vals
        run_bit_starts[: len(ends)] = starts
    else:  # count == 0 never reaches here; defensive
        run_is_rle[0] = True
    return HybridMeta(
        run_ends, run_is_rle, run_values, run_bit_starts, count, pos,
        n_runs=len(ends),
    )


@functools.partial(jax.jit, static_argnames=("width", "count"))
def _hybrid_jit(buf, run_ends, run_is_rle, run_values, run_bit_starts, n_valid,
                *, width, count):
    """``count`` is the (possibly bucketed) static output size; ``n_valid`` is
    the traced real count — tail lanes beyond it are zeroed."""
    return K.expand_rle_hybrid(
        buf, run_ends, run_is_rle, run_values, run_bit_starts, width, count,
        n_valid=n_valid,
    )


@functools.partial(jax.jit, static_argnames=("max_width", "count"))
def _hybrid_vw_jit(buf, run_ends, run_is_rle, run_values, run_bit_starts,
                   run_widths, n_valid, *, max_width, count):
    """Variable-width hybrid expansion (per-run widths — multi-page dict
    chunks whose index width grows as the dictionary fills)."""
    return K.expand_rle_hybrid_vw(
        buf, run_ends, run_is_rle, run_values, run_bit_starts, run_widths,
        max_width, count, n_valid=n_valid,
    )


@scoped_x64
def decode_hybrid_device(buf_dev: jax.Array, meta: HybridMeta, width: int) -> jax.Array:
    return _hybrid_jit(
        buf_dev,
        jnp.asarray(meta.run_ends),
        jnp.asarray(meta.run_is_rle),
        jnp.asarray(meta.run_values),
        jnp.asarray(meta.run_bit_starts),
        np.int64(meta.count),
        width=width,
        count=meta.count,
    )


# ---------------------------------------------------------------------------
# DELTA_BINARY_PACKED: host block-header parse → device extract + cumsum
# ---------------------------------------------------------------------------

@dataclass
class DeltaMeta:
    first_value: int
    mini_bit_starts: np.ndarray  # int64[M] (padded: repeat last with width 0)
    mini_widths: np.ndarray      # int32[M]
    mini_min_delta: np.ndarray   # uint64[M] per-miniblock (block min repeated)
    values_per_mini: int
    count: int
    consumed: int


def _meta_from_headers(hdrs) -> DeltaMeta:
    """Bucket-pad a kernels.delta.parse_headers result into a DeltaMeta."""
    first, starts, widths, mins, values_per_mini, total, consumed = hdrs
    n = len(starts)
    mp = _bucket(max(n, 1))
    bs = np.zeros(mp, dtype=np.int64)
    ws = np.zeros(mp, dtype=np.int32)
    md = np.zeros(mp, dtype=np.uint64)
    if n:
        bs[:n] = starts
        ws[:n] = widths
        md[:n] = mins
        bs[n:] = starts[-1]
    return DeltaMeta(first, bs, ws, md, values_per_mini, total, consumed)


def parse_delta_meta(buf: bytes, bits: int, pos: int = 0) -> DeltaMeta:
    """Walk DELTA_BINARY_PACKED headers, recording per-miniblock geometry.

    The payload bytes are never touched: only the varint headers and the
    bit-width byte vectors are read (deltabp_decoder.go:38-103 structure).
    The walk itself lives in kernels.delta.parse_headers (native C with a
    Python reference fallback — one source of truth for host and device
    paths); this wrapper only adds the bucketed table padding.  ``bits`` is
    kept for API stability: widths up to 64 are accepted even for 32-bit
    columns (wrap-mod-2^32 parity with the Go reference).
    """
    return _meta_from_headers(delta_host.parse_headers(buf, pos))


def _native_delta_meta(buf: bytes, pos: int) -> Optional[DeltaMeta]:
    """Native-walk-only variant (fuzz parity oracle — see fuzz.py)."""
    hdrs = delta_host.native_headers(buf, pos)
    return None if hdrs is None else _meta_from_headers(hdrs)


def _parse_delta_meta_py(buf: bytes, bits: int, pos: int = 0) -> DeltaMeta:
    """Python-walk-only variant (fuzz parity oracle — see fuzz.py)."""
    return _meta_from_headers(delta_host.python_headers(buf, pos))


@functools.partial(
    jax.jit, static_argnames=("values_per_mini", "count", "bits", "max_width")
)
def _delta_jit(
    buf, first, starts, widths, mins, *, values_per_mini, count, bits, max_width
):
    return K.delta_reconstruct(
        buf, first, starts, widths, mins, values_per_mini, count, bits, max_width
    )


@scoped_x64
def decode_delta_device(buf_dev: jax.Array, meta: DeltaMeta, bits: int) -> jax.Array:
    return _delta_jit(
        buf_dev,
        jnp.asarray(meta.first_value, dtype=jnp.int64),
        jnp.asarray(meta.mini_bit_starts),
        jnp.asarray(meta.mini_widths),
        jnp.asarray(meta.mini_min_delta),
        values_per_mini=meta.values_per_mini,
        count=meta.count,
        bits=bits,
        max_width=max(int(meta.mini_widths.max(initial=0)), 1),
    )


# ---------------------------------------------------------------------------
# Whole-chunk device decoder
# ---------------------------------------------------------------------------

_PTYPE_TO_NAME = {
    Type.INT32: "int32",
    Type.INT64: "int64",
    Type.FLOAT: "float32",
    Type.DOUBLE: "float64",
}


@dataclass
class ParsedDataPage:
    """Host-parsed data page: decompressed bytes + levels + defined count.

    The shared front half of both device decode paths (page-at-a-time
    DeviceChunkDecoder and the batched device_reader): CRC, decompression,
    host level decode, num_nulls validation.
    """

    raw: bytes            # decompressed page bytes (value stream at value_pos)
    value_pos: int
    num_values: int
    defined: int
    encoding: int
    def_levels: Optional[np.ndarray] = None
    rep_levels: Optional[np.ndarray] = None
    # raw RLE/bit-packed level streams as (source_buffer, start, size): the
    # batched reader stages THESE (run-dominated, tiny) and expands them on
    # device, instead of shipping the host-decoded uint32 arrays (4 bytes per
    # leaf slot per level — the dominant transfer on nested files)
    def_stream: Optional[tuple] = None
    rep_stream: Optional[tuple] = None
    # def-stream run tables from the decode_levels=False walk (native eq-count
    # gives `defined` without materializing levels); reused by _plan_levels
    def_meta: Optional["HybridMeta"] = None
    # lazily-decompressed value stream: (compressed_payload, codec, ulen).
    # Set by parse_data_page(lazy_decompress=True) on pages eligible for
    # device-side snappy expansion (PLAIN values, levels outside the
    # compressed region); then ``raw`` is b"" until materialize().  Consumers
    # that need host bytes call materialize(); the device-snappy planner
    # ships the compressed payload instead.
    comp: Optional[tuple] = None

    def materialize(self) -> bytes:
        if self.comp is not None:
            self.peek()
            self.comp = None
        return self.raw

    def peek(self) -> bytes:
        """Decompressed bytes WITHOUT dropping the compressed payload.

        The byte-array ship routes need both: the host walks length
        prefixes over the decompressed stream, but the LINK still carries
        the compressed payload (device-side expansion).  ``materialize()``
        keeps its drop-the-payload semantics for routes that commit to
        host bytes.
        """
        if self.comp is not None and len(self.raw) == 0:
            payload, codec, ulen = self.comp
            self.raw = decompress_block(payload, codec, ulen)
        return self.raw


def parse_data_page(
    ps: PageSlice, buf: bytes, codec: int, leaf: SchemaNode,
    validate_crc: bool = False, alloc=None, decode_levels: bool = True,
    lazy_decompress: bool = False,
) -> ParsedDataPage:
    """Parse one v1/v2 data page on host (no device work).

    With ``decode_levels=False`` (the batched reader) neither level array is
    host-decoded: rep streams are only *located* (the v1 length prefix gives
    the span without decoding), and def streams are header-walked with the
    native eq-counter (meta_parse.cpp want_eq) so the defined-value count —
    which gates every static decode shape — comes straight off the run walk;
    the run tables are kept on the page for the device-side expansion.
    Without the native library the def levels fall back to a host decode
    (the count has to come from somewhere).  The device-side
    *reconstruction* from levels (validity scatter, row starts) runs as
    prefix scans in jax_kernels.
    """
    header = ps.header
    payload = buf[ps.payload_start : ps.payload_end]
    _check_crc(header, payload, validate_crc)
    if alloc is not None:
        # register the REAL decompressed size before materializing it — the
        # chunk-level metadata totals are attacker-controlled and optional
        alloc.register(max(header.uncompressed_page_size or 0, 0))
    max_rep, max_def = leaf.max_rep, leaf.max_def
    if header.type == PageType.DATA_PAGE:
        dh = header.data_page_header
        num_values = dh.num_values or 0
        if num_values < 0:
            raise ParquetError(f"negative page value count {num_values}")
        if (lazy_decompress and max_rep == 0 and max_def == 0
                and parse_encoding(dh.encoding) == Encoding.PLAIN):
            # no levels inside the compressed region: the whole payload is
            # the PLAIN value stream — keep it compressed for device-side
            # expansion (materialize() restores the host bytes on demand)
            return ParsedDataPage(
                raw=b"", value_pos=0, num_values=num_values,
                defined=num_values, encoding=dh.encoding,
                comp=(payload, codec, max(header.uncompressed_page_size or 0,
                                          0)),
            )
        raw = decompress_block(payload, codec, header.uncompressed_page_size)
        pos = 0
        rlv = dlv = None
        rsp = dsp = None
        def_meta = None

        def _prefixed_span(p0):
            """v1 length prefix: locate the stream without decoding it."""
            if len(raw) - p0 < 4:
                raise ParquetError("truncated level stream length prefix")
            size = int.from_bytes(raw[p0 : p0 + 4], "little")
            if p0 + 4 + size > len(raw):
                raise ParquetError(f"level stream length {size} exceeds page")
            return size

        if max_rep > 0:
            if decode_levels:
                rlv, used = rle.decode_prefixed(
                    raw[pos:], bitpack.bit_width(max_rep), num_values
                )
            else:
                used = 4 + _prefixed_span(pos)
            rsp = (raw, pos + 4, used - 4)  # hybrid payload past the u32 size
            pos += used
        if max_def > 0:
            w = bitpack.bit_width(max_def)
            if decode_levels:
                dlv, used = rle.decode_prefixed(raw[pos:], w, num_values)
            else:
                size = _prefixed_span(pos)
                used = 4 + size
                def_meta = parse_hybrid_meta(
                    raw, w, num_values, pos=pos + 4, end=pos + 4 + size,
                    eq_target=max_def,
                )
                if def_meta.eq_count is None:  # no native walk: must decode
                    dlv, _ = rle.decode_prefixed(raw[pos:], w, num_values)
            dsp = (raw, pos + 4, used - 4)
            pos += used
        if def_meta is not None and def_meta.eq_count is not None:
            defined = def_meta.eq_count
        elif dlv is not None:
            defined = int(np.count_nonzero(dlv == max_def))
        else:
            defined = num_values
        return ParsedDataPage(
            raw=raw, value_pos=pos, num_values=num_values, defined=defined,
            encoding=dh.encoding, def_levels=dlv, rep_levels=rlv,
            def_stream=dsp, rep_stream=rsp, def_meta=def_meta,
        )

    dh = header.data_page_header_v2
    num_values = dh.num_values or 0
    if num_values < 0:
        raise ParquetError(f"negative page value count {num_values}")
    rep_len = dh.repetition_levels_byte_length or 0
    def_len = dh.definition_levels_byte_length or 0
    if rep_len < 0 or def_len < 0 or rep_len + def_len > len(payload):
        raise ParquetError("v2 level lengths exceed page")
    rlv = dlv = None
    rsp = dsp = None
    def_meta = None
    if max_rep > 0:
        if rep_len == 0:
            raise ParquetError("v2 page missing repetition levels")
        if decode_levels:
            rlv = rle.decode(payload[:rep_len], bitpack.bit_width(max_rep),
                             num_values)
        rsp = (payload, 0, rep_len)
    if max_def > 0:
        w = bitpack.bit_width(max_def)
        if decode_levels:
            dlv = rle.decode(
                payload[rep_len : rep_len + def_len], w, num_values
            )
        else:
            def_meta = parse_hybrid_meta(
                payload, w, num_values, pos=rep_len,
                end=rep_len + def_len, eq_target=max_def,
            )
            if def_meta.eq_count is None:  # no native walk: must decode
                dlv = rle.decode(
                    payload[rep_len : rep_len + def_len], w, num_values
                )
        dsp = (payload, rep_len, def_len)
    if def_meta is not None and def_meta.eq_count is not None:
        defined = def_meta.eq_count
    elif dlv is not None:
        defined = int(np.count_nonzero(dlv == max_def))
    else:
        defined = num_values
    if dh.num_nulls is not None and max_def > 0 and max_rep == 0:
        actual_nulls = num_values - defined
        if dh.num_nulls != actual_nulls:
            raise ParquetError(
                f"v2 page declares {dh.num_nulls} nulls, levels say {actual_nulls}"
            )
    values_block = payload[rep_len + def_len :]
    uncompressed_values = header.uncompressed_page_size - rep_len - def_len
    comp = None
    if dh.is_compressed is None or dh.is_compressed:
        if (lazy_decompress
                and parse_encoding(dh.encoding) == Encoding.PLAIN):
            # v2 keeps levels OUTSIDE the compressed region, so the value
            # block can stay compressed for device-side expansion
            raw, comp = b"", (values_block, codec,
                              max(uncompressed_values, 0))
        else:
            raw = decompress_block(values_block, codec, uncompressed_values)
    else:
        raw = values_block
    return ParsedDataPage(
        raw=raw, value_pos=0, num_values=num_values, defined=defined,
        encoding=dh.encoding, def_levels=dlv, rep_levels=rlv,
        def_stream=dsp, rep_stream=rsp, def_meta=def_meta, comp=comp,
    )


def host_decode_dictionary(raw: bytes, leaf: SchemaNode, encoding: int, count: int):
    """Decode a dictionary page's values on host.

    Returns ByteArrayData for ragged dictionaries, else (u8_rows, dtype_name, n)
    — the byte-row staging form dict_gather_bytes consumes.
    """
    from .kernels import plain as plain_host

    enc = parse_encoding(encoding, "dictionary page encoding")
    if enc not in (Encoding.PLAIN, Encoding.PLAIN_DICTIONARY):
        raise ParquetError(f"dictionary page encoding {enc.name} unsupported")
    if count < 0:
        raise ParquetError(f"negative dictionary size {count}")
    decoded = plain_host.decode(raw, leaf.physical_type, count, leaf.type_length)
    if isinstance(decoded, ByteArrayData):
        return decoded
    arr = np.ascontiguousarray(decoded)
    n = len(arr)
    row_bytes = (arr.nbytes // n) if n else arr.dtype.itemsize
    base = arr.dtype.name if arr.ndim == 1 else "uint32"  # INT96: (n,3) u32
    u8 = (
        arr.view(np.uint8).reshape(n, row_bytes)
        if n else np.zeros((0, row_bytes), dtype=np.uint8)
    )
    return u8, base, n


# The value stream starts at a page-dependent byte offset inside the staged
# page buffer; the offset is a *traced* scalar so one executable serves every
# page of the same (dtype, count) geometry — no recompile, no re-staging.

@functools.partial(jax.jit, static_argnames=("dtype", "count"))
def _plain_jit(buf, off, *, dtype, count):
    nbytes = 8 if dtype in ("int64", "float64") else 4
    raw = jax.lax.dynamic_slice(buf, (off,), (count * nbytes,))
    return K.plain_decode_fixed(raw, dtype, count)


@functools.partial(jax.jit, static_argnames=("k", "count"))
def _plain_rows_jit(buf, off, *, k, count):
    """PLAIN INT96 rows: 12-byte rows bitcast to little-endian u32[count, 3]
    (the host decoder's layout)."""
    raw = jax.lax.dynamic_slice(buf, (off,), (count * k,))
    return jax.lax.bitcast_convert_type(
        raw.reshape(count, k // 4, 4), jnp.uint32
    ).reshape(count, k // 4)


@functools.partial(jax.jit, static_argnames=("k", "count"))
def _plain_flba_jit(buf, off, *, k, count):
    """PLAIN FIXED_LEN_BYTE_ARRAY: uniform (offsets, heap) ragged form —
    the host decoder's representation (kernels/plain.py FLBA)."""
    heap = jax.lax.dynamic_slice(buf, (off,), (count * k,))
    offsets = jnp.arange(count + 1, dtype=jnp.int64) * k
    return offsets, heap


@functools.partial(jax.jit, static_argnames=("dtype", "count"))
def _bss_jit(buf, off, *, dtype, count):
    nbytes = 8 if dtype in ("int64", "float64") else 4
    raw = jax.lax.dynamic_slice(buf, (off,), (count * nbytes,))
    return K.byte_stream_split_decode(raw, dtype, count)


@functools.partial(jax.jit, static_argnames=("count",))
def _bool_plain_jit(buf, off, *, count):
    bit_pos = off.astype(jnp.int64) * 8 + jnp.arange(count, dtype=jnp.int64)
    return K.extract_bits(buf, bit_pos, 1, 1).astype(jnp.bool_)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dict_gather_bytes_jit(dict_u8, indices, *, dtype):
    return K.dict_gather_bytes(dict_u8, indices, dtype)


@functools.partial(jax.jit, static_argnames=("k", "itemsize"))
def _dict_rows_jit(buf, base, *, k, itemsize):
    """Cut a dictionary's (k, itemsize) u8 rows out of the staged buffer.

    The dictionary bytes ride the one row-group transfer instead of a
    separate jnp.asarray per chunk (each such transfer costs a fixed
    transfer round trip); this on-device slice is an async dispatch.
    ``k`` is bucketed, and the caller MUST stage the dictionary with a
    zero-filled reserve covering k*itemsize (stager.add(..., reserve=...)):
    on the deferred range-check path, clamped out-of-range indices DO gather
    the tail rows before validation resolves, and they must read as zeros —
    never a neighboring chunk's staged bytes (see device_reader._finish_dict).
    """
    return jax.lax.dynamic_slice(buf, (base,), (k * itemsize,)).reshape(
        k, itemsize
    )


@functools.partial(jax.jit, static_argnames=("out_heap_size",))
def _ragged_take_jit(offsets, heap, indices, *, out_heap_size):
    return K.ragged_take(offsets, heap, indices, out_heap_size)


# Eager (non-jit) ops are poison on a TPU backend: the FIRST dispatch of every
# distinct eager op/shape pays a full XLA compile (~0.7-3s measured on the
# early remote backend), so even a handful of stray jnp.max / slice /
# concatenate calls in the
# decode path dwarfs the actual decode.  Everything below keeps those tail ops
# inside jit; np scalars and np.zeros feed jit/device_put directly so no eager
# broadcast is ever dispatched.

_max_jit = jax.jit(jnp.max)


@jax.jit
def _concat_jit(parts):
    return jnp.concatenate(parts)


@jax.jit
def _concat_ragged_jit(offs, heaps):
    """Concatenate per-page (offsets, heap) pairs into one ragged column.

    Offsets are rebased by the running heap length entirely on device — no
    host sync on the per-page heap sizes.
    """
    out_offs = [offs[0]]
    base = offs[0][-1]
    for o in offs[1:]:
        out_offs.append(o[1:] + base)
        base = base + o[-1]
    return jnp.concatenate(out_offs), jnp.concatenate(heaps)


@functools.partial(jax.jit, static_argnames=("size",))
def _slice_jit(x, *, size):
    return x[:size]


@jax.jit
def _stack_jit(xs):
    # stack deferred-check scalars on device so syncing them costs ONE host
    # transfer: every transfer pays a full round trip,
    # and jax.device_get fetches list leaves one by one
    return jnp.stack(xs)


@dataclass
class DeviceColumnData:
    """Decoded column chunk resident on device.

    Fixed-width: ``values`` is a jax Array of the defined values.  BYTE_ARRAY:
    ``offsets``/``heap`` hold the ragged representation on device instead.
    Levels (when present) are device uint32 arrays, one per leaf slot.
    """

    values: Optional[jax.Array] = None
    offsets: Optional[jax.Array] = None
    heap: Optional[jax.Array] = None
    def_levels: Optional[jax.Array] = None
    rep_levels: Optional[jax.Array] = None
    max_def: int = 0
    max_rep: int = 0
    num_leaf_slots: int = 0
    # logical dtype when the device representation differs: DOUBLE columns are
    # uint32[n,2] word pairs on device (TPU f64 emulation rounds real f64 data —
    # see jax_kernels.plain_decode_fixed) and only become f64 on the host.
    value_dtype: Optional[str] = None
    # Number of REAL defined values; device arrays may be padded past it to a
    # bucketed static shape (executable sharing across chunks — _bucket_count).
    # None means the arrays are exact.  Level arrays may likewise be padded
    # past num_leaf_slots.  A jitted consumer *wants* the bucketed shapes (it
    # recompiles per shape); host materialization slices the padding off.
    n_values: Optional[int] = None

    @property
    def num_values(self) -> int:
        """Real defined-value count (excludes bucketing pad and nulls)."""
        if self.n_values is not None:
            return self.n_values
        if self.values is not None:
            return int(self.values.shape[0])
        if self.offsets is not None:
            return max(int(self.offsets.shape[0]) - 1, 0)
        return 0

    def validity(self) -> jax.Array:
        if self.def_levels is None:
            return jnp.ones(self.num_leaf_slots, dtype=bool)
        # def_levels may be bucket-padded; tail lanes are garbage, so the
        # mask must stop at the real slot count
        return K.levels_to_validity(
            self.def_levels, self.max_def
        )[: self.num_leaf_slots]

    def levels_to_host(self):
        """(def_levels, rep_levels) as exact host arrays (padding sliced)."""
        n = self.num_leaf_slots
        d = None if self.def_levels is None else np.asarray(self.def_levels)[:n]
        r = None if self.rep_levels is None else np.asarray(self.rep_levels)[:n]
        return d, r

    def to_host(self) -> "ByteArrayData | np.ndarray":
        n = self.num_values
        if self.offsets is not None:
            off = np.asarray(self.offsets)[: n + 1]
            heap = np.asarray(self.heap)
            if len(off) and heap.nbytes > off[-1]:
                heap = heap[: off[-1]]  # drop bucketed staging padding
            return ByteArrayData(offsets=off, heap=heap)
        vals = np.asarray(self.values)[:n]
        if self.value_dtype == "float64" and vals.ndim == 2:
            return np.ascontiguousarray(vals).view("<f8").reshape(len(vals))
        return vals


class DeviceChunkDecoder:
    """Decode one column chunk into device-resident arrays.

    Mirrors chunk_decode.ChunkDecoder page-for-page; falls back to the host
    kernels only for the sequential byte-array paths (PLAIN/DELTA BYTE_ARRAY
    value streams), shipping their (offsets, heap) results to device.
    """

    def __init__(self, leaf: SchemaNode, validate_crc: bool = False,
                 context: "dict | None" = None):
        self.leaf = leaf
        self.validate_crc = validate_crc
        self.context = dict(context or {})
        self.dict_u8: Optional[jax.Array] = None           # fixed-width dict, u8 rows
        self.dict_dtype: Optional[str] = None              # target dtype name
        self.dict_len: int = 0
        self.dict_offsets: Optional[jax.Array] = None      # ragged dict
        self.dict_heap: Optional[jax.Array] = None
        self._dict_host_offsets: Optional[np.ndarray] = None
        self._idx_maxima: list = []  # per-page device max dict index, checked per chunk

    # -- dictionary ----------------------------------------------------------

    def _decode_dict_page(self, ps: PageSlice, buf: bytes, codec: int) -> None:
        header = ps.header
        payload = buf[ps.payload_start : ps.payload_end]
        _check_crc(header, payload, self.validate_crc)
        raw = decompress_block(payload, codec, header.uncompressed_page_size)
        dh = header.dictionary_page_header
        decoded = host_decode_dictionary(
            raw, self.leaf, dh.encoding, dh.num_values or 0
        )
        if isinstance(decoded, ByteArrayData):
            self._dict_host_offsets = decoded.offsets
            self.dict_offsets = jnp.asarray(decoded.offsets)
            self.dict_heap = jnp.asarray(decoded.heap)
            self.dict_len = len(decoded)
        else:
            # raw byte rows: gathers must move bits verbatim, and u8[...,k]→wide
            # bitcasts are the only ones TPU's X64 pass supports
            u8, base, n = decoded
            self.dict_u8 = jnp.asarray(u8)
            self.dict_dtype = base
            self.dict_len = n

    # -- values --------------------------------------------------------------

    def _decode_values_device(self, enc: int, raw: bytes, pos: int, count: int):
        """Decode the value stream at byte offset ``pos`` of page bytes ``raw``.

        Returns (values_array, offsets, heap) — exactly one representation set.
        ``raw`` is staged to device at most once; all kernels address into it
        with byte/bit offsets instead of re-staging slices.
        """
        ptype = self.leaf.physical_type
        avail = len(raw) - pos
        enc = parse_encoding(enc)
        if enc == Encoding.PLAIN_DICTIONARY:
            enc = Encoding.RLE_DICTIONARY

        if enc == Encoding.PLAIN:
            if ptype == Type.BOOLEAN:
                need = (count + 7) // 8
                if avail < need:
                    raise ParquetError(f"PLAIN BOOLEAN truncated: {avail} < {need}")
                return (
                    _bool_plain_jit(
                        pad_buffer(raw), np.int64(pos), count=count
                    ),
                    None,
                    None,
                )
            name = _PTYPE_TO_NAME.get(ptype)
            if name is not None:
                need = count * np.dtype(name).itemsize
                if avail < need:
                    raise ParquetError(f"PLAIN data truncated: {avail} < {need}")
                return (
                    _plain_jit(pad_buffer(raw), np.int64(pos), dtype=name, count=count),
                    None,
                    None,
                )
            # INT96 / BYTE_ARRAY / FIXED: host parse, device-stage result
            from .kernels import plain as plain_host

            decoded = plain_host.decode(raw[pos:], ptype, count, self.leaf.type_length)
            if isinstance(decoded, ByteArrayData):
                return None, jnp.asarray(decoded.offsets), jnp.asarray(decoded.heap)
            return jnp.asarray(decoded), None, None

        if enc == Encoding.RLE_DICTIONARY:
            if self.dict_u8 is None and self.dict_offsets is None:
                raise ParquetError("dictionary-encoded page but no dictionary page seen")
            if avail < 1:
                raise ParquetError("dictionary page data truncated (missing width)")
            width = int(raw[pos])
            if width > 32:
                raise ParquetError(f"dictionary index width {width} invalid")
            meta = parse_hybrid_meta(raw, width, count, pos=pos + 1,
                                     compute_max=True)
            idx = decode_hybrid_device(pad_buffer(raw), meta, width)
            if self.dict_u8 is not None:
                if count and self.dict_len == 0:
                    raise ParquetError("dictionary indices with empty dictionary")
                # range check: on host when the native walk reported the max;
                # otherwise deferred to the end of the chunk (decode()) as one
                # on-device max + one sync
                if count and meta.max_value is not None:
                    if meta.max_value >= self.dict_len:
                        raise ParquetError(
                            f"dictionary index {meta.max_value} out of range "
                            f"({self.dict_len})"
                        )
                elif count:
                    self._idx_maxima.append(_max_jit(idx))
                return (
                    _dict_gather_bytes_jit(self.dict_u8, idx, dtype=self.dict_dtype),
                    None,
                    None,
                )
            # ragged dictionary: need output heap size on host
            host_idx = np.asarray(idx, dtype=np.int64)
            off = self._dict_host_offsets
            if count and host_idx.max(initial=0) >= len(off) - 1:
                raise ParquetError(
                    f"dictionary index {int(host_idx.max())} out of range ({len(off) - 1})"
                )
            out_heap = int((off[host_idx + 1] - off[host_idx]).sum())
            new_off, new_heap = _ragged_take_jit(
                self.dict_offsets, self.dict_heap, idx,
                out_heap_size=_bucket_bytes(max(out_heap, 1), 64),
            )
            if not out_heap:
                return None, new_off, jnp.asarray(np.zeros(0, dtype=np.uint8))
            return None, new_off, _slice_jit(new_heap, size=out_heap)

        if enc == Encoding.DELTA_BINARY_PACKED:
            bits = 32 if ptype == Type.INT32 else 64
            if ptype not in (Type.INT32, Type.INT64):
                raise ParquetError(f"DELTA_BINARY_PACKED invalid for {ptype!r}")
            meta = parse_delta_meta(raw, bits, pos=pos)
            if meta.count < count:
                raise ParquetError(f"delta stream yielded {meta.count} of {count} values")
            vals = decode_delta_device(pad_buffer(raw), meta, bits)
            if meta.count == count:
                return vals, None, None
            return _slice_jit(vals, size=count), None, None

        if enc == Encoding.BYTE_STREAM_SPLIT:
            name = _PTYPE_TO_NAME.get(ptype)
            if name is None:
                # FIXED_LEN_BYTE_ARRAY etc.: host decode, stage the result
                # (same fallback pattern as the sequential byte-array paths)
                from .chunk_decode import _byte_stream_split_decode

                decoded = _byte_stream_split_decode(
                    raw[pos:], ptype, count, self.leaf.type_length
                )
                if isinstance(decoded, ByteArrayData):
                    return None, jnp.asarray(decoded.offsets), jnp.asarray(decoded.heap)
                return jnp.asarray(decoded), None, None
            need = count * np.dtype(name).itemsize
            if avail < need:
                raise ParquetError(f"BYTE_STREAM_SPLIT truncated: {avail} < {need}")
            return (
                _bss_jit(pad_buffer(raw), np.int64(pos), dtype=name, count=count),
                None,
                None,
            )

        if enc == Encoding.RLE:
            if ptype != Type.BOOLEAN:
                raise ParquetError(f"RLE value encoding invalid for {ptype!r}")
            if avail < 4:
                raise ParquetError("truncated boolean RLE stream")
            size = int.from_bytes(raw[pos : pos + 4], "little")
            if pos + 4 + size > len(raw):
                raise ParquetError(f"boolean RLE length {size} exceeds page")
            meta = parse_hybrid_meta(raw, 1, count, pos=pos + 4, end=pos + 4 + size)
            vals = decode_hybrid_device(pad_buffer(raw), meta, 1)
            return vals.astype(jnp.bool_), None, None

        # DELTA_LENGTH_BYTE_ARRAY / DELTA_BYTE_ARRAY: host decode, stage result
        from .kernels import bytearray as ba_host

        if enc == Encoding.DELTA_LENGTH_BYTE_ARRAY:
            d = ba_host.decode_delta_length(raw[pos:], count)
            return None, jnp.asarray(d.offsets), jnp.asarray(d.heap)
        if enc == Encoding.DELTA_BYTE_ARRAY:
            d = ba_host.decode_delta(raw[pos:], count)
            return None, jnp.asarray(d.offsets), jnp.asarray(d.heap)
        raise ParquetError(f"unsupported value encoding {enc.name} for {ptype!r}")

    # -- pages ---------------------------------------------------------------

    def _decode_data_page(self, ps: PageSlice, buf: bytes, codec: int):
        """Shared host parse (parse_data_page) + device value decode."""
        p = parse_data_page(ps, buf, codec, self.leaf, self.validate_crc)
        v, off, heap = self._decode_values_device(
            p.encoding, p.raw, p.value_pos, p.defined
        )
        dlv = jnp.asarray(p.def_levels) if p.def_levels is not None else None
        rlv = jnp.asarray(p.rep_levels) if p.rep_levels is not None else None
        return v, off, heap, dlv, rlv, p.num_values

    # -- chunk ---------------------------------------------------------------

    @scoped_x64
    def decode(self, buf: bytes, codec: int, total_values: int) -> DeviceColumnData:
        from .quarantine import error_context

        ctx = dict(self.context)
        if "column" not in ctx and self.leaf.path:
            ctx["column"] = ".".join(self.leaf.path)
        # absolute file offsets in the records, matching the host paths'
        # (a ledger offset an operator seeks to must be the page's, not a
        # chunk-relative one)
        chunk_offset = ctx.pop("chunk_offset", 0) or 0
        with error_context(**ctx):
            pages = walk_pages(buf, total_values)
        vals_parts, off_parts, heap_parts = [], [], []
        def_parts, rep_parts = [], []
        slots = 0
        page_ordinal = 0
        self._idx_maxima = []
        for ps in pages:
            pt = ps.header.type
            if pt == PageType.DICTIONARY_PAGE:
                with error_context(offset=chunk_offset + ps.payload_start,
                                   **ctx):
                    self._decode_dict_page(ps, buf, codec)
                continue
            if pt in (PageType.DATA_PAGE, PageType.DATA_PAGE_V2):
                with error_context(page=page_ordinal,
                                   offset=chunk_offset + ps.payload_start,
                                   **ctx):
                    v, off, heap, d, r, n = self._decode_data_page(
                        ps, buf, codec)
                page_ordinal += 1
            else:
                continue
            slots += n
            if v is not None:
                vals_parts.append(v)
            else:
                off_parts.append(off)
                heap_parts.append(heap)
            if d is not None:
                def_parts.append(d)
            if r is not None:
                rep_parts.append(r)

        if self._idx_maxima:
            mx = int(np.asarray(_stack_jit(self._idx_maxima)).max())
            if mx >= self.dict_len:
                raise ParquetError(
                    f"dictionary index {mx} out of range ({self.dict_len})"
                )

        out = DeviceColumnData(
            max_def=self.leaf.max_def,
            max_rep=self.leaf.max_rep,
            num_leaf_slots=slots,
            value_dtype=(
                "float64" if self.leaf.physical_type == Type.DOUBLE else None
            ),
        )
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
        if def_parts:
            out.def_levels = (
                def_parts[0] if len(def_parts) == 1 else _concat_jit(def_parts)
            )
        if rep_parts:
            out.rep_levels = (
                rep_parts[0] if len(rep_parts) == 1 else _concat_jit(rep_parts)
            )
        return out


@scoped_x64
def read_chunk_device(
    f, chunk, leaf: SchemaNode, validate_crc: bool = False
) -> DeviceColumnData:
    """Device twin of chunk_decode.read_chunk (same seek/size/meta discipline)."""
    md, offset = validate_chunk_meta(chunk, leaf)
    f.seek(offset)
    buf = f.read(md.total_compressed_size)
    if len(buf) != md.total_compressed_size:
        raise ParquetError(
            f"chunk truncated: wanted {md.total_compressed_size} bytes at {offset}, "
            f"got {len(buf)}"
        )
    dec = DeviceChunkDecoder(leaf, validate_crc=validate_crc,
                             context={"chunk_offset": offset})
    return dec.decode(buf, md.codec, md.num_values)
