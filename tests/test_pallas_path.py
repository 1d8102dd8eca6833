"""Production Pallas decode path: A/B parity against the XLA extract path.

The batched reader routes uniform-width hybrid streams (dictionary indices,
def/rep levels) through pallas_kernels.unpack_bp_groups when TPQ_PALLAS=1 (or
natively on TPU).  On the CPU test backend the kernel runs through the Pallas
interpreter — slow but bit-exact — so these tests decode every file twice and
require identical output.  Reference semantics: hybrid_decoder.go:81-165.
"""

import numpy as np
import pytest

from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
from tpu_parquet.kernels import bitpack, rle
from tpu_parquet.schema.core import build_schema, data_column
from tpu_parquet.writer import FileWriter


@pytest.mark.parametrize("width", [1, 2, 3, 7, 8, 13, 17, 24, 32])
def test_unpack_bp_groups_matches_host_unpack(width):
    import jax.numpy as jnp

    from tpu_parquet.pallas_kernels import (
        bp_groups_pad, bp_value_index, unpack_bp_groups,
    )

    rng = np.random.default_rng(width)
    n = 5000
    vals = rng.integers(0, 1 << min(width, 32), n, dtype=np.uint64)
    packed = np.frombuffer(bitpack.pack(vals, width), np.uint8)
    groups = -(-n // 8)
    gpad = bp_groups_pad(groups)
    buf = np.zeros(gpad * width + 64, dtype=np.uint8)
    buf[: packed.nbytes] = packed
    out = unpack_bp_groups(jnp.asarray(buf), 0, width, gpad, interpret=True)
    # value-major output: value j of group g at j * gpad + g
    got = np.asarray(out)[bp_value_index(np.arange(n), gpad)]
    got = got.astype(np.uint64)
    np.testing.assert_array_equal(got, vals)


def test_unpack_bp_groups_nonzero_base():
    import jax.numpy as jnp

    from tpu_parquet.pallas_kernels import (
        bp_groups_pad, bp_value_index, unpack_bp_groups,
    )

    rng = np.random.default_rng(0)
    n, width = 4096, 11
    vals = rng.integers(0, 1 << width, n, dtype=np.uint64)
    packed = np.frombuffer(bitpack.pack(vals, width), np.uint8)
    base = 192  # 64-aligned staging offset
    gpad = bp_groups_pad(-(-n // 8))
    buf = np.zeros(base + gpad * width + 64, dtype=np.uint8)
    buf[base : base + packed.nbytes] = packed
    out = unpack_bp_groups(jnp.asarray(buf), base, width, gpad, interpret=True)
    got = np.asarray(out)[bp_value_index(np.arange(n), gpad)]
    np.testing.assert_array_equal(got.astype(np.uint64), vals)


def _mixed_run_values(rng, n, card):
    """Index stream with long repeated spans: forces RLE *and* BP runs."""
    vals = rng.integers(0, card, n, dtype=np.uint32)
    for x in rng.integers(0, max(n - 600, 1), 8):
        vals[x : x + 500] = vals[x]
    return vals


def _decode_both_ways(path, monkeypatch, columns=None):
    from tpu_parquet.device_reader import DeviceFileReader

    outs = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("TPQ_PALLAS", mode)
        cols = {}
        with DeviceFileReader(path, columns=columns) as r:
            for got in r.iter_row_groups():
                for k, v in got.items():
                    cols.setdefault(k, []).append(v)
        outs[mode] = cols
    return outs["0"], outs["1"]


def _assert_cols_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        assert len(a[k]) == len(b[k])
        for ca, cb in zip(a[k], b[k]):
            ha, hb = ca.to_host(), cb.to_host()
            if hasattr(ha, "offsets"):
                np.testing.assert_array_equal(ha.offsets, hb.offsets)
                np.testing.assert_array_equal(ha.heap, hb.heap)
            else:
                np.testing.assert_array_equal(ha, hb)
            da, _ = ca.levels_to_host()
            db, _ = cb.levels_to_host()
            if da is not None or db is not None:
                np.testing.assert_array_equal(da, db)


def test_dict_indices_pallas_parity(tmp_path, monkeypatch):
    """Dictionary column with mixed RLE/BP index runs decodes identically."""
    path = str(tmp_path / "dict.parquet")
    rng = np.random.default_rng(1)
    schema = build_schema([data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED)])
    pool = [f"val_{i:03d}".encode() for i in range(700)]
    idx = _mixed_run_values(rng, 60_000, len(pool))
    from tpu_parquet.column import ByteArrayData, ColumnData

    lens = np.array([len(pool[i]) for i in idx])
    offs = np.zeros(len(idx) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    heap = np.frombuffer(b"".join(pool[i] for i in idx), dtype=np.uint8).copy()
    with FileWriter(path, schema, codec=CompressionCodec.SNAPPY,
                    use_dictionary=True, page_size=16 << 10) as w:
        w.write_columns({"s": ColumnData(values=ByteArrayData(offsets=offs,
                                                              heap=heap))})
    xla, pallas = _decode_both_ways(path, monkeypatch)
    _assert_cols_equal(xla, pallas)


def test_levels_pallas_parity(tmp_path, monkeypatch):
    """Nullable column: def-level streams expand identically on both paths."""
    path = str(tmp_path / "nulls.parquet")
    rng = np.random.default_rng(2)
    schema = build_schema([data_column("v", Type.INT64, FRT.OPTIONAL)])
    n = 50_000
    vals = rng.integers(-1000, 1000, n)
    mask = rng.random(n) < 0.3
    # long all-null and all-present spans: RLE level runs next to BP ones
    mask[1000:3000] = True
    mask[10_000:14_000] = False
    from tpu_parquet.column import ColumnData

    col = ColumnData(
        values=vals[~mask].astype(np.int64),
        def_levels=(~mask).astype(np.uint32),
        max_def=1,
    )
    with FileWriter(path, schema, codec=CompressionCodec.UNCOMPRESSED,
                    page_size=8 << 10) as w:
        w.write_columns({"v": col})
    xla, pallas = _decode_both_ways(path, monkeypatch)
    _assert_cols_equal(xla, pallas)


def test_pallas_default_off_on_cpu(monkeypatch):
    """Without TPQ_PALLAS=1 the CPU backend keeps the XLA path (no
    interpreter in production), and TPQ_PALLAS=0 forces it off everywhere."""
    from tpu_parquet.device_reader import _pallas_interpret_mode

    monkeypatch.delenv("TPQ_PALLAS", raising=False)
    assert _pallas_interpret_mode() is None  # CPU conftest backend
    monkeypatch.setenv("TPQ_PALLAS", "0")
    assert _pallas_interpret_mode() is None
    monkeypatch.setenv("TPQ_PALLAS", "1")
    assert _pallas_interpret_mode() is True


def test_interpret_mode_is_an_error_on_tpu(monkeypatch):
    """The interpreter is the CPU's Pallas path; on a TPU every kernel
    compiles natively, and asking for the interpreter there raises."""
    from tpu_parquet import pallas_kernels as pk

    assert pk.resolve_interpret(None) is True  # CPU conftest backend
    assert pk.resolve_interpret(False) is False
    monkeypatch.setattr(pk, "pallas_available", lambda: True)
    assert pk.resolve_interpret(None) is False
    with pytest.raises(RuntimeError, match="interpret"):
        pk.resolve_interpret(True)


def test_pallas_plan_declines_pathological_runs(tmp_path, monkeypatch):
    """A stream shattered into tiny alternating runs must fall back (and
    still decode correctly) — the segment-copy guard, not an error path."""
    monkeypatch.setenv("TPQ_PALLAS", "1")
    import jax.numpy as jnp

    from tpu_parquet.device_reader import (
        _PALLAS_MAX_SEGS, _RowGroupStager, _plan_hybrid_pallas,
    )
    from tpu_parquet.jax_decode import parse_hybrid_meta

    # alternating 8-value BP runs and RLE runs, enough to trip the guard
    width = 4
    parts = []
    n_pairs = _PALLAS_MAX_SEGS + 8
    for _ in range(n_pairs):
        parts.append(bytes([(1 << 1) | 1]) + bytes(width))  # 1-group BP run
        parts.append(bytes([16 << 1, 5]))  # RLE run: 16 copies of 5
    stream = b"".join(parts)
    count = n_pairs * 24
    meta = parse_hybrid_meta(stream, width, count, pos=0)
    stager = _RowGroupStager()
    plan = _plan_hybrid_pallas(stager, [(meta, stream, count)], width, count,
                               count, True)
    assert plan is None  # guard declined; callers use the XLA path


def test_streaming_stager_multi_strip_parity(tmp_path, monkeypatch):
    """Strip-streamed staging (iter_row_groups worker) assembles the same
    device buffer as the single-transfer path: shrink the strip size so a
    small file crosses many strip boundaries, decode both ways, compare."""
    from tpu_parquet.column import ColumnData
    from tpu_parquet.device_reader import DeviceFileReader, _RowGroupStager

    path = str(tmp_path / "strips.parquet")
    rng = np.random.default_rng(3)
    schema = build_schema([
        data_column("a", Type.INT64, FRT.REQUIRED),
        data_column("b", Type.INT32, FRT.REQUIRED),
    ])
    n = 200_000
    with FileWriter(path, schema, codec=CompressionCodec.SNAPPY) as w:
        w.write_columns({
            "a": ColumnData(values=rng.integers(-(1 << 62), 1 << 62, n)),
            "b": ColumnData(values=rng.integers(0, 1 << 30, n).astype(np.int32)),
        })

    def scan():
        cols = {}
        with DeviceFileReader(path) as r:
            for got in r.iter_row_groups():
                for k, v in got.items():
                    cols.setdefault(k, []).append(v.to_host())
        return cols

    ref = scan()  # strips never trip (file << 16 MiB)
    monkeypatch.setattr(_RowGroupStager, "STRIP", 1 << 16)
    got = scan()  # dozens of strips + tail
    assert set(ref) == set(got)
    for k in ref:
        for a, b in zip(ref[k], got[k]):
            np.testing.assert_array_equal(a, b)
