"""Batched DeviceFileReader vs host FileReader: bit-for-bit differential.

Same oracle strategy as test_jax_decode.py, but through the fused per-chunk
path (one staged buffer + one dispatch per chunk, deferred checks).
"""

import io

import numpy as np
import pytest

from tpu_parquet.column import ByteArrayData
from tpu_parquet.device_reader import DeviceDictColumn, DeviceFileReader
from tpu_parquet.format import (
    CompressionCodec,
    ConvertedType,
    Encoding,
    FieldRepetitionType as FRT,
    LogicalType,
    StringType,
    Type,
)
from tpu_parquet.reader import FileReader
from tpu_parquet.schema.core import (
    ColumnParameters,
    build_schema,
    data_column,
    list_column,
)
from tpu_parquet.writer import FileWriter

RNG = np.random.default_rng(23)


def _string_col(name, repetition=FRT.OPTIONAL):
    return data_column(
        name, Type.BYTE_ARRAY, repetition,
        ColumnParameters(
            logical_type=LogicalType(STRING=StringType()),
            converted_type=ConvertedType.UTF8,
        ),
    )


def _compare_file(buf_bytes):
    host = FileReader(io.BytesIO(buf_bytes))
    dev = DeviceFileReader(io.BytesIO(buf_bytes))
    for i in range(host.num_row_groups):
        h_cols = host.read_row_group(i)
        d_cols = dev.read_row_group(i)
        assert set(h_cols) == set(d_cols)
        for name, h in h_cols.items():
            d = d_cols[name]
            got = d.to_host()
            if isinstance(h.values, ByteArrayData):
                assert isinstance(got, ByteArrayData), name
                np.testing.assert_array_equal(
                    got.offsets, h.values.offsets, err_msg=name
                )
                np.testing.assert_array_equal(got.heap, h.values.heap, err_msg=name)
            else:
                gv = got
                if h.values.dtype == np.bool_:
                    gv = gv.astype(np.bool_)
                if h.values.dtype.kind == "f":
                    np.testing.assert_array_equal(
                        np.ascontiguousarray(gv).view(np.uint8),
                        np.ascontiguousarray(h.values).view(np.uint8),
                        err_msg=name,
                    )
                else:
                    np.testing.assert_array_equal(gv, h.values, err_msg=name)
            d_def, d_rep = d.levels_to_host()
            for lvl, dl in (("def_levels", d_def), ("rep_levels", d_rep)):
                hl = getattr(h, lvl)
                assert (hl is None) == (dl is None), (name, lvl)
                if hl is not None:
                    np.testing.assert_array_equal(dl, hl, err_msg=name)
    host.close()
    dev.close()


def _write(schema, rows, **kw):
    buf = io.BytesIO()
    with FileWriter(buf, schema, **kw) as w:
        w.write_rows(rows)
    return buf.getvalue()


def _mixed_schema():
    return build_schema([
        data_column("id", Type.INT64, FRT.REQUIRED),
        data_column("x", Type.INT32, FRT.OPTIONAL),
        data_column("score", Type.DOUBLE, FRT.OPTIONAL),
        data_column("ratio", Type.FLOAT, FRT.REQUIRED),
        data_column("active", Type.BOOLEAN, FRT.REQUIRED),
        _string_col("name"),
    ])


def _mixed_rows(n):
    return [
        {
            "id": i * 3 - 1000,
            "x": None if i % 7 == 0 else i % 1000,
            "score": None if i % 11 == 0 else RNG.standard_normal(),
            "ratio": float(i % 13) * 0.5,
            "active": i % 2 == 0,
            "name": f"name-{i % 300}".encode(),
        }
        for i in range(n)
    ]


@pytest.mark.parametrize("codec", [
    CompressionCodec.UNCOMPRESSED, CompressionCodec.SNAPPY,
    CompressionCodec.ZSTD,
])
def test_batched_reader_codecs(codec):
    from conftest import require_codec

    require_codec(codec)
    _compare_file(_write(_mixed_schema(), _mixed_rows(2000), codec=codec))


@pytest.mark.parametrize("version", [1, 2])
def test_batched_reader_page_versions(version):
    _compare_file(
        _write(_mixed_schema(), _mixed_rows(2000), data_page_version=version)
    )


def test_batched_reader_multi_page_multi_rowgroup():
    # small pages + small row groups: concat + global run tables + multi-RG
    _compare_file(_write(
        _mixed_schema(), _mixed_rows(5000),
        page_size=2048, row_group_size=64 << 10,
    ))


def test_batched_reader_delta():
    schema = build_schema([
        data_column("i32", Type.INT32, FRT.REQUIRED),
        data_column("i64", Type.INT64, FRT.REQUIRED),
    ])
    rows = [
        {"i32": int(a), "i64": int(b)}
        for a, b in zip(
            RNG.integers(-(1 << 30), 1 << 30, 5000),
            RNG.integers(-(1 << 62), 1 << 62, 5000),
        )
    ]
    _compare_file(_write(
        schema, rows, use_dictionary=False, page_size=4096,
        column_encodings={"i32": Encoding.DELTA_BINARY_PACKED,
                          "i64": Encoding.DELTA_BINARY_PACKED},
    ))


def test_batched_reader_plain_no_dict():
    schema = build_schema([
        data_column("a", Type.INT64, FRT.REQUIRED),
        data_column("b", Type.DOUBLE, FRT.REQUIRED),
        data_column("c", Type.BOOLEAN, FRT.REQUIRED),
    ])
    rows = [
        {"a": i, "b": RNG.standard_normal(), "c": i % 3 == 0}
        for i in range(4000)
    ]
    _compare_file(_write(schema, rows, use_dictionary=False, page_size=4096))


def test_batched_reader_nested():
    schema = build_schema([
        list_column("tags", data_column("element", Type.INT64, FRT.OPTIONAL)),
        _string_col("label"),
    ])
    rows = []
    for i in range(2000):
        tags = (
            None if i % 13 == 0 else []
            if i % 7 == 0 else [int(j) if j % 3 else None for j in range(i % 6)]
        )
        rows.append({
            "tags": tags,
            "label": None if i % 5 == 0 else f"L{i % 40}".encode(),
        })
    _compare_file(_write(schema, rows, page_size=2048))


def test_dict_column_stays_encoded():
    """Fixed-width dict columns come back as DeviceDictColumn; materialize
    gathers on device and matches."""
    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    rows = [{"v": int(v)} for v in RNG.integers(0, 50, 3000)]
    data = _write(schema, rows)
    dev = DeviceFileReader(io.BytesIO(data))
    col = dev.read_row_group(0)["v"]
    assert isinstance(col, DeviceDictColumn)
    mat = col.materialize()
    host = FileReader(io.BytesIO(data)).read_row_group(0)["v"]
    np.testing.assert_array_equal(mat.to_host(), host.values)
    np.testing.assert_array_equal(col.to_host(), host.values)


def test_batched_reader_column_projection():
    data = _write(_mixed_schema(), _mixed_rows(1000))
    dev = DeviceFileReader(io.BytesIO(data), columns=["id", "name"])
    cols = dev.read_row_group(0)
    assert set(cols) == {"id", "name"}


def test_batched_reader_corrupt_dict_index_host_check():
    """Out-of-range dictionary indices are rejected at decode time.

    With the native header walk, the stream max is computed on host during
    parse (meta_parse.cpp want_max) and the error raises eagerly — the decode
    path needs zero device→host syncs.
    """
    from tpu_parquet.footer import ParquetError
    from tests.test_jax_decode import _craft_dict_chunk
    from tpu_parquet.device_reader import decode_chunk_batched

    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    leaf = schema.leaves[0]
    buf, codec = _craft_dict_chunk([1, 9, 2], np.arange(4))
    deferred = []
    with pytest.raises(ParquetError, match="out of range"):
        decode_chunk_batched(buf, codec, 3, leaf, deferred)
        # pure-Python walk defers to device: drain the check like finalize()
        for mx, dict_len, path in deferred:
            if int(np.asarray(mx)) >= dict_len:
                raise ParquetError(
                    f"dictionary index {int(np.asarray(mx))} out of range "
                    f"({dict_len}) in column {path}"
                )


def test_batched_reader_corrupt_dict_index_deferred_fallback(monkeypatch):
    """Without the native library, the deferred finalize() check still catches
    corrupt indices (the no-toolchain fallback path)."""
    from tpu_parquet.footer import ParquetError
    from tpu_parquet import native
    from tests.test_jax_decode import _craft_dict_chunk
    from tpu_parquet.device_reader import decode_chunk_batched

    monkeypatch.setattr(native, "hybrid_meta", lambda *a, **k: None)
    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    leaf = schema.leaves[0]
    buf, codec = _craft_dict_chunk([1, 9, 2], np.arange(4))
    deferred = []
    col = decode_chunk_batched(buf, codec, 3, leaf, deferred)
    assert deferred, "deferred check must be recorded"
    mx, dict_len, path = deferred[0]
    assert int(np.asarray(mx)) == 9 and dict_len == 4


def test_reader_stats(tmp_path):
    """Observability counters (SURVEY.md §5.5): rows, pages/chunk, staged
    bytes, throughput — populated after a full read."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = tmp_path / "s.parquet"
    pq.write_table(
        pa.table({"a": np.arange(20000, dtype=np.int64),
                  "b": np.arange(20000, dtype=np.int64) * 2}),
        p, compression="snappy", row_group_size=6000, use_dictionary=False,
    )
    with DeviceFileReader(p) as r:
        for cols in r.iter_row_groups():
            pass
        st = r.stats()
    assert st.row_groups == 4
    assert st.chunks == 8
    assert st.rows == 20000
    assert st.pages >= st.chunks
    assert st.compressed_bytes > 0
    assert st.staged_bytes >= 2 * 2 * 20000
    from tpu_parquet import native

    if native.available():
        # both int64 columns narrow-transcoded to 2 bytes/value (16-bit
        # span), NOT full 8-byte width; without the native library the
        # transcode bails and full-width staging is correct
        assert st.staged_bytes < 2 * 8 * 20000
    assert st.wall_seconds > 0 and st.rows_per_sec > 0
    assert st.pages_per_chunk >= 1.0
    d = st.as_dict()
    assert d["rows"] == 20000 and d["bytes_per_sec"] > 0


def test_profiler_trace_hook(tmp_path):
    """profile_dir= wraps the decode in a JAX profiler trace (SURVEY §5.1)."""
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    p = tmp_path / "t.parquet"
    pq.write_table(pa.table({"a": np.arange(1000, dtype=np.int64)}), p,
                   use_dictionary=False)
    trace_dir = str(tmp_path / "trace")
    with DeviceFileReader(p, profile_dir=trace_dir) as r:
        for cols in r.iter_row_groups():
            pass
    found = []
    for root, _, files in os.walk(trace_dir):
        found.extend(files)
    assert found, "profiler trace produced no files"


def test_device_reader_memory_budget(tmp_path):
    """HBM staging budget (SURVEY §5.3): a tight max_memory raises instead of
    staging an oversized row group; a generous one reads fine."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from tpu_parquet.alloc import MemoryBudgetExceeded

    p = tmp_path / "b.parquet"
    pq.write_table(pa.table({"a": np.arange(200_000, dtype=np.int64)}), p,
                   use_dictionary=False, compression="snappy")
    with DeviceFileReader(p, max_memory=64 << 20) as r:
        assert sum(1 for _ in r.iter_row_groups()) == 1
    with DeviceFileReader(p, max_memory=100_000) as r:
        with pytest.raises(MemoryBudgetExceeded):
            for _ in r.iter_row_groups():
                pass


def test_iter_batches(tmp_path):
    """Fixed-shape device batches across row-group boundaries."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 10_000
    a = np.arange(n, dtype=np.int64) * 3
    b = np.arange(n, dtype=np.float64) / 7
    p = tmp_path / "b.parquet"
    pq.write_table(pa.table({"a": a, "b": b}), p, row_group_size=2307,
                   use_dictionary=False)
    got_a, got_b = [], []
    with DeviceFileReader(p) as r:
        for batch in r.iter_batches(999):
            assert batch["a"].shape == (999,)
            assert batch["b"].shape == (999, 2) or batch["b"].shape == (999,)
            got_a.append(np.asarray(batch["a"]))
            hb = batch["b"]
            arr = np.asarray(hb)
            if arr.ndim == 2:  # f64 device representation: uint32 word pairs
                arr = np.ascontiguousarray(arr).view("<f8").reshape(-1)
            got_b.append(arr)
    full = n - n % 999  # drop_remainder semantics
    np.testing.assert_array_equal(np.concatenate(got_a), a[:full])
    np.testing.assert_array_equal(np.concatenate(got_b), b[:full])


def test_iter_batches_dict_column_materializes(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    vals = np.arange(5000, dtype=np.int64) % 17
    p = tmp_path / "d.parquet"
    pq.write_table(pa.table({"v": vals}), p)  # dictionary-encoded by default
    out = []
    with DeviceFileReader(p) as r:
        for batch in r.iter_batches(512):
            out.append(np.asarray(batch["v"]))
    np.testing.assert_array_equal(np.concatenate(out), vals[: 5000 - 5000 % 512])


def test_iter_batches_rejects_ragged(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = tmp_path / "s.parquet"
    pq.write_table(pa.table({"s": [f"x{i%1000}" for i in range(3000)]}), p,
                   use_dictionary=False)
    with DeviceFileReader(p) as r:
        with pytest.raises(TypeError, match="ragged"):
            next(iter(r.iter_batches(100)))


def test_mixed_dict_plain_chunk(tmp_path):
    """Dictionary-overflow chunks (dict-encoded page prefix with GROWING index
    widths, then PLAIN suffix) decode on the fused device path bit-for-bit."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 400_000
    vals = np.arange(n, dtype=np.int64) * 7 - 3
    dbl = (np.arange(n) * 0.25) - 100.0
    p = tmp_path / "mix.parquet"
    # tiny dictionary page size limit forces overflow to PLAIN mid-chunk
    pq.write_table(pa.table({"v": vals, "d": dbl}), p,
                   compression="snappy", dictionary_pagesize_limit=64 << 10,
                   row_group_size=n)
    from tpu_parquet.chunk_decode import walk_pages
    from tpu_parquet.format import Encoding, PageType

    # confirm the fixture really is mixed (else the test silently weakens)
    with FileReader(p) as hr:
        md = hr.metadata.row_groups[0].columns[0].meta_data
        data = open(p, "rb").read()
        start = (md.dictionary_page_offset
                 if md.dictionary_page_offset is not None
                 else md.data_page_offset)
        encs = set()
        for ps in walk_pages(data[start : start + md.total_compressed_size],
                             md.num_values):
            if ps.header.type != PageType.DICTIONARY_PAGE:
                dh = ps.header.data_page_header or ps.header.data_page_header_v2
                encs.add(Encoding(dh.encoding))
        assert Encoding.PLAIN in encs and (
            Encoding.RLE_DICTIONARY in encs or Encoding.PLAIN_DICTIONARY in encs
        ), encs
        h = hr.read_row_group(0)
    with DeviceFileReader(p) as dr:
        d = dr.read_row_group(0)
    np.testing.assert_array_equal(np.asarray(d["v"].to_host()), h["v"].values)
    np.testing.assert_array_equal(
        np.asarray(d["d"].to_host()).view(np.uint8),
        np.ascontiguousarray(h["d"].values).view(np.uint8),
    )


def test_growing_dict_width_fused(tmp_path):
    """pyarrow writes multi-page dict chunks whose index bit width GROWS as
    the dictionary fills; the fused per-run-width expansion must decode them
    without falling back to the page-at-a-time path (the config-5 hot spot).
    """
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(3)
    n = 60_000
    vals = rng.integers(0, 40_000, n)  # dict grows page to page
    p = tmp_path / "grow.parquet"
    pq.write_table(
        pa.table({"v": vals.astype(np.int64),
                  "d": rng.uniform(0, 1, n)}),
        p, compression="snappy", data_page_size=16 << 10,
        row_group_size=1 << 20,
    )
    # confirm the file really has multi-width dict chunks (else the test
    # silently stops covering the vw path)
    import tpu_parquet.device_reader as drmod

    calls = []
    orig = drmod._ChunkAssembler._finish_host

    def spy(self, common):
        calls.append(tuple(self.leaf.path))
        return orig(self, common)

    drmod._ChunkAssembler._finish_host = spy
    try:
        with DeviceFileReader(p) as r:
            got = r.read_row_group(0)
    finally:
        drmod._ChunkAssembler._finish_host = orig
    assert not calls, f"fell back to page-at-a-time host path for {calls}"
    with FileReader(p) as hr:
        h = hr.read_row_group(0)
    np.testing.assert_array_equal(got["v"].to_host(), h["v"].values)
    np.testing.assert_array_equal(
        np.ascontiguousarray(got["d"].to_host()).view(np.uint8),
        np.ascontiguousarray(h["d"].values).view(np.uint8),
    )


def test_flba_and_int96_fused(tmp_path):
    """FLBA (UUID-like) and INT96 PLAIN chunks take the fused rows path,
    not the per-page host fallback."""
    import datetime

    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(8)
    n = 20_000
    uuids = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    ts = [datetime.datetime(2001, 1, 1) + datetime.timedelta(seconds=int(s))
          for s in rng.integers(0, 10**8, n)]
    p = tmp_path / "f.parquet"
    pq.write_table(
        pa.table({
            "u": pa.array([v.tobytes() for v in uuids],
                          type=pa.binary(16)),
            "t": pa.array(ts, type=pa.timestamp("ns")),
        }),
        p, use_dictionary=False, compression="snappy",
        use_deprecated_int96_timestamps=True, data_page_size=32 << 10,
    )
    import tpu_parquet.device_reader as drmod

    calls = []
    orig = drmod._ChunkAssembler._finish_host

    def spy(self, common):
        calls.append(tuple(self.leaf.path))
        return orig(self, common)

    drmod._ChunkAssembler._finish_host = spy
    try:
        with DeviceFileReader(p) as dr:
            d = dr.read_row_group(0)
    finally:
        drmod._ChunkAssembler._finish_host = orig
    assert not calls, f"fell back to page-at-a-time host path for {calls}"
    with FileReader(p) as hr:
        h = hr.read_row_group(0)
    gu = d["u"].to_host()
    np.testing.assert_array_equal(gu.offsets, h["u"].values.offsets)
    np.testing.assert_array_equal(gu.heap, h["u"].values.heap)
    np.testing.assert_array_equal(d["t"].to_host(), h["t"].values)


def test_rle_dict_index_out_of_range_rejected_when_width_covered(tmp_path):
    """RLE run values are raw unmasked bytes, so a dictionary whose length
    covers the full bit-width range (dict_len >= 2^width) does NOT make every
    encodable index valid: an RLE value byte patched out of range must be
    rejected by the host AND the batched device reader alike (the covered
    fast path may skip only the bit-packed O(values) scan)."""
    import jax
    import pytest

    from tpu_parquet.chunk_decode import validate_chunk_meta, walk_pages
    from tpu_parquet.column import ByteArrayData, ColumnData
    from tpu_parquet.errors import ParquetError
    from tpu_parquet.format import (
        CompressionCodec, FieldRepetitionType as FRT, PageType, Type,
    )
    from tpu_parquet.jax_decode import parse_data_page
    from tpu_parquet.reader import FileReader
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.writer import FileWriter

    path = str(tmp_path / "oob.parquet")
    schema = build_schema([data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED)])
    # 2-entry dictionary (width=1, covered), long repeated tail -> RLE run
    vals = [b"aa"] * 4 + [b"bb"] * 200
    heap = np.frombuffer(b"".join(vals), np.uint8).copy()
    offs = np.cumsum([0] + [len(v) for v in vals]).astype(np.int64)
    with FileWriter(path, schema, codec=CompressionCodec.UNCOMPRESSED,
                    use_dictionary=True) as w:
        w.write_columns({"s": ColumnData(values=ByteArrayData(
            offsets=offs, heap=heap))})

    # locate the index stream's final RLE run value byte and patch it OOB
    with FileReader(path) as r:
        leaf = next(iter(r.schema.selected_leaves()))
        chunk = r.metadata.row_groups[0].columns[0]
        md, off = validate_chunk_meta(chunk, leaf)
        r._f.seek(off)
        buf = r._f.read(md.total_compressed_size)
        patched = None
        for ps in walk_pages(buf, md.num_values):
            if ps.header.type != PageType.DATA_PAGE:
                continue
            p = parse_data_page(ps, buf, md.codec, leaf)
            stream_file_pos = off + ps.payload_start + p.value_pos
            assert buf[ps.payload_start + p.value_pos] == 1  # width byte
            patched = stream_file_pos + len(buf) - ps.payload_start \
                - p.value_pos - 1  # last byte of the page = RLE value byte
        assert patched is not None
    data = bytearray(open(path, "rb").read())
    assert data[patched] in (0, 1)
    data[patched] = 3  # out of range for dict_len == 2
    open(path, "wb").write(bytes(data))

    with pytest.raises(ParquetError):
        with FileReader(path) as r:
            for _ in r.iter_row_groups():
                pass
    from tpu_parquet.device_reader import DeviceFileReader

    with pytest.raises(ParquetError):
        with DeviceFileReader(path) as r:
            for _ in r.iter_row_groups():
                pass
            r.finalize()


def test_plain_byte_array_device_compaction_matches_host(tmp_path):
    """PLAIN (non-dictionary) BYTE_ARRAY: the device-side lengths->offsets->
    heap compaction (_plain_bytes_pages_jit) must reproduce the host decode
    exactly across multi-page chunks, empty strings, nulls, and multiple row
    groups."""
    from tpu_parquet.column import ByteArrayData, ColumnData
    from tpu_parquet.device_reader import DeviceFileReader
    from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
    from tpu_parquet.reader import FileReader
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.writer import FileWriter

    rng = np.random.default_rng(11)
    path = str(tmp_path / "plain_bytes.parquet")
    schema = build_schema([
        data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED),
        data_column("t", Type.BYTE_ARRAY, FRT.OPTIONAL),
    ])
    n = 30_000
    lens = rng.integers(0, 30, n)  # includes empty strings
    heap = rng.integers(65, 91, int(lens.sum()), dtype=np.uint8)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    mask = rng.random(n) < 0.25  # nulls for t
    lens_t = lens[~mask]
    offs_t = np.zeros(len(lens_t) + 1, np.int64)
    np.cumsum(lens_t, out=offs_t[1:])
    heap_t = rng.integers(97, 123, int(lens_t.sum()), dtype=np.uint8)
    with FileWriter(path, schema, codec=CompressionCodec.SNAPPY,
                    use_dictionary=False, page_size=16 << 10,
                    row_group_size=200 << 10) as w:
        w.write_columns({
            "s": ColumnData(values=ByteArrayData(offsets=offs, heap=heap)),
            "t": ColumnData(values=ByteArrayData(offsets=offs_t, heap=heap_t),
                            def_levels=(~mask).astype(np.uint32), max_def=1),
        })

    host = {}
    with FileReader(path) as r:
        for rg in r.iter_row_groups():
            for k, v in rg.items():
                host.setdefault(k, []).append(v)
    dev = {}
    with DeviceFileReader(path) as r:
        for rg in r.iter_row_groups():
            for k, v in rg.items():
                dev.setdefault(k, []).append(v)
    assert set(host) == set(dev)
    for k in host:
        assert len(host[k]) == len(dev[k])
        for h, d in zip(host[k], dev[k]):
            dh = d.to_host()
            np.testing.assert_array_equal(h.values.offsets, dh.offsets)
            np.testing.assert_array_equal(h.values.heap, dh.heap)
            dd, _ = d.levels_to_host()
            if h.def_levels is not None:
                np.testing.assert_array_equal(h.def_levels, dd)


def test_scan_files_multi_file_pipeline(tmp_path):
    """scan_files yields every file's row groups in order, equal to per-file
    reads, closes readers, and still raises deferred errors per file."""
    from tpu_parquet.column import ColumnData
    from tpu_parquet.device_reader import DeviceFileReader, scan_files
    from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.writer import FileWriter

    rng = np.random.default_rng(5)
    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    paths, expect = [], []
    for f in range(3):
        p = str(tmp_path / f"part{f}.parquet")
        vals = rng.integers(-100, 100, 5000 + f * 111)
        with FileWriter(p, schema, codec=CompressionCodec.SNAPPY,
                        row_group_size=16 << 10) as w:
            w.write_columns({"v": ColumnData(values=vals)})
        paths.append(p)
        expect.append(vals)

    got = {p: [] for p in paths}
    for p, cols in scan_files(paths, with_path=True):
        got[p].append(np.asarray(cols["v"].to_host()))
    for p, vals in zip(paths, expect):
        np.testing.assert_array_equal(np.concatenate(got[p]), vals)

    # parity with per-file iteration (row group boundaries included)
    for p in paths:
        per_file = []
        with DeviceFileReader(p) as r:
            for cols in r.iter_row_groups():
                per_file.append(np.asarray(cols["v"].to_host()))
        assert len(per_file) == len(got[p])
        for a, b in zip(per_file, got[p]):
            np.testing.assert_array_equal(a, b)


def test_scan_files_closes_readers_at_boundary_and_on_error(
    tmp_path, monkeypatch
):
    """A finished file's reader closes as soon as its last group is yielded
    (descriptors stay bounded over many shards), and an error mid-scan still
    closes every opened reader."""
    from tpu_parquet.device_reader import DeviceFileReader, scan_files
    from tpu_parquet.errors import ParquetError

    good = str(tmp_path / "good.parquet")
    good2 = str(tmp_path / "good2.parquet")
    bad = str(tmp_path / "bad.parquet")
    _write_oob_dict_file(good, patch=False)
    _write_oob_dict_file(good2, patch=False)
    _write_oob_dict_file(bad, patch=True)

    created = []
    orig = DeviceFileReader.__init__

    def spy(self, *a, **k):
        orig(self, *a, **k)
        created.append(self)

    monkeypatch.setattr(DeviceFileReader, "__init__", spy)

    # boundary closing: by the time file 2's group arrives, file 1 is closed
    seen = []
    for p, cols in scan_files([good, good2], with_path=True):
        seen.append(p)
        if p == good2:
            assert created[0]._host._f.closed
    assert seen == [good, good2]
    assert all(r._host._f.closed for r in created)

    # error propagation: the bad file's out-of-range dictionary index raises
    # (eagerly, during its prepare — pipeline depth means the preceding
    # yield is preempted), and the finally closes every reader
    created.clear()
    with pytest.raises(ParquetError):
        for cols in scan_files([good, bad]):
            pass
    assert len(created) == 2
    assert all(r._host._f.closed for r in created)


def _write_oob_dict_file(path, patch: bool):
    """A 2-entry-dictionary file; with ``patch`` its RLE index run value is
    rewritten out of range (the deferred/covered-width check must reject)."""
    from tpu_parquet.chunk_decode import validate_chunk_meta, walk_pages
    from tpu_parquet.column import ColumnData
    from tpu_parquet.format import PageType
    from tpu_parquet.jax_decode import parse_data_page

    schema = build_schema([data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED)])
    vals = [b"aa"] * 4 + [b"bb"] * 200
    heap = np.frombuffer(b"".join(vals), np.uint8).copy()
    offs = np.cumsum([0] + [len(v) for v in vals]).astype(np.int64)
    with FileWriter(path, schema, codec=CompressionCodec.UNCOMPRESSED,
                    use_dictionary=True) as w:
        w.write_columns({"s": ColumnData(values=ByteArrayData(
            offsets=offs, heap=heap))})
    if not patch:
        return
    with FileReader(path) as r:
        leaf = next(iter(r.schema.selected_leaves()))
        chunk = r.metadata.row_groups[0].columns[0]
        md, off = validate_chunk_meta(chunk, leaf)
        r._f.seek(off)
        buf = r._f.read(md.total_compressed_size)
        patched = None
        for ps in walk_pages(buf, md.num_values):
            if ps.header.type != PageType.DATA_PAGE:
                continue
            parse_data_page(ps, buf, md.codec, leaf)
            patched = off + len(buf) - 1  # last byte = RLE run value byte
        assert patched is not None
    data = bytearray(open(path, "rb").read())
    assert data[patched] in (0, 1)
    data[patched] = 3
    open(path, "wb").write(bytes(data))


def test_narrow_int_transcode_exact(tmp_path):
    """PLAIN INT columns whose span fits < width bytes ship truncated
    (device_reader._plan_narrow_ints) and must reconstruct bit-exactly —
    including negative minima, constant columns, multi-page chunks, and the
    full-range case that must BYPASS the transcode."""
    import tpu_parquet.device_reader as DR

    rng = np.random.default_rng(11)
    cases = {
        "k1": rng.integers(-100, 100, 30000),
        "k3": rng.integers(1, 200_000, 30000),
        "k5_neg": -(1 << 33) + rng.integers(0, 1 << 34, 30000),
        "k8_full": rng.integers(-(1 << 62), 1 << 62, 30000),
        "const": np.full(30000, -42, dtype=np.int64),
        "i32_k2": rng.integers(0, 1000, 30000).astype(np.int32),
        "i32_full": rng.integers(-(1 << 31), (1 << 31) - 1, 30000).astype(np.int32),
    }
    hits = {}
    orig = DR._ChunkAssembler._plan_narrow_ints

    def spy(self, common, stager, name, **kw):
        r = orig(self, common, stager, name, **kw)
        hits[".".join(self.leaf.path)] = r is not None
        return r

    DR._ChunkAssembler._plan_narrow_ints = spy
    try:
        cols = [
            data_column(n, Type.INT32 if v.dtype == np.int32 else Type.INT64,
                        FRT.REQUIRED)
            for n, v in cases.items()
        ]
        path = str(tmp_path / "narrow.parquet")
        with FileWriter(path, build_schema(cols), use_dictionary=False,
                        codec=CompressionCodec.SNAPPY) as w:
            for lo in range(0, 30000, 10000):  # several pages per chunk
                w.write_columns({n: v[lo:lo + 10000] for n, v in cases.items()})
        with DeviceFileReader(path) as r:
            for rg in r.iter_row_groups():
                for n, v in cases.items():
                    got = rg[n].to_host()
                    assert got.dtype == v.dtype, n
                    assert np.array_equal(got, v), n
    finally:
        DR._ChunkAssembler._plan_narrow_ints = orig
    from tpu_parquet import native

    if native.available():
        # wide-span columns (k8_full, i32_full) never reach the narrow
        # planner (stats hint rules them out of the preference list); the
        # mid-width spans rank narrow ahead of shipping the compressed
        # stream and must transcode.  k1/const are the ship planner's
        # judgment call: their snappy payloads are so small (1 significant
        # byte / constant) that keeping them compressed can beat even the
        # 1-byte transcode, so the planner may route them either way —
        # but whenever the narrow planner IS consulted it must succeed.
        assert "k8_full" not in hits and "i32_full" not in hits
        assert all(hits.values()), hits
        assert {"k3", "k5_neg", "i32_k2"} <= {k for k, v in hits.items()
                                              if v}, hits


def test_device_snappy_expansion_exact(tmp_path):
    """Fixed-width PLAIN SNAPPY chunks ship COMPRESSED and expand on device
    (_plan_device_snappy / _snappy_plain_staged_jit).  Values must match the
    host decode bit for bit — including copy-heavy (RLE-style) streams that
    exercise the pointer-doubling resolver, doubles (word-pair form), and v2
    pages whose levels live outside the compressed region."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    import tpu_parquet.device_reader as DR

    rng = np.random.default_rng(5)
    n = 60000
    wide = rng.integers(-(1 << 62), 1 << 62, n)
    rep = np.repeat(rng.integers(0, 40, n // 200), 200) * (1 << 40)  # copies
    dbl = rng.uniform(900.0, 105000.0, n)
    opt = wide.astype("float64")
    opt_mask = rng.random(n) < 0.25
    p = str(tmp_path / "ds.parquet")
    # v2 pages: levels live outside the compressed region, so even the
    # OPTIONAL column is device-snappy eligible.  NOTE pyarrow stores
    # incompressible v2 pages with is_compressed=False — only `rep`
    # (copy-heavy) actually arrives compressed; the others still exercise
    # the route-selection logic and correctness.
    pq.write_table(
        pa.table({
            "wide": wide, "rep": rep, "dbl": dbl,
            "opt": pa.array(np.where(opt_mask, np.nan, opt),
                            mask=opt_mask),
        }),
        p, compression="snappy", use_dictionary=False,
        data_page_version="2.0", row_group_size=20000,
    )
    used = []
    orig = DR._ChunkAssembler._plan_device_snappy

    def spy(self, common, stager, name):
        r = orig(self, common, stager, name)
        used.append((".".join(self.leaf.path), r is not None))
        return r

    DR._ChunkAssembler._plan_device_snappy = spy
    try:
        host = {}
        with FileReader(p) as r:
            for rg in r.iter_row_groups():
                for k, v in rg.items():
                    host.setdefault(k, []).append(v)
        with DeviceFileReader(p) as r:
            for i, rg in enumerate(r.iter_row_groups()):
                for k, col in rg.items():
                    hv = host[k][i].values
                    got = col.to_host()
                    assert np.array_equal(
                        np.asarray(got).view(np.uint8).reshape(-1),
                        np.asarray(hv).view(np.uint8).reshape(-1),
                    ), k
    finally:
        DR._ChunkAssembler._plan_device_snappy = orig
    from tpu_parquet import native

    if native.available():
        # the copy-heavy column is the one pyarrow actually compressed; it
        # must have taken the device expansion path in every row group
        assert [k for k, ok in used if ok].count("rep") == 3


def test_device_snappy_kill_switch(tmp_path, monkeypatch):
    """TPQ_DEVICE_SNAPPY=0 must force the host-decompress path with
    identical results (the A/B the bench and debugging rely on)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(6)
    vals = rng.integers(-(1 << 62), 1 << 62, 30000)
    p = str(tmp_path / "ks.parquet")
    pq.write_table(pa.table({"v": vals}), p, compression="snappy",
                   use_dictionary=False)
    monkeypatch.setenv("TPQ_DEVICE_SNAPPY", "0")
    with DeviceFileReader(p) as r:
        (rg,) = list(r.iter_row_groups())
        assert np.array_equal(rg["v"].to_host(), vals)


def test_device_snappy_deep_copy_chain(tmp_path, monkeypatch):
    """A constant DOUBLE column produces an RLE-style snappy stream whose
    copy chain is thousands of ops deep — the pointer-doubling resolver
    must converge within its static iteration bound and stay bit-exact.
    (Floats never take the narrow-int transcode, so this routes through
    _plan_device_snappy by construction.)"""
    import tpu_parquet.device_reader as DR

    monkeypatch.delenv("TPQ_DEVICE_SNAPPY", raising=False)
    n = 300000
    vals = np.full(n, 1.2345678e5)  # constant: maximal back-reference chains
    schema = build_schema([data_column("d", Type.DOUBLE, FRT.REQUIRED)])
    p = str(tmp_path / "deep.parquet")
    with FileWriter(p, schema, use_dictionary=False,
                    codec=CompressionCodec.SNAPPY, page_size=1 << 20) as w:
        w.write_columns({"d": vals})
    used = []
    orig = DR._ChunkAssembler._plan_device_snappy

    def spy(self, common, stager, name):
        r = orig(self, common, stager, name)
        used.append(r is not None)
        return r

    monkeypatch.setattr(DR._ChunkAssembler, "_plan_device_snappy", spy)
    with DeviceFileReader(p) as r:
        out = np.concatenate(
            [np.asarray(rg["d"].to_host()) for rg in r.iter_row_groups()]
        )
        st = r.stats()
    assert np.array_equal(out.view(np.uint8), vals.view(np.uint8))
    from tpu_parquet import native

    if native.available():
        assert all(used) and used, used
        assert st.pages_device_expanded > 0


def test_snappy_plan_four_byte_offset_copy():
    """Hand-crafted stream with a kind-3 (4-byte little-endian offset) copy
    — a tag our own compressor never emits — must plan identically to the
    native decompressor's output (the device resolver consumes exactly this
    plan; the host-resolver differential pins its semantics)."""
    from tpu_parquet import native

    if not native.available():
        pytest.skip("native library unavailable")
    # uncompressed: 70000 literal bytes then 100 bytes copied from offset 65540
    lit = bytes(range(256)) * 274  # 70144 bytes
    lit = lit[:70000]
    out_len = 70100
    stream = bytearray()
    # uvarint length header
    v = out_len
    while v >= 0x80:
        stream.append((v & 0x7F) | 0x80)
        v >>= 7
    stream.append(v)
    # literal (len-1 >= 60 -> 62<<2 with 3 extra length bytes)
    ln = len(lit) - 1
    stream.append(62 << 2)
    stream += bytes([ln & 0xFF, (ln >> 8) & 0xFF, (ln >> 16) & 0xFF])
    stream += lit
    # kind-3 copy: len 100 (split: 64 + 36), offset 65540 (> 2^16)
    for clen in (64, 36):
        stream.append(((clen - 1) << 2) | 3)
        off = 65540
        stream += bytes([off & 0xFF, (off >> 8) & 0xFF,
                         (off >> 16) & 0xFF, (off >> 24) & 0xFF])
    data = bytes(stream)
    want = native.snappy_decompress(data, out_len)
    r = native.snappy_plan(data, out_len)
    assert not isinstance(r, int) and r is not None
    dst_end, op_src, is_lit, depth = r
    # execute the plan on host (mirror of the device resolver's semantics)
    out = np.zeros(out_len, np.uint8)
    comp = np.frombuffer(data, np.uint8)
    start = 0
    for e, s, lt in zip(dst_end, op_src, is_lit):
        if lt:
            out[start:e] = comp[s : s + (e - start)]
        else:
            for i in range(e - start):
                out[start + i] = out[start - s + (i % s)]
        start = e
    assert bytes(out) == bytes(want)
    assert depth >= 1


def test_fused_row_group_mode_matches_default():
    """TPQ_FUSE_RG=1 (the opt-in whole-row-group fused jit) must decode
    byte-identically to the default per-plan dispatch — the opt-in path
    shares the _Plan contract and would otherwise rot untested."""
    import tpu_parquet.device_reader as dr

    path = _write(_mixed_schema(), _mixed_rows(3000),
                  page_size=4096, row_group_size=128 << 10)
    old = dr._FUSE_RG
    dr._FUSE_RG = True
    try:
        _compare_file(path)
    finally:
        dr._FUSE_RG = old


@pytest.mark.parametrize("backend,env_dir", [
    ("cpu", False), ("cpu", True), ("tpu", False), ("tpu", True)])
def test_compile_cache_location(backend, env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR (which jax reads into its config) wins and
    the code sets no other directory; unset, a TPU caches at the fixed
    <checkout>/.jax_cache/ and the CPU caches nothing.  The size and time
    thresholds apply wherever a cache is on."""
    import os

    import jax

    from tpu_parquet import device_reader as dr

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setattr(dr, "_CACHE_ENABLED", False)
    monkeypatch.setattr(dr.jax, "default_backend", lambda: backend)
    try:
        jax.config.update(keys[0], str(tmp_path) if env_dir else None)
        dr._enable_compile_cache()
        got = jax.config.jax_compilation_cache_dir
        if env_dir:
            assert got == str(tmp_path)
        elif backend == "tpu":
            assert got == os.path.join(dr._CHECKOUT, ".jax_cache")
        else:
            assert not got
        if got:
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.2
            assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
