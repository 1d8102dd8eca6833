"""Parallel (mesh/shard_map) decode tests on the virtual 8-device CPU mesh.

Covers: page batching, data-parallel sharded decode for hybrid/delta/plain,
the 2-D mesh variant with a model-sharded dictionary (masked gather + psum
routing), global stats collectives, and the work-list shard planner.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tpu_parquet import parallel as par
from tpu_parquet.kernels import delta as delta_host, rle as rle_host

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= 8, "conftest must provide 8 virtual devices"
    return par.make_mesh(jax.devices()[:8])


def _hybrid_batch(n_pages, count, width, dict_len):
    vals = [RNG.integers(0, dict_len, count).astype(np.uint64) for _ in range(n_pages)]
    raws = [rle_host.encode(v, width) for v in vals]
    return par.pack_hybrid_pages(raws, width, count), vals


def test_sharded_dict_decode(mesh):
    batch, vals = _hybrid_batch(16, 500, 7, 100)
    dictionary = RNG.integers(-(1 << 40), 1 << 40, 100)
    dict_u8 = jnp.asarray(dictionary.view(np.uint8).reshape(100, 8))
    out, stats = par.sharded_dict_decode(batch, dict_u8, "int64", mesh, with_stats=True)
    expect = np.stack([dictionary[v.astype(np.int64)] for v in vals])
    np.testing.assert_array_equal(np.asarray(out), expect)
    st = np.asarray(stats)
    assert st[0] == 16 * 500
    assert st[1] == min(int(v.min()) for v in vals)
    assert st[2] == max(int(v.max()) for v in vals)
    # output keeps its sharding for downstream pjit consumption
    assert "data" in str(out.sharding)


def test_sharded_dict_decode_2d():
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh2 = Mesh(devs, ("data", "model"))
    batch, vals = _hybrid_batch(8, 256, 6, 50)
    dictionary = RNG.integers(-(1 << 30), 1 << 30, 50)
    dict_u8 = jnp.asarray(dictionary.view(np.uint8).reshape(50, 8))
    out = par.sharded_dict_decode_2d(batch, dict_u8, "int64", mesh2)
    expect = np.stack([dictionary[v.astype(np.int64)] for v in vals])
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_sharded_dict_decode_2d_uneven_dict():
    # dict size not divisible by model axis → padding path
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh2 = Mesh(devs, ("data", "model"))
    batch, vals = _hybrid_batch(4, 128, 6, 37)
    dictionary = RNG.integers(0, 1 << 20, 37)
    dict_u8 = jnp.asarray(dictionary.view(np.uint8).reshape(37, 8))
    out = par.sharded_dict_decode_2d(batch, dict_u8, "int64", mesh2)
    expect = np.stack([dictionary[v.astype(np.int64)] for v in vals])
    np.testing.assert_array_equal(np.asarray(out), expect)


@pytest.mark.parametrize("bits", [32, 64])
def test_sharded_delta_decode(mesh, bits):
    dt = np.int32 if bits == 32 else np.int64
    count = 384
    vals = [np.cumsum(RNG.integers(-40, 40, count)).astype(dt) for _ in range(16)]
    raws = [delta_host.encode(v, bits=bits) for v in vals]
    batch = par.pack_delta_pages(raws, bits, count)
    out = par.sharded_delta_decode(batch, bits, mesh)
    np.testing.assert_array_equal(np.asarray(out), np.stack(vals))


def test_sharded_plain_decode(mesh):
    count = 512
    vals = [RNG.integers(-(1 << 50), 1 << 50, count) for _ in range(8)]
    bufs = np.zeros((8, par._bucket(count * 8 + par._SLACK, 64)), np.uint8)
    for i, v in enumerate(vals):
        bufs[i, : count * 8] = v.view(np.uint8)
    out = par.sharded_plain_decode(jnp.asarray(bufs), "int64", count, mesh)
    np.testing.assert_array_equal(np.asarray(out), np.stack(vals))


def test_column_stats(mesh):
    vals = RNG.integers(-1000, 1000, (8, 256))
    st = np.asarray(par.column_stats(jnp.asarray(vals), mesh))
    assert st[0] == vals.size
    assert st[1] == vals.min()
    assert st[2] == vals.max()


def test_plan_shards_balanced():
    sizes = [100, 90, 80, 70, 30, 30, 20, 10]
    plan = par.plan_shards(sizes, 3)
    # every group assigned exactly once
    assert sorted(i for s in plan for i in s) == list(range(8))
    loads = [sum(sizes[i] for i in s) for s in plan]
    assert max(loads) - min(loads) <= 60  # LPT bound for this instance
    # deterministic
    assert plan == par.plan_shards(sizes, 3)


def test_plan_shards_more_shards_than_groups():
    plan = par.plan_shards([10, 20], 4)
    assert sorted(i for s in plan for i in s) == [0, 1]
    assert sum(1 for s in plan if s) == 2


def test_graft_entry_single_chip():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "/root/repo/__graft_entry__.py"
    )
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    fn, args = g.entry()
    from tpu_parquet.jax_kernels import enable_x64

    # trace under x64: the example args are int64 metadata, and a no-x64
    # jit boundary would downcast them before the kernels' scoped_x64
    # contexts apply
    with enable_x64():
        out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert out[0].shape == (256,)
    assert out[1].shape == (256,)


def test_graft_dryrun_multichip():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", "/root/repo/__graft_entry__.py"
    )
    g = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(g)
    g.dryrun_multichip(8)
    g.dryrun_multichip(2)


def test_pack_hybrid_pages_tail_padding(mesh):
    """Short tail page pads with a synthetic zero run; decode matches.

    Page-batch size must divide the mesh's data axis (8 here) — the short page
    sits last, as a real chunk's tail page would.
    """
    count, width = 200, 5
    pages = [RNG.integers(0, 20, count).astype(np.uint64) for _ in range(7)]
    vals_tail = RNG.integers(0, 20, 57).astype(np.uint64)
    raws = [rle_host.encode(v, width) for v in pages] + [
        rle_host.encode(vals_tail, width)
    ]
    batch = par.pack_hybrid_pages(
        raws, width, count, counts=[count] * 7 + [57]
    )
    dictionary = RNG.integers(0, 1 << 30, 20)
    dict_u8 = jnp.asarray(dictionary.view(np.uint8).reshape(20, 8))
    out, _ = par.sharded_dict_decode(batch, dict_u8, "int64", mesh)
    got = np.asarray(out)
    for i, v in enumerate(pages):
        np.testing.assert_array_equal(got[i], dictionary[v.astype(np.int64)])
    np.testing.assert_array_equal(got[7, :57], dictionary[vals_tail.astype(np.int64)])
    np.testing.assert_array_equal(got[7, 57:], np.full(count - 57, dictionary[0]))


# ---------------------------------------------------------------------------
# Multi-host work list → global sharded array (SURVEY.md §5.8)
# ---------------------------------------------------------------------------

def _write_span_file(tmp_path, rows=1000, rg_rows=137):
    from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.writer import FileWriter

    vals = np.arange(rows, dtype=np.int64) * 3 - 500
    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    p = tmp_path / "span.parquet"
    with FileWriter(p, schema, codec=CompressionCodec.SNAPPY,
                    use_dictionary=False) as w:
        for lo in range(0, rows, rg_rows):
            w.write_columns({"v": vals[lo : lo + rg_rows]})
            w.flush_row_group()
    return p, vals


def test_shard_row_ranges_properties():
    spans = par.shard_row_ranges(1000, 8)
    assert len(spans) == 8
    assert spans[0] == (0, 125) and spans[-1] == (875, 1000)
    # uneven: equal spans, short tail
    spans = par.shard_row_ranges(1001, 8)
    assert all(hi - lo == 126 for lo, hi in spans[:-1])
    assert spans[-1] == (882, 1001)
    assert par.shard_row_ranges(0, 4) == [(0, 0)] * 4


def test_decode_row_span_touches_only_needed_groups(tmp_path):
    from tpu_parquet.reader import FileReader

    p, vals = _write_span_file(tmp_path)
    with FileReader(p) as r:
        np.testing.assert_array_equal(
            par.decode_row_span(r, "v", 130, 290), vals[130:290]
        )
        np.testing.assert_array_equal(
            par.decode_row_span(r, "v", 0, 1000), vals
        )
        np.testing.assert_array_equal(
            par.decode_row_span(r, "v", 999, 1000), vals[999:]
        )


def test_global_column_array(mesh, tmp_path):
    """Work list → per-device decode → one global row-sharded array."""
    from tpu_parquet.reader import FileReader

    p, vals = _write_span_file(tmp_path)
    with FileReader(p) as r:
        arr, valid = par.global_column_array(r, "v", mesh)
    assert valid == 1000
    assert arr.shape == (1000,)  # 1000 divides evenly over 8 shards
    np.testing.assert_array_equal(np.asarray(arr), vals)
    # every device holds exactly its contiguous span
    for shard in arr.addressable_shards:
        lo = shard.index[0].start or 0
        np.testing.assert_array_equal(np.asarray(shard.data), vals[lo : lo + 125])


def test_global_column_array_padded_tail(mesh, tmp_path):
    from tpu_parquet.reader import FileReader

    p, vals = _write_span_file(tmp_path, rows=997)
    with FileReader(p) as r:
        arr, valid = par.global_column_array(r, "v", mesh)
    assert valid == 997
    per = -(-997 // 8)
    assert arr.shape == (per * 8,)
    np.testing.assert_array_equal(np.asarray(arr)[:997], vals)
    assert not np.any(np.asarray(arr)[997:])  # zero tail padding


def test_process_local_column_single_process(mesh, tmp_path):
    """Multi-host API path on a single process: the same plan/assembly code
    runs with process_count()==1 (decodes everything locally)."""
    from tpu_parquet.reader import FileReader

    p, vals = _write_span_file(tmp_path)
    with FileReader(p) as r:
        arr, valid = par.process_local_column(r, "v", mesh)
    assert valid == 1000
    np.testing.assert_array_equal(np.asarray(arr), vals)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_scan_files_stays_on_default_device(tmp_path, prefetch, monkeypatch):
    """Under ``jax.default_device(d)`` a scan stages AND decodes on d: jax's
    default device is thread-local, and the staging worker used to put
    every buffer on device 0 (a cross-device copy per row group on a
    multi-chip host)."""
    from tpu_parquet import device_reader as dr

    path, _ = _write_span_file(tmp_path, rows=20_000, rg_rows=7000)
    dev = jax.devices()[3]
    staged = set()
    real = dr._run_plans

    def spy(plans, buf_dev, timer=None):
        if buf_dev is not None:
            staged.update(buf_dev.devices())
        return real(plans, buf_dev, timer)

    monkeypatch.setattr(dr, "_run_plans", spy)
    # small strips: the row group streams through the staging worker
    monkeypatch.setattr(dr._RowGroupStager, "STRIP", 4096)
    with jax.default_device(dev):
        groups = list(dr.scan_files([path], prefetch=prefetch))
    assert staged == {dev}
    assert {d for g in groups for c in g.values()
            for d in c.values.devices()} == {dev}
