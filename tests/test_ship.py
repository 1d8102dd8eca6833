"""Ship-planner tests: cost model, forced routes, and route bit-identity.

Every planner choice ({plain, narrow, narrow+snappy, device-snappy,
recompress}) must decode bit-identically to the host reader — the cost model
(tpu_parquet/ship.py) only ROUTES bytes, it never owns correctness — across
prefetch={0,4} (the sequential and overlapped host paths), including the
``TPQ_FORCE_ROUTE`` override that CI uses to pin routes deterministically.
"""

import os

import numpy as np
import pytest

from tpu_parquet import native
from tpu_parquet.column import ByteArrayData, ColumnData
from tpu_parquet.device_reader import DeviceFileReader
from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
from tpu_parquet.reader import FileReader
from tpu_parquet.schema.core import build_schema, data_column
from tpu_parquet.ship import (
    ROUTES, ChunkFacts, ROUTE_DEVICE_SNAPPY, ROUTE_NARROW,
    ROUTE_NARROW_SNAPPY, ROUTE_PLAIN, ROUTE_RECOMPRESS, ShipPlanner,
)
from tpu_parquet.writer import FileWriter

N = 40_000


def _columns():
    rng = np.random.default_rng(17)
    pool = [f"supplier_{i % 400:04d}_{i % 7}".encode() for i in range(400)]
    idx = rng.integers(0, len(pool), N)
    offs = np.zeros(N + 1, dtype=np.int64)
    np.cumsum([len(pool[i]) for i in idx], out=offs[1:])
    heap = np.frombuffer(b"".join(pool[i] for i in idx), np.uint8).copy()
    return {
        # narrow span (k=3), residuals random: narrow engages, compression
        # of the narrow buffer buys little
        "ids": rng.integers(0, 200_000, N),
        # date-like (k=2, sorted-by-date run structure): narrow output is
        # low-entropy — the narrow+snappy composition's home turf
        "dates": np.repeat(19_000 + rng.integers(0, 1200, N // 50),
                           50).astype(np.int64),
        # full 63-bit range: every shrink route must decline
        "wide": rng.integers(-(1 << 62), 1 << 62, N),
        "dbl": np.repeat(rng.uniform(0.0, 1.0, N // 100), 100),
        "s": ColumnData(values=ByteArrayData(offsets=offs, heap=heap)),
    }


def _schema():
    return build_schema([
        data_column("ids", Type.INT64, FRT.REQUIRED),
        data_column("dates", Type.INT64, FRT.REQUIRED),
        data_column("wide", Type.INT64, FRT.REQUIRED),
        data_column("dbl", Type.DOUBLE, FRT.REQUIRED),
        data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED),
    ])


@pytest.fixture(scope="module")
def ship_files(tmp_path_factory):
    root = tmp_path_factory.mktemp("ship")
    cols = _columns()
    paths = {}
    for codec in (CompressionCodec.SNAPPY, CompressionCodec.GZIP,
                  CompressionCodec.UNCOMPRESSED):
        p = str(root / f"ship_{codec.name.lower()}.parquet")
        with FileWriter(p, _schema(), codec=codec,
                        use_dictionary=False) as w:
            for lo in range(0, N, 10_000):  # several pages per chunk
                w.write_columns({
                    k: (v[lo:lo + 10_000] if not isinstance(v, ColumnData)
                        else ColumnData(values=ByteArrayData(
                            offsets=(v.values.offsets[lo:lo + 10_001]
                                     - v.values.offsets[lo]),
                            heap=v.values.heap[
                                v.values.offsets[lo]:v.values.offsets[
                                    min(lo + 10_000, N)]],
                        )))
                    for k, v in cols.items()
                })
        paths[codec.name.lower()] = p
    return paths, cols


def _ragged_rows(ba):
    off = np.asarray(ba.offsets)
    heap = np.asarray(ba.heap)
    return [heap[off[i]:off[i + 1]].tobytes() for i in range(len(off) - 1)]


def _assert_matches_host(path, prefetch):
    host = {}
    with FileReader(path) as r:
        for rg in r.iter_row_groups():
            for k, v in rg.items():
                host.setdefault(k, []).append(v)
    with DeviceFileReader(path, prefetch=prefetch) as r:
        for i, rg in enumerate(r.iter_row_groups()):
            for k, col in rg.items():
                got = col.to_host()
                want = host[k][i].values
                if isinstance(want, ByteArrayData):
                    assert _ragged_rows(got) == _ragged_rows(want), k
                else:
                    g, w = np.asarray(got), np.asarray(want)
                    assert g.dtype == w.dtype, k
                    assert np.array_equal(g.view(np.uint8).reshape(-1),
                                          w.view(np.uint8).reshape(-1)), k
        return r.stats()


# ---------------------------------------------------------------------------
# cost model units
# ---------------------------------------------------------------------------

def test_planner_orderings():
    p = ShipPlanner(link_mbps=350.0, force=None)
    L = 8 << 20
    # snappy file, ratio ~1, no narrow hint: keep the payload (the host
    # decompress it skips is the whole win)
    r = p.routes(ChunkFacts(logical=L, width=8, comp_bytes=int(0.99 * L)))
    assert r[0] == ROUTE_DEVICE_SNAPPY
    # narrow stats hint beats shipping the compressed stream
    r = p.routes(ChunkFacts(logical=L, width=8, narrow_k=3,
                            comp_bytes=L // 2, narrow_possible=True))
    assert r.index(ROUTE_NARROW) < r.index(ROUTE_DEVICE_SNAPPY)
    # byte-array heap in a gzip file: recompression wins over raw shipping
    r = p.routes(ChunkFacts(logical=L, width=0, comp_bytes=0))
    assert r[0] == ROUTE_RECOMPRESS
    # tiny stream: nothing beats just shipping it
    assert p.routes(ChunkFacts(logical=1000, width=0))[0] == ROUTE_PLAIN
    # every cost table includes the plain anchor
    assert ROUTE_PLAIN in p.costs(ChunkFacts(logical=L, width=8))
    assert p.decision_table(ChunkFacts(logical=L, width=8))[ROUTE_PLAIN] > 0


def test_planner_slow_link_prefers_composition():
    """On a congested link the narrow+snappy composition must outrank the
    uncompressed narrow ship — the whole point of composing the two."""
    slow = ShipPlanner(link_mbps=60.0, force=None)
    fast = ShipPlanner(link_mbps=5000.0, force=None)
    f = ChunkFacts(logical=8 << 20, width=8, narrow_k=3,
                   narrow_possible=True)
    r = slow.routes(f)
    assert r.index(ROUTE_NARROW_SNAPPY) < r.index(ROUTE_NARROW)
    # on a fast link the host passes dominate: plain must win
    assert fast.routes(f)[0] == ROUTE_PLAIN


def test_planner_env_overrides(monkeypatch):
    monkeypatch.setenv("TPQ_LINK_MBPS", "123.5")
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "recompress")
    p = ShipPlanner()
    assert p.link_mbps == 123.5
    assert p.routes(ChunkFacts(logical=1 << 20, width=8)) == [
        ROUTE_RECOMPRESS, ROUTE_PLAIN]
    # malformed env value: ONE warning, then cost-ranked routing — the
    # TPQ_FORCE_ROUTE degradation contract (an env typo must never turn
    # reader construction, or a scan mid-flight through default_planner's
    # env re-read, into a raise).  An explicit force= argument is a
    # programming contract and still raises.
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "bogus")
    assert ShipPlanner().force is None
    with pytest.raises(ValueError, match="warp"):
        ShipPlanner(force="warp")


@pytest.mark.parametrize("lane", [
    ("LINK_MBPS", "device_link_mbps", "TPQ_LINK_MBPS", "link_mbps"),
    ("DEVICE_RESOLVE_MBPS", "device_resolve_mbps", "TPQ_DEVICE_MBPS",
     "device_mbps"),
], ids=["link", "device"])
@pytest.mark.parametrize("kind", ["cpu", "TPU v5 lite", "TPU v9 unknown"])
def test_rate_keyed_by_device_kind(kind, lane, monkeypatch):
    """Without the env override the planner takes the measured row of the
    device's kind; a kind nobody measured raises instead of guessing."""
    from tpu_parquet import ship

    table, fn, env, attr = lane
    monkeypatch.delenv(env, raising=False)
    real = getattr(ship, fn)
    monkeypatch.setattr(ship, fn, lambda: real(kind))
    if kind not in getattr(ship, table):
        with pytest.raises(ValueError, match=kind):
            ShipPlanner()
        monkeypatch.setenv(env, "800")  # the override still wins
        assert getattr(ShipPlanner(), attr) == 800.0
    else:
        assert getattr(ShipPlanner(), attr) == getattr(ship, table)[kind]


@pytest.mark.parametrize("comp", [0, 4 << 20])
def test_v5e_rows_ship_lineitem_doubles_plain(comp):
    """With the v5e rows, an 8 MB PLAIN double chunk of SF1 lineitem (lazy
    snappy pages or none) ships plain: on that chip the device snappy
    resolve measured ~6.8 MB/s, and pricing it at the CPU row's 3000
    made the warm SF1 scan 3.6x slower (PR 21)."""
    from tpu_parquet import ship

    kind = "TPU v5 lite"
    p = ShipPlanner(link_mbps=ship.LINK_MBPS[kind],
                    device_mbps=ship.DEVICE_RESOLVE_MBPS[kind], fuse=True)
    f = ChunkFacts(logical=8 << 20, width=8, comp_bytes=comp, native=True)
    assert p.routes(f)[0] == ROUTE_PLAIN


# ---------------------------------------------------------------------------
# route bit-identity (the acceptance-criteria matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 4])
@pytest.mark.parametrize("codec", ["snappy", "gzip", "uncompressed"])
def test_planned_routes_bit_identical(ship_files, codec, prefetch,
                                      monkeypatch):
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    paths, _ = ship_files
    st = _assert_matches_host(paths[codec], prefetch)
    d = st.as_dict()
    assert d["ship_routes"], "planner recorded no routes"
    assert d["link_bytes_shipped"] <= d["link_bytes_logical"]
    if native.available():
        # the headline claim: compressed shipping engages beyond PLAIN
        # fixed-width — the string heap must NOT ship as raw host bytes
        routes = set(d["ship_routes"])
        assert routes & {ROUTE_DEVICE_SNAPPY, ROUTE_RECOMPRESS,
                         ROUTE_NARROW, ROUTE_NARROW_SNAPPY}, d
        assert d["link_bytes_shipped"] < d["link_bytes_logical"]


@pytest.mark.parametrize("prefetch", [0, 4])
@pytest.mark.parametrize("route", list(ROUTES))
def test_forced_route_bit_identical(ship_files, route, prefetch,
                                    monkeypatch):
    """TPQ_FORCE_ROUTE pins the route (deterministic CI); infeasible forces
    (narrow on doubles, device_snappy on gzip) must fall back to plain with
    identical results, never an error."""
    paths, _ = ship_files
    monkeypatch.setenv("TPQ_FORCE_ROUTE", route)
    for codec in ("snappy", "gzip"):
        st = _assert_matches_host(paths[codec], prefetch)
        assert st.as_dict()["ship_routes"]


def test_forced_route_histogram(ship_files, monkeypatch):
    """The forced route must actually be TAKEN where feasible, and the
    counters must prove the byte cut."""
    if not native.available():
        pytest.skip("native library unavailable")
    paths, _ = ship_files
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "recompress")
    st = _assert_matches_host(paths["gzip"], 0).as_dict()
    rec = st["ship_routes"].get(ROUTE_RECOMPRESS)
    assert rec is not None and rec["shipped"] < rec["logical"]
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "narrow")
    st = _assert_matches_host(paths["gzip"], 0).as_dict()
    nar = st["ship_routes"].get(ROUTE_NARROW)
    assert nar is not None and nar["shipped"] < nar["logical"]


def test_narrow_snappy_composition_engages(ship_files, monkeypatch):
    """At congested-link settings the planner composes narrow + snappy on
    low-entropy int columns (`dates`), and the composed route reconstructs
    bit-exactly — the plain_int64-gap mechanism of the ISSUE."""
    if not native.available():
        pytest.skip("native library unavailable")
    paths, _ = ship_files
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    monkeypatch.setenv("TPQ_LINK_MBPS", "60")
    st = _assert_matches_host(paths["gzip"], 0).as_dict()
    ns = st["ship_routes"].get(ROUTE_NARROW_SNAPPY)
    assert ns is not None, st["ship_routes"]
    assert ns["shipped"] < ns["logical"] // 2


def test_bytes_heap_ships_compressed_snappy(ship_files, monkeypatch):
    """The lineitem16 byte mover: PLAIN BYTE_ARRAY value heaps in a snappy
    file keep the file's own payload over the link."""
    if not native.available():
        pytest.skip("native library unavailable")
    paths, _ = ship_files
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    st = _assert_matches_host(paths["snappy"], 0).as_dict()
    ds = st["ship_routes"].get(ROUTE_DEVICE_SNAPPY)
    assert ds is not None and ds["shipped"] < ds["logical"], st["ship_routes"]
    assert st["pages_device_expanded"] > 0


def test_dict_table_ships_compressed(tmp_path, monkeypatch):
    """Dictionary VALUE TABLES route through the planner too: a snappy
    file's fixed-width dictionary keeps its compressed page payload, a
    ragged (string) dictionary recompresses its heap — both decode
    bit-identically through materialize()."""
    if not native.available():
        pytest.skip("native library unavailable")
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    rng = np.random.default_rng(23)
    # large dictionaries so the tables clear MIN_COMPRESS_BYTES
    pool_i = rng.integers(0, 1 << 45, 20_000)
    ints = pool_i[rng.integers(0, len(pool_i), N)]
    pool = [f"warehouse_row_{i:06d}".encode() for i in range(20_000)]
    sidx = rng.integers(0, len(pool), N)
    offs = np.zeros(N + 1, dtype=np.int64)
    np.cumsum([len(pool[i]) for i in sidx], out=offs[1:])
    heap = np.frombuffer(b"".join(pool[i] for i in sidx), np.uint8).copy()
    schema = build_schema([
        data_column("di", Type.INT64, FRT.REQUIRED),
        data_column("ds", Type.BYTE_ARRAY, FRT.REQUIRED),
    ])
    p = str(tmp_path / "dict.parquet")
    with FileWriter(p, schema, codec=CompressionCodec.SNAPPY,
                    use_dictionary=True) as w:
        w.write_columns({
            "di": ints,
            "ds": ColumnData(values=ByteArrayData(offsets=offs, heap=heap)),
        })
    with DeviceFileReader(p) as r:
        (rg,) = list(r.iter_row_groups())
        got_i = np.asarray(rg["di"].to_host())
        got_s = rg["ds"].to_host()
        st = r.stats().as_dict()
    assert np.array_equal(got_i, ints)
    assert _ragged_rows(got_s) == [pool[i] for i in sidx]
    routes = set(st["ship_routes"])
    assert routes & {ROUTE_DEVICE_SNAPPY, ROUTE_RECOMPRESS}, st["ship_routes"]


def test_op_cap_overflow_falls_back(ship_files, monkeypatch):
    """A stream shattered past the op-table cap must fall through to the
    next route (never error, never ship a broken table) — the satellite's
    op-count-cap-overflow case at the integration level."""
    import tpu_parquet.device_reader as DR

    paths, _ = ship_files
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    monkeypatch.setattr(DR, "_SNAPPY_MAX_OPS", 2)
    st = _assert_matches_host(paths["snappy"], 0).as_dict()
    assert ROUTE_DEVICE_SNAPPY not in st["ship_routes"], st["ship_routes"]


def test_recompress_counted_in_pipeline_stats(ship_files, monkeypatch):
    """Link recompression runs on the prefetch pool's threads and its
    seconds surface in the `recompress` stage (pipeline.py STAGES)."""
    if not native.available():
        pytest.skip("native library unavailable")
    paths, _ = ship_files
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    with DeviceFileReader(paths["gzip"], prefetch=4) as r:
        for _ in r.iter_row_groups():
            pass
        ps = r.pipeline_stats().as_dict()
        st = r.stats().as_dict()
    if ROUTE_RECOMPRESS in st["ship_routes"]:
        assert ps["recompress_seconds"] > 0.0
    assert "recompress_seconds" in ps


def test_plain_force_ships_everything_raw(ship_files, monkeypatch):
    """TPQ_FORCE_ROUTE=plain is the A/B baseline: logical == shipped."""
    paths, _ = ship_files
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "plain")
    st = _assert_matches_host(paths["snappy"], 0).as_dict()
    assert set(st["ship_routes"]) == {ROUTE_PLAIN}
    assert st["link_bytes_shipped"] == st["link_bytes_logical"]


def test_reader_degrades_bogus_forced_route(ship_files, monkeypatch):
    """A typo'd TPQ_FORCE_ROUTE must not turn reader construction into a
    raise: one warning line, then cost-ranked routing, bit-identical
    results (the same degradation contract as every other TPQ_* knob)."""
    paths, _ = ship_files
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "warp")
    st = _assert_matches_host(paths["snappy"], 0).as_dict()
    assert st["ship_routes"]  # the scan ran, cost-ranked
