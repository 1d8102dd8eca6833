"""Fused decode megakernel tests (ISSUE 13 / ROADMAP direction 2).

The contract under test: the fused route (`fused_plain`) decodes
BIT-IDENTICALLY to the host reader across prefetch={0,4} and validate_crc
on/off — the megakernel only fuses device passes, it never owns different
semantics — and degrades to its unfused twin (with a counter, never a
crash) wherever it cannot claim a stream.
On CPU the whole fused graph runs through the Pallas interpreter
(TPQ_FUSE=1), so tier-1 proves the exact graph a TPU compiles.  The
registry ``device`` section's ``device_passes`` counter is the structural
proof of fusion: one pass per dispatch on fused routes, >=3 on the staged
chains.
"""

import json
import os

import numpy as np
import pytest

from tpu_parquet import native
from tpu_parquet.column import ColumnData
from tpu_parquet.device_reader import DeviceFileReader
from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
from tpu_parquet.reader import FileReader
from tpu_parquet.schema.core import build_schema, data_column
from tpu_parquet.ship import (
    FUSED_ROUTES, ROUTE_FUSED_PLAIN, ROUTE_PLAIN, ROUTES, UNFUSED_OF,
    ChunkFacts, ShipPlanner, fused_eligible, parse_route,
)
from tpu_parquet.writer import FileWriter, corrupt_page

N = 80_000
ROWS_PER_GROUP = 40_000


def _columns():
    rng = np.random.default_rng(23)
    return {
        # date-like with run structure: the narrow routes' home turf
        "dates": np.repeat(19_000 + rng.integers(0, 1200, N // 50),
                           50).astype(np.int64),
        # full 63-bit range: every shrink route declines; the fused PLAIN
        # kernel's lane (the plain_int64 debt)
        "wide": rng.integers(-(1 << 62), 1 << 62, N),
        # 32-bit lanes through the kernel
        "cnt": rng.integers(0, 50_000, N).astype(np.int32),
        "rate": rng.uniform(0, 1, N).astype(np.float32),
        "dbl": np.repeat(rng.uniform(0.0, 1.0, N // 100), 100),
    }


def _schema():
    return build_schema([
        data_column("dates", Type.INT64, FRT.REQUIRED),
        data_column("wide", Type.INT64, FRT.REQUIRED),
        data_column("cnt", Type.INT32, FRT.REQUIRED),
        data_column("rate", Type.FLOAT, FRT.REQUIRED),
        data_column("dbl", Type.DOUBLE, FRT.REQUIRED),
    ])


@pytest.fixture(scope="module")
def fused_file(tmp_path_factory):
    root = tmp_path_factory.mktemp("fused")
    cols = _columns()
    p = str(root / "fused.parquet")
    with FileWriter(p, _schema(), codec=CompressionCodec.SNAPPY,
                    write_crc=True, use_dictionary=False) as w:
        for lo in range(0, N, ROWS_PER_GROUP):
            w.write_columns({k: v[lo:lo + ROWS_PER_GROUP]
                             for k, v in cols.items()})
            w.flush_row_group()
    return p, cols


def _host_groups(path, **kw):
    out = []
    with FileReader(path, **kw) as r:
        for rg in r.iter_row_groups():
            out.append({k: np.asarray(v.values) for k, v in rg.items()})
    return out


def _assert_device_matches(path, host, prefetch=0, **kw):
    with DeviceFileReader(path, prefetch=prefetch, **kw) as r:
        n = 0
        for i, rg in enumerate(r.iter_row_groups()):
            for k, col in rg.items():
                g, w = np.asarray(col.to_host()), host[i][k]
                assert g.dtype == w.dtype, (k, g.dtype, w.dtype)
                assert np.array_equal(g.view(np.uint8).reshape(-1),
                                      w.view(np.uint8).reshape(-1)), k
            n += 1
        assert n == len(host)
        return r


# ---------------------------------------------------------------------------
# bit-identity matrix: fused route x prefetch x validate_crc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("crc", [None, False])
@pytest.mark.parametrize("prefetch", [0, 4])
@pytest.mark.parametrize("route", list(FUSED_ROUTES))
def test_fused_route_bit_identical(fused_file, route, prefetch, crc,
                                   monkeypatch):
    path, _ = fused_file
    monkeypatch.setenv("TPQ_FUSE", "1")
    monkeypatch.setenv("TPQ_FORCE_ROUTE", route)
    host = _host_groups(path)
    r = _assert_device_matches(path, host, prefetch=prefetch,
                               validate_crc=crc)
    st = r.stats().as_dict()
    # the forced fused route actually RAN where it could (every column
    # of this file is flat and 4 or 8 bytes wide)
    assert st["ship_routes"].get(route, {}).get("streams", 0) >= 1, \
        st["ship_routes"]


def test_planned_fused_bit_identical(fused_file, monkeypatch):
    """TPQ_FUSE=1 with no force: the PLANNER picks fused rows where they
    rank (the plain tie goes to fused_plain) and the scan stays
    bit-identical."""
    path, _ = fused_file
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    monkeypatch.setenv("TPQ_FUSE", "1")
    host = _host_groups(path)
    r = _assert_device_matches(path, host)
    routes = set(r.stats().as_dict()["ship_routes"])
    assert routes & set(FUSED_ROUTES), routes


def test_fuse_off_never_routes_fused(fused_file, monkeypatch):
    path, _ = fused_file
    monkeypatch.delenv("TPQ_FORCE_ROUTE", raising=False)
    monkeypatch.setenv("TPQ_FUSE", "0")
    host = _host_groups(path)
    r = _assert_device_matches(path, host)
    assert not set(r.stats().as_dict()["ship_routes"]) & set(FUSED_ROUTES)


# ---------------------------------------------------------------------------
# quarantine containment through a fused kernel's unit
# ---------------------------------------------------------------------------

def test_fused_corrupt_page_containment(fused_file, tmp_path, monkeypatch):
    """A corrupt page on a chunk HEADED FOR a fused kernel: skip_unit
    accounting is exact and every surviving row group stays bit-identical
    — corruption containment (PR 8) is policy-layer, and fusion must not
    re-open it."""
    import shutil

    src, _ = fused_file
    path = str(tmp_path / "corrupt.parquet")
    shutil.copyfile(src, path)
    # column 0 is `dates` — a stream the fused kernel claims
    corrupt_page(path, row_group=1, column=0, page=0, mode="bitflip",
                 seed=3)
    monkeypatch.setenv("TPQ_FUSE", "1")
    host = _host_groups(src)
    for route in FUSED_ROUTES:
        monkeypatch.setenv("TPQ_FORCE_ROUTE", route)
        with DeviceFileReader(path, on_data_error="skip_unit") as r:
            got = list(r.iter_row_groups())
            q = r.quarantine
            assert q.units_skipped == 1
            recs = q.log.snapshot()
            assert len(recs) == 1 and recs[0]["row_group"] == 1
        assert len(got) == 1  # group 1 quarantined, group 0 survives
        for k, col in got[0].items():
            g, w = np.asarray(col.to_host()), host[0][k]
            assert np.array_equal(g.view(np.uint8).reshape(-1),
                                  w.view(np.uint8).reshape(-1)), (route, k)


# ---------------------------------------------------------------------------
# planner: fused rows, tie preference, eligibility
# ---------------------------------------------------------------------------

def test_planner_offers_fused_rows():
    p = ShipPlanner(link_mbps=350.0, force=None, fuse=True)
    f = ChunkFacts(logical=8 << 20, width=8, narrow_k=3,
                   narrow_possible=True, flat=True)
    order, costs = p.plan(f)
    assert ROUTE_FUSED_PLAIN in costs
    dev = p.device_costs(f, routes=costs)
    # the spill-inclusive unfused prediction (fusion-win's bar) exceeds
    # the fused model
    unf = p.unfused_device_costs(f, routes=costs)
    for fr in FUSED_ROUTES:
        assert unf[fr] > dev[fr]
    # equal-cost tie goes to fused: plain and fused_plain share host/link
    # terms on a link-bound stream
    if costs[ROUTE_FUSED_PLAIN] == costs[ROUTE_PLAIN]:
        assert order.index(ROUTE_FUSED_PLAIN) < order.index(ROUTE_PLAIN)


def test_planner_fuse_off_and_ineligible():
    off = ShipPlanner(fuse=False)
    f = ChunkFacts(logical=8 << 20, width=8, flat=True)
    assert not set(off.costs(f)) & set(FUSED_ROUTES)
    on = ShipPlanner(fuse=True)
    # not flat (level lanes) / width 0: no fused rows even with fuse on
    assert not set(on.costs(ChunkFacts(logical=8 << 20, width=8,
                                       flat=False))) & set(FUSED_ROUTES)
    assert not set(on.costs(ChunkFacts(logical=8 << 20,
                                       width=0))) & set(FUSED_ROUTES)
    assert fused_eligible(ChunkFacts(logical=1 << 20, width=8)) == \
        FUSED_ROUTES
    assert fused_eligible(ChunkFacts(logical=0, width=8)) == ()


def test_route_registry_is_single_table():
    """Satellite: one route-name registry.  The fused names are in ROUTES
    (so TPQ_FORCE_ROUTE and the ScanPlan route memo accept them), every
    fused name maps to its twin, and parse_route is the one env-validation
    entry point (degrades, never raises)."""
    from tpu_parquet.scanplan import ScanPlan

    for fr in FUSED_ROUTES:
        assert fr in ROUTES
        assert UNFUSED_OF[fr] in ROUTES
    assert parse_route("fused_plain") == ROUTE_FUSED_PLAIN
    assert parse_route(" fused_plain ") == ROUTE_FUSED_PLAIN
    assert parse_route("fused_narrow_snappy") is None  # gone in PR 21
    assert parse_route("warp-speed") is None
    assert parse_route("") is None
    # the plan IR memoizes fused routes like any other (replay hint)
    plan = ScanPlan(row_groups=[])
    plan.note_route(0, "a", ROUTE_FUSED_PLAIN, "fused")
    assert plan.route_hint(0, "a") == ROUTE_FUSED_PLAIN


def test_forced_fused_on_ineligible_degrades(tmp_path, monkeypatch):
    """Forced fused on a nullable column (level lanes) degrades to the
    unfused route with a COUNTER, not a crash — and stays correct."""
    schema = build_schema([data_column("v", Type.INT64, FRT.OPTIONAL)])
    rng = np.random.default_rng(5)
    defs = (rng.uniform(size=4000) < 0.9).astype(np.int32)
    vals = rng.integers(0, 1 << 40, int(defs.sum()))
    p = str(tmp_path / "opt.parquet")
    with FileWriter(p, schema, codec=CompressionCodec.SNAPPY,
                    use_dictionary=False) as w:
        w.write_columns({"v": ColumnData(values=vals, def_levels=defs,
                                         max_def=1, max_rep=0)})
    monkeypatch.setenv("TPQ_FUSE", "1")
    host = _host_groups(p)
    for route in FUSED_ROUTES:
        monkeypatch.setenv("TPQ_FORCE_ROUTE", route)
        with DeviceFileReader(p) as r:
            for i, rg in enumerate(r.iter_row_groups()):
                got = np.asarray(rg["v"].to_host())
                assert np.array_equal(got, host[i]["v"])
            st = r.stats().as_dict()
        assert st["fused_fallbacks"] >= 1
        assert not set(st["ship_routes"]) & set(FUSED_ROUTES)


# ---------------------------------------------------------------------------
# structural proof: one device pass per fused dispatch, >=3 on the chains
# ---------------------------------------------------------------------------

def _device_routes(path, route, monkeypatch):
    monkeypatch.setenv("TPQ_FORCE_ROUTE", route)
    with DeviceFileReader(path) as r:
        for _ in r.iter_row_groups():
            pass
        return (r.obs_registry().as_dict().get("device") or {}) \
            .get("routes") or {}


@pytest.mark.parametrize("fused_route", list(FUSED_ROUTES))
def test_fused_one_pass_per_dispatch(fused_file, fused_route, monkeypatch):
    """The acceptance bar: fused routes show exactly ONE device pass per
    (row group, column) dispatch in the registry; the unfused twin's chain
    shows >=3 per dispatch on the same file."""
    path, _ = fused_file
    monkeypatch.setenv("TPQ_FUSE", "1")
    dev = _device_routes(path, fused_route, monkeypatch)
    c = dev.get(fused_route)
    assert c is not None and c["dispatches"] >= 1, dev
    assert c["device_passes"] == c["dispatches"], c
    un = _device_routes(path, UNFUSED_OF[fused_route], monkeypatch)
    uc = un.get(UNFUSED_OF[fused_route])
    assert uc is not None and uc["dispatches"] >= 1, un
    assert uc["device_passes"] >= 3 * uc["dispatches"], uc


# ---------------------------------------------------------------------------
# satellites: cached availability, ledger fingerprint, doctor fusion-win
# ---------------------------------------------------------------------------

def test_pallas_available_probed_once(monkeypatch):
    from tpu_parquet import pallas_kernels as pk

    calls = {"n": 0}
    real = pk.jax.devices

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(pk.jax, "devices", counting)
    pk._reset_available_cache()
    try:
        first = pk.pallas_available()
        for _ in range(10):
            assert pk.pallas_available() == first
        assert calls["n"] == 1  # one probe, cached thereafter
        assert pk.pallas_mode() in ("compiled", "interpret")
    finally:
        pk._reset_available_cache()


def test_ledger_fingerprint_records_pallas_mode(monkeypatch):
    from tpu_parquet.ledger import env_fingerprint
    from tpu_parquet.pallas_kernels import pallas_mode

    monkeypatch.setenv("TPQ_FUSE", "1")
    fp = env_fingerprint()
    assert fp["TPQ_FUSE"] == "1"
    assert fp["pallas_mode"] == pallas_mode()  # interpret on CPU CI


def test_doctor_fusion_win(tmp_path):
    import argparse
    import io

    from tpu_parquet.cli.pq_tool import cmd_doctor
    from tpu_parquet.obs import OBS_VERSION, doctor_registry

    tree = {
        "obs_version": OBS_VERSION,
        "pipeline": {"stage_seconds": 0.1},
        "reader": {
            "host_seconds": 0.05, "staged_bytes": 1 << 20,
            "ship_routes": {
                "fused_plain": {
                    "streams": 4, "logical": 4 << 20, "shipped": 1 << 20,
                    "predicted_s": 0.01, "predicted_device_s": 0.002,
                    "predicted_unfused_device_s": 0.02,
                },
            },
        },
        "device": {
            "dispatches": 4, "device_seconds": 0.005,
            "routes": {"fused_plain": {
                "dispatches": 4, "device_seconds": 0.005,
                "bytes_in": 4 << 20, "bytes_staged": 1 << 20,
                "device_passes": 4}},
            "kernels": {"fused": {"dispatches": 4,
                                  "device_seconds": 0.005}},
            "h2d": {"transfers": 1, "device_seconds": 0.001,
                    "bytes": 1 << 20},
        },
    }
    rep = doctor_registry(tree)
    fw = rep.get("fusion_win")
    assert fw is not None
    assert fw["route"] == "fused_plain"
    assert fw["speedup"] == pytest.approx(0.02 / 0.005, rel=1e-3)
    # a slower-than-predicted fused lane reports NO win
    worse = json.loads(json.dumps(tree))
    worse["device"]["routes"]["fused_plain"]["device_seconds"] = 0.5
    assert doctor_registry(worse).get("fusion_win") is None
    # the CLI renders it
    p = tmp_path / "reg.json"
    p.write_text(json.dumps(tree))
    buf = io.StringIO()
    assert cmd_doctor(argparse.Namespace(file=str(p), config=None),
                      out=buf) == 0
    out = buf.getvalue()
    assert "fusion-win" in out and "fused_plain" in out


def test_fused_routes_ride_ship_feedback(fused_file, monkeypatch):
    """The obs spine treats fused routes uniformly: ship_feedback carries
    the fused route with its unfused device prediction, and the device
    section names the `fused` kernel family."""
    path, _ = fused_file
    monkeypatch.setenv("TPQ_FUSE", "1")
    monkeypatch.setenv("TPQ_FORCE_ROUTE", ROUTE_FUSED_PLAIN)
    with DeviceFileReader(path) as r:
        for _ in r.iter_row_groups():
            pass
        tree = r.obs_registry().as_dict()
    fb = tree["reader"]["ship_feedback"]["routes"]
    rec = fb.get(ROUTE_FUSED_PLAIN)
    assert rec is not None
    assert rec["device_unfused_predicted_seconds"] is not None
    assert rec["device_unfused_predicted_seconds"] > 0
    assert "fused" in (tree["device"] or {}).get("kernels", {})
    json.dumps(tree)  # artifact-ready
