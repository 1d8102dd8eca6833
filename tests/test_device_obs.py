"""Device-lane observability tests.

Covers the per-route / per-kernel-family dispatch counters (DeviceStats
golden keys, registry ``device`` section merge paths incl. a 2-OS-process
round trip, the stage/dispatch split replacing the double-counted
``device_seconds`` scalar), HBM residency accounting on ``AllocTracker``
(sampler track + flight dump watermark), the planner's device-lane feedback
(``ship.device_costs`` / ``recalibrate_device_mbps``, ``ship_feedback``
device lane null contract, doctor's ``h2d-bound`` sibling and dominant
route/kernel naming on older trees that carry device seconds), graceful
degradation on artifacts predating the ``device`` section, and the bounded
``TPQ_XPROF`` capture window.  Device time itself comes from a profiler
capture (tests/test_profiler_spans.py).
"""

import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpu_parquet.obs import (
    OBS_VERSION, StatsRegistry, Tracer, doctor_registry, trace_summary,
)
from tpu_parquet.pipeline import PipelineStats

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_ints(path, rows=120_000, groups=3, seed=0):
    from tpu_parquet.format import FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.writer import FileWriter

    rng = np.random.default_rng(seed)
    schema = build_schema([
        data_column("v", Type.INT64, FRT.REQUIRED),
        data_column("w", Type.INT32, FRT.REQUIRED),
    ])
    per = rows // groups
    with FileWriter(path, schema, row_group_size=1) as w:
        for _ in range(groups):
            w.write_columns({
                "v": rng.integers(0, 1 << 40, per),
                "w": rng.integers(0, 1000, per).astype(np.int32),
            })
            w.flush_row_group()
    return path


# ---------------------------------------------------------------------------
# DeviceStats + registry `device` section (golden keys, merge paths)
# ---------------------------------------------------------------------------

def _device_stats():
    from tpu_parquet.device_reader import DeviceStats

    ds = DeviceStats()
    ds.note_dispatch("plain", "plain", bytes_out=1000, bytes_staged=1000)
    ds.note_dispatch("device_snappy", "snappy_resolve", bytes_out=4000,
                     bytes_staged=1500)
    ds.note_h2d(2500)
    return ds


def test_device_stats_as_dict_golden_keys():
    d = _device_stats().as_dict()
    assert set(d) == {"dispatches", "bytes_out", "routes", "kernels", "h2d",
                      "run_expand"}
    assert d["dispatches"] == 2
    assert d["bytes_out"] == 5000
    assert set(d["routes"]) == {"plain", "device_snappy"}
    counters = {"dispatches", "bytes_out", "bytes_staged", "device_passes"}
    for r in d["routes"].values():
        assert set(r) == counters
    assert set(d["kernels"]) == {"plain", "snappy_resolve"}
    for k in d["kernels"].values():
        assert set(k) == counters
    assert d["kernels"]["snappy_resolve"]["bytes_staged"] == 1500
    assert set(d["h2d"]) == {"transfers", "bytes"}
    assert d["h2d"]["bytes"] == 2500
    assert d["run_expand"] == {"expansions": 0, "runs": 0, "lanes": 0}
    json.dumps(d)  # artifact-ready


def test_device_stats_run_expand_counts_and_merges():
    """``run_expand`` sums what each dispatched program expands — hybrid
    expansions, their real run entries, their lanes — and merges across
    registries like every other flow."""
    ds = _device_stats()
    ds.note_dispatch("plain", "gather", bytes_out=800, run_expand=(2, 70,
                                                                     4096))
    ds.note_dispatch("plain", "levels", bytes_out=64, run_expand=(1, 3, 16))
    assert ds.as_dict()["run_expand"] == {"expansions": 3, "runs": 73,
                                          "lanes": 4112}
    a, b = StatsRegistry(), StatsRegistry()
    a.add_device(ds)
    b.add_device(ds)
    a.merge_dict(b.as_dict())
    assert a.as_dict()["device"]["run_expand"] == {
        "expansions": 6, "runs": 146, "lanes": 8224}


def test_registry_device_section_merge_from_and_dict():
    """The device section composes like io/data_errors: flows add across
    add_device / merge_from / merge_dict (the 2-process seam)."""
    a = StatsRegistry()
    a.add_device(_device_stats())
    b = StatsRegistry()
    b.add_device(_device_stats())
    a.merge_from(b)
    t = a.as_dict()["device"]
    assert t["dispatches"] == 4
    assert t["routes"]["plain"]["dispatches"] == 2
    assert t["routes"]["device_snappy"]["bytes_out"] == 8000
    assert t["kernels"]["snappy_resolve"]["bytes_out"] == 8000
    assert t["h2d"]["transfers"] == 2
    # serialized (cross-process) merge stacks on top
    a.merge_dict(b.as_dict())
    assert a.as_dict()["device"]["dispatches"] == 6


_CHILD = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from tpu_parquet.device_reader import DeviceStats
from tpu_parquet.obs import StatsRegistry

ds = DeviceStats()
for i in range(100):
    ds.note_dispatch("narrow", "narrow", bytes_out=10, bytes_staged=5)
ds.note_h2d(64)
reg = StatsRegistry()
reg.add_device(ds)
print(json.dumps(reg.as_dict()))
"""


def test_two_process_device_merge_roundtrip():
    outs = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, "-c", _CHILD, REPO_ROOT],
            capture_output=True, text=True, timeout=120,
        )
        assert res.returncode == 0, res.stderr
        outs.append(json.loads(res.stdout))
    reg = StatsRegistry()
    for o in outs:
        reg.merge_dict(o)
    t = reg.as_dict()["device"]
    assert t["routes"]["narrow"]["dispatches"] == 200
    assert t["routes"]["narrow"]["bytes_out"] == 2000
    assert t["routes"]["narrow"]["bytes_staged"] == 1000
    assert t["kernels"]["narrow"]["dispatches"] == 200
    assert t["h2d"] == {"transfers": 2, "bytes": 128}


# ---------------------------------------------------------------------------
# planner device lane (ship.device_costs / recalibrate_device_mbps)
# ---------------------------------------------------------------------------

def test_ship_planner_device_costs_keys_and_values():
    from tpu_parquet.ship import ChunkFacts, ROUTE_PLAIN, ShipPlanner

    p = ShipPlanner(link_mbps=350.0, device_mbps=3000.0)
    f = ChunkFacts(logical=8 << 20, width=8, narrow_k=2,
                   narrow_possible=True, native=True)
    costs = p.costs(f)
    dev = p.device_costs(f)
    assert set(dev) == set(costs)  # same feasibility, per-route
    assert dev[ROUTE_PLAIN] == 0.0  # reshape+bitcast: no device compute
    # the compressed routes charge the resolve per OUTPUT byte
    assert dev["recompress"] == pytest.approx((8 << 20) / 3000e6)
    # narrow widens to L; narrow_snappy ALSO resolves the narrowed stream
    # first — strictly more device work, and the same term costs() uses
    assert dev["narrow"] == pytest.approx((8 << 20) / 3000e6)
    assert dev["narrow_snappy"] == pytest.approx((10 << 20) / 3000e6)
    assert dev["narrow_snappy"] > dev["narrow"]


def test_ship_planner_device_mbps_env(monkeypatch):
    from tpu_parquet.ship import ChunkFacts, ShipPlanner, default_planner

    monkeypatch.setenv("TPQ_DEVICE_MBPS", "1500")
    p = ShipPlanner()
    assert p.device_mbps == 1500.0
    # default_planner rebuilds when the env knob changes
    assert default_planner().device_mbps == 1500.0
    monkeypatch.setenv("TPQ_DEVICE_MBPS", "3000")
    assert default_planner().device_mbps == 3000.0
    f = ChunkFacts(logical=1 << 20, width=0, comp_bytes=1 << 19, native=True)
    halved = ShipPlanner(device_mbps=1500.0).device_costs(f)
    full = ShipPlanner(device_mbps=3000.0).device_costs(f)
    assert halved["device_snappy"] == pytest.approx(
        2 * full["device_snappy"])


def test_recalibrate_device_mbps():
    from tpu_parquet.ship import recalibrate_device_mbps

    assert recalibrate_device_mbps(0.0) is None
    assert recalibrate_device_mbps(None) is None
    assert recalibrate_device_mbps(-5.0) is None
    assert recalibrate_device_mbps(2.5e9) == pytest.approx(2500.0)
    assert recalibrate_device_mbps(10.0) == 1.0  # floored at the clamp


# ---------------------------------------------------------------------------
# ship_feedback device lane (null contract) + doctor verdicts
# ---------------------------------------------------------------------------

def test_ship_feedback_device_lane_null_until_measured():
    from tpu_parquet.device_reader import ReaderStats

    reg = StatsRegistry()
    rs = ReaderStats()
    rs.count_route("plain", 100, 100, 0.001, 0.0005)
    rs.staged_bytes = 100
    reg.add_reader(rs)
    r = reg.ship_feedback()["routes"]["plain"]
    # timing lane never ran: predicted real, measured explicitly null
    assert r["device_predicted_seconds"] == pytest.approx(0.0005)
    assert r["device_measured_seconds"] is None
    assert r["device_error_ratio"] is None
    json.dumps(r)
    # a counter-only device section (every tree now) measures no seconds
    reg.add_device(_device_stats())
    assert reg.ship_feedback()["routes"]["plain"][
        "device_measured_seconds"] is None
    # an older tree's timed device section arrives: the lane fills in
    reg = StatsRegistry()
    reg.add_reader(rs)
    reg.add_device({"routes": {"plain": {"dispatches": 1,
                                         "device_seconds": 0.001,
                                         "bytes_in": 100,
                                         "bytes_staged": 100}}})
    r = reg.ship_feedback()["routes"]["plain"]
    assert r["device_measured_seconds"] == pytest.approx(0.001)
    assert r["device_error_ratio"] == pytest.approx(2.0)


def _device_tree(routes, h2d_s=0.0, pipeline=None, reader=None):
    dev = {
        "dispatches": sum(c["dispatches"] for c in routes.values()),
        "device_seconds": sum(c["device_seconds"] for c in routes.values()),
        "routes": routes,
        "kernels": {"snappy_resolve": {
            "dispatches": 1,
            "device_seconds": max((c["device_seconds"]
                                   for c in routes.values()), default=0.0),
        }},
        "h2d": {"transfers": 1, "device_seconds": h2d_s, "bytes": 1 << 20},
    }
    return {
        "obs_version": OBS_VERSION,
        "pipeline": pipeline or {"io_seconds": 0.2, "decompress_seconds": 0.2,
                                 "stage_seconds": 0.3},
        "reader": reader or {},
        "device": dev,
    }


def test_doctor_h2d_bound_verdict():
    tree = _device_tree(
        {"plain": {"dispatches": 2, "device_seconds": 0.5,
                   "bytes_in": 1000, "bytes_staged": 1000}},
        h2d_s=5.0)
    rep = doctor_registry(tree)
    assert rep["verdict"] == "h2d-bound"
    assert rep["dominant_lane"] == "h2d"
    assert rep["lanes"]["h2d"] == pytest.approx(5.0)


def test_doctor_names_dominant_device_route_and_recalibrates():
    routes = {
        "device_snappy": {"dispatches": 3, "device_seconds": 4.0,
                          "bytes_in": 4 << 20, "bytes_staged": 1 << 20},
        "plain": {"dispatches": 1, "device_seconds": 0.5,
                  "bytes_in": 1 << 20, "bytes_staged": 1 << 20},
    }
    reader = {"ship_routes": {
        "device_snappy": {"streams": 3, "logical": 4 << 20,
                          "shipped": 1 << 20, "predicted_s": 0.01,
                          "predicted_device_s": 1.0},
    }}
    rep = doctor_registry(_device_tree(routes, reader=reader))
    assert rep["verdict"] == "device-resolve-bound"
    dv = rep["device"]
    assert dv["dominant_route"] == "device_snappy"
    assert dv["dominant_kernel"] == "snappy_resolve"
    assert dv["measured_seconds"] == pytest.approx(4.0)
    assert dv["error_ratio"] == pytest.approx(4.0)  # 4x slower than modeled
    # 4x outside the band: the DOMINANT route's measured resolve rate is
    # the re-run knob ((4<<20) bytes_in / 4.0s ≈ 1.05 MB/s, one decimal) —
    # never a blend that lets plain's near-zero-compute bytes dilute it
    assert rep["recalibrate_device_mbps"] == pytest.approx(1.0)
    assert rep["device"]["measured_device_mbps"] == pytest.approx(1.0)
    # inside the band: no recalibration worth chasing
    reader["ship_routes"]["device_snappy"]["predicted_device_s"] = 4.0
    rep = doctor_registry(_device_tree(routes, reader=reader))
    assert "recalibrate_device_mbps" not in rep
    assert rep["device"]["error_ratio"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# graceful degradation on artifacts predating the `device` section
# (table-driven: doctor / trace / ledger.diff over an old banked record)
# ---------------------------------------------------------------------------

_OLD_PIPE = {"io_seconds": 1.0, "decompress_seconds": 2.0,
             "stage_seconds": 0.5, "dispatch_seconds": 0.2,
             "finalize_seconds": 0.1, "stall_seconds": 0.0}


def _old_cfg(dev_rps=1e7):
    """A config shaped like a pre-device-section banked record (the
    BENCH_LOCAL_r08-era schema: obs tree without `device`, ship_routes
    without predicted_device_s)."""
    return {
        "rows": 1000, "device_rows_per_sec": dev_rps,
        "device_windows_s": [[0.1, 0.1]],
        "obs": {
            "obs_version": OBS_VERSION,
            "pipeline": dict(_OLD_PIPE),
            "reader": {"host_seconds": 1.0, "ship_routes": {
                "plain": {"streams": 1, "logical": 10, "shipped": 10,
                          "predicted_s": 0.001}}},
            "alloc": {"peak_bytes": 100},
        },
    }


@pytest.mark.parametrize("surface", ["doctor", "doctor_cli", "trace",
                                     "ledger_diff"])
def test_old_records_degrade_gracefully(surface, tmp_path):
    """Artifacts and ledger records predating the device registry section
    print n/a (or simply omit device rows) — never a KeyError."""
    if surface == "doctor":
        rep = doctor_registry(_old_cfg()["obs"])
        assert rep is not None
        assert "device" not in rep  # nothing fabricated
        assert rep["lanes"]["h2d"] == 0.0  # present, zero — never dominant
        assert rep["lanes"]["device_resolve"] == pytest.approx(0.3)
    elif surface == "doctor_cli":
        from tpu_parquet.cli import pq_tool

        p = str(tmp_path / "old_reg.json")
        with open(p, "w") as f:
            json.dump(_old_cfg()["obs"], f)
        out = io.StringIO()
        args = pq_tool.build_parser().parse_args(["doctor", p])
        assert args.func(args, out=out) == 0
        assert "device: n/a" in out.getvalue()
    elif surface == "trace":
        # a ship instant without predicted_device_s (old trace artifact)
        tr = Tracer()
        tr.instant("ship", route="plain", column="v", logical=10, shipped=10,
                   predicted_s=0.001)
        r = trace_summary(tr.export())["routes"]["plain"]
        assert r["device_predicted_seconds"] == 0.0
        assert r["device_measured_seconds"] is None
        assert r["device_error_ratio"] is None
    else:
        from tpu_parquet import ledger

        old = {"configs": {"c": _old_cfg(1e7)}}
        new = {"configs": {"c": _old_cfg(1e6)}}  # 10x regression
        d = ledger.diff(old, new)
        assert d["regressions"], "regression must still be flagged"
        # attribution over old records: no device pseudo-stages, no raise
        att = d["regressions"][0].get("attribution")
        assert att is None or not att["stage"].startswith("device:")


def test_ledger_attributes_device_route_growth():
    from tpu_parquet import ledger

    a = _old_cfg()
    b = _old_cfg()
    a["obs"]["device"] = {"routes": {"device_snappy": {
        "dispatches": 1, "device_seconds": 0.1, "bytes_in": 1,
        "bytes_staged": 1}}}
    b["obs"]["device"] = {"routes": {"device_snappy": {
        "dispatches": 1, "device_seconds": 5.0, "bytes_in": 1,
        "bytes_staged": 1}}}
    att = ledger.attribute_stages(a, b)
    assert att["stage"] == "device:device_snappy"
    assert att["moved_seconds"] == pytest.approx(4.9)


# ---------------------------------------------------------------------------
# stage/dispatch split (the device_seconds double-count fix)
# ---------------------------------------------------------------------------

def test_serial_run_lane_sum_close_to_wall(tmp_path):
    """On a truly serial run (read_row_group: prepare, stage, and dispatch
    all inline on one thread — iter_row_groups always overlaps staging
    one group deep) host + stage + dispatch lane seconds must sum to ≈
    the reader wall — the property the old shared `device_seconds` scalar
    (worker AND dispatcher adding concurrent intervals) could violate
    from both sides."""
    from tpu_parquet.device_reader import DeviceFileReader

    path = _write_ints(str(tmp_path / "serial.parquet"))
    with DeviceFileReader(path) as r:
        for i in range(r.num_row_groups):
            r.read_row_group(i, finalize=False)
        r.finalize()
        st = r.stats()
        wall = st.wall_seconds
        lanes = st.host_seconds + st.stage_seconds + st.dispatch_seconds
    assert st.stage_seconds > 0.0
    assert st.dispatch_seconds > 0.0
    # disjoint sub-intervals of one thread's wall can never exceed it
    # (+5% timer slack), and the decode work dominates the iteration
    # overhead on a 120k-row file
    assert lanes <= wall * 1.05, (lanes, wall)
    assert lanes >= wall * 0.5, (lanes, wall)


def test_pipelined_run_keeps_lanes_distinct(tmp_path):
    """prefetch>0: the staging worker adds ONLY to stage_seconds, the
    dispatcher ONLY to dispatch_seconds (both nonzero, no shared scalar)."""
    from tpu_parquet.device_reader import DeviceFileReader

    path = _write_ints(str(tmp_path / "piped.parquet"))
    with DeviceFileReader(path, prefetch=2) as r:
        for _ in r.iter_row_groups():
            pass
        d = r.stats().as_dict()
    assert d["stage_seconds"] > 0.0
    assert d["dispatch_seconds"] > 0.0
    assert "device_seconds" not in d  # the double-counted scalar is gone


# ---------------------------------------------------------------------------
# the device section end to end (registry counters, trace table, doctor)
# ---------------------------------------------------------------------------

def test_device_section_end_to_end_with_doctor(tmp_path):
    """On a traced run the registry carries a device section whose routes
    mirror the ship routes, counted at dispatch: dispatches, the bytes the
    programs return and the bytes staged, per route and per kernel family.
    It carries no device seconds (those come from a profiler capture), so
    ship_feedback's device lane stays null, `pq_tool trace` renders, and
    `pq_tool doctor` prints `device: n/a`."""
    from tpu_parquet.cli import pq_tool
    from tpu_parquet.device_reader import DeviceFileReader

    path = _write_ints(str(tmp_path / "e2e.parquet"))
    tp = str(tmp_path / "trace.json")
    with DeviceFileReader(path, prefetch=2, trace=tp) as r:
        for _ in r.iter_row_groups():
            pass
        tree = r.obs_registry().as_dict()
        st = r.stats().as_dict()
    dev = tree["device"]
    assert dev is not None and dev["dispatches"] > 0
    # every counted route is a route the planner actually chose — plus the
    # default "plain" attribution for columns with no value-stream ship
    # record (dict-index/levels-only plans)
    assert set(dev["routes"]) <= set(st["ship_routes"]) | {"plain"}
    assert dev["h2d"]["transfers"] > 0
    assert dev["h2d"]["bytes"] == st["staged_bytes"]
    for c in list(dev["routes"].values()) + list(dev["kernels"].values()):
        assert c["bytes_out"] > 0 and "device_seconds" not in c
    assert sum(c["bytes_staged"] for c in dev["routes"].values()) \
        == st["staged_bytes"]
    assert sum(c["dispatches"] for c in dev["kernels"].values()) \
        == dev["dispatches"]
    fb = tree["reader"]["ship_feedback"]["routes"]
    assert all(r["device_measured_seconds"] is None for r in fb.values())
    out = io.StringIO()
    args = pq_tool.build_parser().parse_args(["trace", tp])
    assert args.func(args, out=out) == 0
    out = io.StringIO()
    args = pq_tool.build_parser().parse_args(["doctor", tp])
    assert args.func(args, out=out) == 0
    assert "device: n/a" in out.getvalue()
    assert "device" not in doctor_registry(tree)


# ---------------------------------------------------------------------------
# HBM residency accounting (AllocTracker device ledger)
# ---------------------------------------------------------------------------

def test_alloc_device_ledger_watermark():
    from tpu_parquet.alloc import AllocTracker, tracker_snapshots

    al = AllocTracker(0)
    al.register_device(1000)
    al.register_device(2000)
    assert al.device_snapshot() == (3000, 3000)
    al.release_device(2000)
    al.register_device(500)
    assert al.device_snapshot() == (1500, 3000)
    # the host ledger's per-row-group reset never touches HBM residency
    al.reset()
    assert al.device_snapshot() == (1500, 3000)
    snaps = [s for s in tracker_snapshots() if s.get("device_peak") == 3000]
    assert snaps and snaps[0]["device_in_use"] == 1500
    # the registry picks the watermark up
    reg = StatsRegistry()
    reg.note_alloc_peak(al)
    assert reg.as_dict()["alloc"]["device_peak_bytes"] == 3000


def test_device_residency_in_sampler_tracks_and_flight_dump(tmp_path):
    """The device_bytes watermark rides the reader's alloc sampler track
    and the flight dump's tracker section (acceptance criterion)."""
    from tpu_parquet.device_reader import DeviceFileReader
    from tpu_parquet.obs import FlightRecorder

    path = _write_ints(str(tmp_path / "resid.parquet"))
    tp = str(tmp_path / "trace.json")
    rec = FlightRecorder(capacity=64)
    with DeviceFileReader(path, trace=tp, sample_ms=5) as r:
        peak_seen = 0
        for _ in r.iter_row_groups():
            in_use, peak = r.alloc.device_snapshot()
            peak_seen = max(peak_seen, in_use)
            doc = rec.snapshot()
        st = r.stats()
        assert peak_seen > 0  # staged buffers were resident mid-scan
        # finalize (iter end) released them
        assert r.alloc.device_snapshot()[0] == 0
        assert r.alloc.device_snapshot()[1] >= peak_seen
        trackers = [t for t in doc["trackers"] if t.get("device_peak")]
        assert trackers, "flight dump carries no device watermark"
    doc = json.loads(open(tp).read())
    alloc_tracks = [e for e in doc["traceEvents"]
                    if e.get("ph") == "C" and e.get("name") == "alloc_bytes"]
    assert alloc_tracks
    assert any("device_peak" in (e.get("args") or {}) for e in alloc_tracks)
    # the device counter track rode the same sampler
    dev_tracks = [e for e in doc["traceEvents"]
                  if e.get("ph") == "C" and e.get("name") == "device"]
    assert dev_tracks
    assert st.staged_bytes > 0


def test_residency_pending_vs_outstanding(tmp_path):
    """finalize releases only DISPATCHED groups' bytes: a staged-but-not-
    dispatched buffer (the pipelined stage-ahead) stays on the ledger."""
    from tpu_parquet.device_reader import DeviceFileReader

    path = _write_ints(str(tmp_path / "pend.parquet"), rows=30_000, groups=1)
    with DeviceFileReader(path) as r:
        prepared = r._prepare_row_group(0)
        buf = prepared[2].stage()
        r._note_staged(prepared[2])
        staged = prepared[2].total
        assert r.alloc.device_snapshot()[0] == staged
        # finalize BEFORE dispatch: the pending buffer must survive
        r.finalize()
        assert r.alloc.device_snapshot()[0] == staged
        r._dispatch_row_group(prepared, buf)
        r.finalize()
        assert r.alloc.device_snapshot()[0] == 0
    # close() is the deferred-scan backstop for still-pending bytes
    assert r.alloc.device_snapshot()[0] == 0


# ---------------------------------------------------------------------------
# TPQ_XPROF bounded window
# ---------------------------------------------------------------------------

def test_xprof_window_captures_once(tmp_path, monkeypatch):
    import jax

    import tpu_parquet.device_reader as dr

    xdir = str(tmp_path / "xprof")
    monkeypatch.setenv("TPQ_XPROF", xdir)
    monkeypatch.setattr(dr, "_XPROF_DONE", False)
    path = _write_ints(str(tmp_path / "xp.parquet"), rows=30_000, groups=2)
    with dr.DeviceFileReader(path) as r:
        for _ in r.iter_row_groups():
            pass
    # the window closed with the scan
    assert not jax.profiler.TraceAnnotation.is_enabled()
    files = [os.path.join(root, f)
             for root, _, fs in os.walk(xdir) for f in fs]
    assert files, "no xprof artifact written"
    # one capture per process: a second scan must not re-open the window
    with dr.DeviceFileReader(path) as r:
        for _ in r.iter_row_groups():
            pass
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_xprof_window_covers_scan_files(tmp_path, monkeypatch):
    """scan_files drives _scan_pipeline directly (never iter_row_groups),
    so it must own its own capture window — the multi-file runs the
    feature targets."""
    import jax

    import tpu_parquet.device_reader as dr

    xdir = str(tmp_path / "xprof_scan")
    monkeypatch.setenv("TPQ_XPROF", xdir)
    monkeypatch.setattr(dr, "_XPROF_DONE", False)
    paths = [_write_ints(str(tmp_path / f"s{i}.parquet"), rows=20_000,
                         groups=1, seed=i) for i in range(2)]
    n = sum(1 for _ in dr.scan_files(paths))
    assert n == 2
    assert not jax.profiler.TraceAnnotation.is_enabled()
    files = [f for _, _, fs in os.walk(xdir) for f in fs]
    assert files, "scan_files wrote no xprof artifact"


def test_scan_records_run_expand(tmp_path, monkeypatch):
    """A scan of a multi-page dictionary file counts every hybrid
    expansion it dispatches under ``device.run_expand``: one per
    dictionary chunk and one per definition-level stream, with the real
    run entries of their pages and their bucketed lanes.  The Pallas route
    (interpreted here) counts the same as the XLA route."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from tpu_parquet.device_reader import DeviceFileReader

    rng = np.random.default_rng(11)
    n = 24_000
    # repeated stretches and scattered keys: RLE and bit-packed index runs
    k = np.repeat(rng.integers(0, 2000, n // 8), 8)
    scattered = rng.random(n) < 0.4
    k[scattered] = rng.integers(0, 2000, int(scattered.sum()))
    s = np.array([f"v{i % 97}" for i in rng.integers(0, 1000, n)], object)
    nulls = rng.random(n) < 0.2
    s[nulls] = None
    table = pa.table({"k": pa.array(k, pa.int64()), "s": pa.array(s)},
                     schema=pa.schema([pa.field("k", pa.int64(), False),
                                       pa.field("s", pa.string())]))
    path = str(tmp_path / "dict_pages.parquet")
    pq.write_table(table, path, row_group_size=n // 2, data_page_size=2048,
                   use_dictionary=True, compression="snappy")
    meta = pq.ParquetFile(path).metadata
    assert meta.num_row_groups == 2
    assert all(meta.row_group(g).column(c).total_compressed_size > 4096
               for g in range(2) for c in range(2))  # several pages each

    counts = {}
    for mode in ("0", "1"):
        monkeypatch.setenv("TPQ_PALLAS", mode)
        with DeviceFileReader(path) as r:
            for _ in r.iter_row_groups():
                pass
            counts[mode] = r.obs_registry().as_dict()["device"]["run_expand"]
    got = counts["0"]
    # 2 row groups x (k's indices, s's indices, s's definition levels)
    assert got["expansions"] == 6
    assert got["lanes"] >= 3 * n - int(nulls.sum())  # s indexes non-nulls
    assert 6 * 2 < got["runs"] < got["lanes"]  # many pages, many runs
    assert counts["1"] == got
