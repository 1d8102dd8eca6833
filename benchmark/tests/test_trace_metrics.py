"""CPU tests of the readers that take the window's profiler trace apart by
program and host span: ``device.gather_share``, ``device.gather_roofline``
and ``serve.idle_in_request_share``, on synthetic device intervals and host
spans, and on the recorded chip trace (whose program names no family, as a
program without family names would).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os
import shutil
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import window_trace as wt  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "tiny_v5e.xplane.pb")
PEAKS = {"hbm_bytes_per_s": 819e9}


def _ctx(chips, host=(), window=(0, 1000), loops=()):
    t = wt.WindowTrace(window, chips, list(host))
    return {wt.KEY: t, "window": {"loops": list(loops)}, "peaks": PEAKS}


def _chip(busy, modules=()):
    return {"busy": list(busy), "modules": list(modules)}


def _loop(*gather_bytes):
    return {"registries": [
        {"device": {"kernels": {"gather": {"bytes_out": b}}}}
        for b in gather_bytes]}


def test_overlap_of_sorted_interval_lists():
    assert wt.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert wt.overlap([(0, 10)], [(10, 20)]) == 0
    assert wt.overlap([], [(0, 5)]) == 0
    assert wt.overlap([(0, 100)], [(10, 20), (30, 40), (90, 120)]) == 30


def test_gather_share_is_family_time_over_busy_time():
    read = harness.load_metric("device.gather_share").read
    chip = _chip([(0, 100), (150, 250)],
                 [("jit_tpq_gather", 0, 80), ("jit_tpq_plain", 150, 250)])
    assert read(_ctx([chip], window=(0, 300))) == pytest.approx(40.0)
    # summed over chips; the window clips both sides
    other = _chip([(0, 400)], [("jit_tpq_gather", 0, 400)])
    assert read(_ctx([chip, other], window=(0, 300))) == pytest.approx(
        100.0 * (80 + 300) / (200 + 300))


def test_gather_share_reads_nothing_without_family_names():
    read = harness.load_metric("device.gather_share").read
    chip = _chip([(0, 100)], [("jit_fn", 0, 100)])
    assert read(_ctx([chip])) is None


def test_gather_roofline_is_bytes_at_peak_over_family_time():
    read = harness.load_metric("device.gather_roofline").read
    # one second of gather programs that returned 81.9 GB: a tenth of the
    # peak's 819 GB
    chip = _chip([(0, 1e9)], [("jit_tpq_gather", 0, 1e9)])
    ctx = _ctx([chip], window=(0, 2e9), loops=[_loop(40.95e9, 40.95e9)])
    assert read(ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("case", ["no_counter", "no_program"])
def test_gather_roofline_reads_nothing_without_its_inputs(case):
    read = harness.load_metric("device.gather_roofline").read
    if case == "no_counter":
        # a registry from before dispatch counters: no bytes_out
        chip = _chip([(0, 100)], [("jit_tpq_gather", 0, 100)])
        loops = [{"registries": [{"device": {"routes": {}}}, {}]}]
    else:
        chip = _chip([(0, 100)], [("jit_fn", 0, 100)])
        loops = [_loop(1000)]
    assert read(_ctx([chip], loops=loops)) is None


def test_idle_in_request_counts_idle_under_open_requests():
    read = harness.load_metric("serve.idle_in_request_share").read
    # idle 200-600; requests open 100-300 and 500-700 (overlapping
    # requests count once): 200 of 1000 ns idle inside a request
    chip = _chip([(0, 200), (600, 1000)])
    host = [("tpq.serve.request", 100, 300), ("tpq.serve.request", 150, 250),
            ("tpq.serve.request", 500, 700), ("bench.request", 0, 1000)]
    assert read(_ctx([chip], host)) == pytest.approx(20.0)
    # a second, always-busy chip halves the average
    busy = _chip([(0, 1000)])
    assert read(_ctx([chip, busy], host)) == pytest.approx(10.0)


def test_idle_in_request_reads_nothing_without_request_spans():
    read = harness.load_metric("serve.idle_in_request_share").read
    chip = _chip([(0, 200)])
    assert read(_ctx([chip], [("bench.request", 0, 1000)])) is None


def test_load_is_memoized_and_none_without_a_trace(tmp_path):
    ctx = {"run": SimpleNamespace(work=str(tmp_path), devices=[0]),
           "window": {}}
    assert wt.load(ctx) is None
    assert wt.KEY in ctx
    ctx[wt.KEY] = marker = object()
    assert wt.load(ctx) is marker


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded chip trace")
def test_recorded_trace_loads_and_family_readers_stay_silent(tmp_path):
    """The recorded v5e trace (a jitted a + 1, PR 22) names no family and
    no request: the three readers read nothing and raise nothing, and the
    loaded busy time is trace_reduce's."""
    os.makedirs(tmp_path / "xprof" / "plugins")
    shutil.copy(RECORDED, tmp_path / "xprof" / "plugins" / "t.xplane.pb")
    ctx = {"run": SimpleNamespace(work=str(tmp_path), devices=[0]),
           "window": {"loops": [_loop(1 << 20)]},
           "peaks": harness.peaks("TPU v5 lite")}
    t = wt.load(ctx)
    assert t is not None and len(t.chips) == 1
    assert t.busy_s() == pytest.approx(
        trace_reduce.reduce(RECORDED, n_devices=1)["busy_s"])
    for name in ("device.gather_share", "device.gather_roofline",
                 "serve.idle_in_request_share"):
        assert harness.load_metric(name).read(ctx) is None
