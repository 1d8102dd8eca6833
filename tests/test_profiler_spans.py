"""Program spans and program names in a ``jax.profiler`` capture.

A capture started by the caller (as a benchmark starts one) must hold the
program's own layer boundaries as ``tpq.<stage>`` host annotations, on the
device events' clock, and every plan program must run under a stable
``tpq_<family>`` name instead of its closure's.  The registry's ``device``
section is counted at dispatch, with no completion thread, and the span
bridge must cost next to nothing while no capture is live.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

import tpu_parquet.device_reader as dr
from tpu_parquet.obs import profiler_span

ROWS, GROUPS = 30_000, 3


def _write(path, table, **kw):
    pq.write_table(table, path, row_group_size=ROWS // GROUPS, **kw)
    return path


def _lineitem_like(path):
    """zstd, dictionary and nullable columns: the benchmark's layout."""
    rng = np.random.default_rng(3)
    qty = rng.integers(1, 51, ROWS).astype(float)
    qty[::7] = np.nan
    return _write(path, pa.table({
        "key": pa.array(np.arange(ROWS) // 4, pa.int64()),
        "qty": pa.array(qty, from_pandas=True),
        "flag": pa.array(rng.choice(["A", "N", "R"], ROWS)),
    }), compression="zstd")


def _host_events(trace_dir):
    """``[(name, start_ns, end_ns, line)]`` of the capture's host plane."""
    from jax.profiler import ProfileData

    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    assert pb, "the capture wrote no trace"
    out = []
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, line.name))
    return out


def _captured(trace_dir, fn):
    """Host events of a caller-started capture around ``fn()`` (no Python
    tracer, as the benchmark starts it)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def _block(groups):
    jax.block_until_ready([a for g in groups for c in g.values()
                           for a in (c.values, c.def_levels,
                                     getattr(c, "indices", None))
                           if a is not None])


@pytest.fixture(scope="module")
def scan_events(tmp_path_factory):
    d = tmp_path_factory.mktemp("scan_capture")
    path = _lineitem_like(str(d / "lineitem.parquet"))
    _block(list(dr.scan_files([path])))  # compile outside the capture
    return _captured(str(d / "xprof"),
                     lambda: _block(list(dr.scan_files([path]))))


@pytest.mark.parametrize("stage", ["open", "plan", "io", "decompress",
                                   "stage", "dispatch", "finalize"])
def test_scan_capture_holds_stage_span(scan_events, stage):
    names = [n for n, *_ in scan_events]
    assert f"tpq.{stage}" in names, sorted(set(n for n in names
                                               if n.startswith("tpq.")))


def test_scan_capture_names_programs_by_family(scan_events):
    calls = {n for n, *_ in scan_events if n.startswith("PjitFunction(")}
    assert "PjitFunction(fn)" not in calls
    # pyarrow dictionary-encodes all three columns
    assert "PjitFunction(tpq_gather)" in calls
    assert all(c.startswith("PjitFunction(tpq_") for c in calls
               if "tpq" in c or c == "PjitFunction(fn)")


def test_serve_request_nests_its_stages(tmp_path):
    from tpu_parquet.serve import ScanRequest, ScanService

    path = _lineitem_like(str(tmp_path / "served.parquet"))
    req = ScanRequest([path], columns=["key", "qty"], filter="key < 2000",
                      device=True)
    svc = ScanService()
    try:
        jax.block_until_ready(jax.tree_util.tree_leaves(
            svc.scan(req), is_leaf=lambda x: isinstance(x, jax.Array)))

        def serve():
            res = svc.scan(req)
            _block([c for cols in res.values() for c in
                    [{k: v if not isinstance(v, list) else v[0]}
                     for k, v in cols.items()]])

        events = _captured(str(tmp_path / "xprof"), serve)
    finally:
        svc.close()
    requests = [e for e in events if e[0] == "tpq.serve.request"]
    assert requests
    for child in ("tpq.cache_probe", "tpq.admission", "tpq.read"):
        inside = [e for e in events if e[0] == child and any(
            r[3] == e[3] and r[1] <= e[1] and e[2] <= r[2]
            for r in requests)]
        assert inside, f"no {child} inside a tpq.serve.request"


def _one_column(kind, path):
    rng = np.random.default_rng(7)
    req = lambda t: pa.schema([pa.field("x", t, nullable=False)])  # noqa
    if kind == "dictionary":
        return _write(path, pa.table({"x": rng.integers(0, 20, ROWS)},
                                     schema=req(pa.int64())))
    if kind == "plain":
        return _write(path, pa.table({"x": rng.integers(0, 1 << 40, ROWS)},
                                     schema=req(pa.int64())),
                      use_dictionary=False)
    if kind == "levels":
        # host-decoded values (byte stream split), levels on the device
        x = [None if i % 5 == 0 else float(i) for i in range(ROWS)]
        return _write(path, pa.table({"x": pa.array(x, pa.float64())}),
                      use_dictionary=False, use_byte_stream_split=["x"])
    if kind == "delta":
        return _write(path, pa.table({"x": np.arange(ROWS) * 3},
                                     schema=req(pa.int64())),
                      use_dictionary=False,
                      column_encoding={"x": "DELTA_BINARY_PACKED"})
    return _write(path, pa.table({"x": [f"v{i}" for i in range(ROWS)]},
                                 schema=req(pa.string())),
                  use_dictionary=False)


@pytest.mark.parametrize("kind,family", [
    ("dictionary", "gather"), ("plain", "plain"), ("levels", "levels"),
    ("delta", "unpack"), ("byte_array", "plain")])
def test_plan_program_named_by_family(kind, family, tmp_path, monkeypatch):
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "plain")
    path = _one_column(kind, str(tmp_path / f"{kind}.parquet"))
    seen = []
    real = dr._single_for

    def spy(key, fn):
        jitted = real(key, fn)
        seen.append((dr._kernel_family(key), jitted.__name__))
        return jitted

    monkeypatch.setattr(dr, "_single_for", spy)
    groups = list(dr.scan_files([path]))
    assert len(groups) == GROUPS
    assert set(seen) == {(family, f"tpq_{family}")}
    # the XLA module carries the name: jit_tpq_<family>
    program = dr._program(f"tpq_{family}", lambda x: x + 1, scope=family)
    assert f"@jit_tpq_{family} " in jax.jit(program).lower(1).as_text()


def test_fused_row_group_program_named(tmp_path, monkeypatch):
    monkeypatch.setattr(dr, "_FUSE_RG", True)
    path = _one_column("dictionary", str(tmp_path / "fused.parquet"))
    seen = []
    real = dr._fused_for

    def spy(key, fns, arities):
        jitted = real(key, fns, arities)
        seen.append(jitted.__name__)
        return jitted

    monkeypatch.setattr(dr, "_fused_for", spy)
    with dr.DeviceFileReader(path) as r:
        for _ in r.iter_row_groups():
            pass
        dev = r.obs_registry().as_dict()["device"]
    assert seen == ["tpq_fused_rg"] * GROUPS
    # one program per row group: one dispatch each
    assert dev["kernels"]["fused_rg"]["dispatches"] == GROUPS


def test_default_scan_registry_times_every_lane(tmp_path, monkeypatch):
    """A default (``prefetch=0``) scan times io and decompress on its
    sequential path, and finalize around the scan's one deferred check;
    every chunk is counted, and each family's ``bytes_out`` is the nbytes
    of what its programs returned."""
    monkeypatch.setenv("TPQ_FORCE_ROUTE", "plain")
    rng = np.random.default_rng(11)
    schema = pa.schema([pa.field("a", pa.int64(), nullable=False),
                        pa.field("b", pa.int32(), nullable=False)])
    path = _write(str(tmp_path / "plain.parquet"), pa.table({
        "a": rng.integers(0, 1 << 40, ROWS),
        "b": rng.integers(0, 1 << 20, ROWS).astype(np.int32)},
        schema=schema), compression="zstd", use_dictionary=False)
    tp = str(tmp_path / "trace.json")
    groups = list(dr.scan_files([path], trace=tp))
    with open(tp) as f:
        reg = json.load(f)["otherData"]["registry"]
    pipe = reg["pipeline"]
    for lane in ("io", "decompress", "finalize"):
        assert pipe[f"{lane}_seconds"] > 0, lane
    n_chunks = GROUPS * 2
    assert pipe["chunks"] == reg["reader"]["chunks"] == n_chunks
    dev = reg["device"]
    assert dev["dispatches"] == n_chunks
    out_bytes = sum(c.values.nbytes for g in groups for c in g.values())
    assert dev["kernels"]["plain"]["bytes_out"] == out_bytes
    assert dev["routes"]["plain"]["bytes_out"] == out_bytes
    assert dev["routes"]["plain"]["bytes_staged"] > 0


def test_no_completion_thread_after_scan(tmp_path):
    path = _lineitem_like(str(tmp_path / "threads.parquet"))
    before = {t.ident for t in threading.enumerate()}
    _block(list(dr.scan_files([path])))
    new = [t.name for t in threading.enumerate() if t.ident not in before]
    assert not [n for n in new if n.startswith("tpq-")], new


def test_span_cost_without_a_capture():
    """With no capture live a span is one flag check and the shared no-op:
    well under the microseconds a row group's stage takes."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert profiler_span("io") is profiler_span("dispatch")
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with profiler_span("decompress"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 2e-6, f"{best * 1e9:.0f} ns per span"
