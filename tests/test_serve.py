"""tpu_parquet.serve: the high-QPS concurrent scan service (ISSUE 10).

The contracts under test, in rough order of importance:

- N concurrent clients over ONE ScanService get responses BIT-IDENTICAL to
  sequential one-shot reads, at prefetch {0, 4} — and the shared PlanCache
  counters prove each file's footer was parsed exactly once;
- a full admission queue fast-rejects with a typed OverloadError (never a
  blocked caller);
- a request stalled inside the IO transport fires the per-request watchdog:
  a flight dump whose autopsy NAMES the stuck request, HangError for that
  caller, and untouched service for everyone else;
- the footer read-through cache (ROADMAP item 4's owed piece) keys on file
  generation — local mtime/size, or a ByteStore's identity token + size —
  and a mutated file invalidates cleanly;
- the ScanPlan IR (scanplan.py) serialize/deserialize round-trips, rejects
  lying blobs, and replays (route + pruning memos) bit-identically.
"""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from tpu_parquet.column import ByteArrayData, ColumnData
from tpu_parquet.errors import HangError, OverloadError, ParquetError
from tpu_parquet.format import CompressionCodec, FieldRepetitionType as FRT, Type
from tpu_parquet.iostore import FaultInjectingStore, FaultSpec, LocalStore
from tpu_parquet.reader import FileReader
from tpu_parquet.scanplan import (ScanPlan, build_scan_plan,
                                  predicate_fingerprint)
from tpu_parquet.schema.core import build_schema, data_column
from tpu_parquet.serve import PlanCache, ScanRequest, ScanService
from tpu_parquet.writer import FileWriter


def _strings(vals):
    return ColumnData(values=ByteArrayData(
        offsets=np.cumsum([0] + [len(v) for v in vals]),
        heap=np.frombuffer(b"".join(vals), np.uint8).copy(),
    ))


def _write_file(path, seed=0, groups=2, rows=600):
    rng = np.random.default_rng(seed)
    schema = build_schema([
        data_column("a", Type.INT64, FRT.REQUIRED),
        data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED),
    ])
    pool = [b"alpha", b"beta", b"gamma", b"delta", b"" ]
    with open(path, "wb") as fh:
        with FileWriter(fh, schema, codec=CompressionCodec.SNAPPY) as w:
            for _g in range(groups):
                svals = [pool[i] for i in rng.integers(0, len(pool), rows)]
                w.write_columns({
                    "a": rng.integers(-(1 << 40), 1 << 40, rows),
                    "s": _strings(svals),
                })
                w.flush_row_group()  # one row group per batch
    return path


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve")
    return [_write_file(str(d / f"f{i}.parquet"), seed=i) for i in range(3)]


def _assert_cols_equal(got, want):
    assert set(got) == set(want)
    for name in want:
        g, w = got[name], want[name]
        assert g.num_leaf_slots == w.num_leaf_slots
        if isinstance(w.values, ByteArrayData):
            np.testing.assert_array_equal(g.values.offsets, w.values.offsets)
            np.testing.assert_array_equal(g.values.heap, w.values.heap)
        else:
            np.testing.assert_array_equal(g.values, w.values)
        for attr in ("def_levels", "rep_levels"):
            gv, wv = getattr(g, attr), getattr(w, attr)
            assert (gv is None) == (wv is None)
            if wv is not None:
                np.testing.assert_array_equal(gv, wv)


# ---------------------------------------------------------------------------
# the acceptance hammer: 16 concurrent clients, bit-identical, parsed once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 4])
def test_concurrent_hammer_bit_identical_and_parsed_once(files, prefetch):
    projections = [None, ["a"], ["s"], ["a", "s"]]
    # the one-shot ground truth: fresh reader per (file, projection)
    expect = {}
    for path in files:
        for cols in projections:
            with FileReader(path, columns=cols) as r:
                expect[(path, tuple(cols or ()))] = r.read_all()

    svc = ScanService(concurrency=4, queue_depth=256)
    results = {}
    errors = []

    def client(ci):
        try:
            for qi in range(4):
                path = files[(ci + qi) % len(files)]
                cols = projections[(ci * 3 + qi) % len(projections)]
                res = svc.scan(ScanRequest(path, columns=cols,
                                           prefetch=prefetch), timeout=120)
                results[(ci, qi)] = ((path, tuple(cols or ())), res[path])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors, errors[:2]
        assert len(results) == 64
        for (key, got) in results.values():
            _assert_cols_equal(got, expect[key])
        c = svc.cache.counters()
        # footers parsed exactly ONCE per file across all 64 requests
        assert c["footer_misses"] == len(files)
        assert c["footer_hits"] == 64 - len(files)
        # plans built once per (file, projection); dictionaries decoded
        # once per (file, row group, dict column)
        assert c["plan_misses"] == len(files) * len(projections)
        assert c["plan_hits"] > 0
        assert c["dict_hits"] > 0
        st = svc.serve_stats()
        assert st["completed"] == 64 and st["failed"] == 0
    finally:
        svc.close()


def test_device_request_matches_host(files):
    with ScanService(concurrency=2) as svc:
        host = svc.scan(ScanRequest(files[0]))[files[0]]
        dev = svc.scan(ScanRequest(files[0], device=True))[files[0]]
    a = dev["a"]
    parts = a if isinstance(a, list) else [a]
    got = np.concatenate([np.asarray(p.to_host()) for p in parts])
    np.testing.assert_array_equal(got, host["a"].values)


def test_device_request_holds_its_slot_until_device_work_is_done(
        files, monkeypatch):
    """A worker waits for each file's device arrays before it moves on, so
    the service's concurrency bounds the device work in flight."""
    import dataclasses

    import jax

    from tpu_parquet.serve import service

    waited = []
    real = service._wait_on_device

    def spy(cols):
        real(cols)
        arrays = [getattr(p, f.name) for parts in cols.values()
                  for p in parts for f in dataclasses.fields(p)]
        waited.append(all(a.is_ready() for a in arrays
                          if isinstance(a, jax.Array)))

    monkeypatch.setattr(service, "_wait_on_device", spy)
    with ScanService(concurrency=2) as svc:
        svc.scan(ScanRequest(files[:2], device=True))
    assert waited == [True, True]  # one wait per file, arrays ready


def test_row_filter_request(files):
    from tpu_parquet.predicate import parse_filter

    with FileReader(files[0], row_filter=parse_filter("a > 0")) as r:
        want = r.read_all()
    with ScanService(concurrency=2) as svc:
        got = svc.scan(ScanRequest(files[0], filter="a > 0"))[files[0]]
        got2 = svc.scan(ScanRequest(files[0], filter="a > 0"))[files[0]]
    _assert_cols_equal(got, want)
    _assert_cols_equal(got2, want)


def test_admission_budget_backpressure(files):
    # a budget far below one request's estimate: requests serialize through
    # the shared InFlightBudget (charged at the cap) but ALL complete
    with ScanService(concurrency=4, max_memory=1 << 16) as svc:
        tickets = [svc.submit(ScanRequest(files[i % len(files)]))
                   for i in range(8)]
        for t in tickets:
            t.result(timeout=120)
        assert svc.serve_stats()["completed"] == 8


# ---------------------------------------------------------------------------
# overload fast-reject
# ---------------------------------------------------------------------------

def test_overload_fast_reject(files):
    stores = []

    def factory(f):
        st = FaultInjectingStore(
            LocalStore(f), FaultSpec(stall_first=1, stall_s=30.0))
        stores.append(st)
        return st

    svc = ScanService(concurrency=1, queue_depth=1, store=factory)
    try:
        t1 = svc.submit(ScanRequest(files[0]))   # occupies the one worker
        time.sleep(0.15)                         # let it enter the stall
        t2 = svc.submit(ScanRequest(files[1]))   # fills the queue
        t0 = time.perf_counter()
        with pytest.raises(OverloadError) as ei:
            svc.submit(ScanRequest(files[2]))
        assert time.perf_counter() - t0 < 1.0    # fast-reject, not a wait
        assert ei.value.queue_depth == 1
        assert svc.serve_stats()["rejected"] == 1
        # release ALL stalls, including stores created after this point
        # (t2's reader opens its own store once t1's worker frees up)
        stop = threading.Event()

        def releaser():
            while not stop.is_set():
                for st in list(stores):
                    st.release()
                time.sleep(0.02)

        rel = threading.Thread(target=releaser)
        rel.start()
        try:
            t1.result(timeout=120)
            t2.result(timeout=120)
        finally:
            stop.set()
            rel.join()
    finally:
        for st in stores:
            st.release()
        svc.close()


def test_close_fails_queued_requests(files):
    stores = []

    def factory(f):
        st = FaultInjectingStore(
            LocalStore(f), FaultSpec(stall_first=1, stall_s=5.0))
        stores.append(st)
        return st

    svc = ScanService(concurrency=1, queue_depth=4, store=factory)
    svc.submit(ScanRequest(files[0]))
    time.sleep(0.1)
    queued = svc.submit(ScanRequest(files[1]))
    stop = threading.Event()

    def releaser():
        while not stop.is_set():
            for st in list(stores):
                st.release()
            time.sleep(0.02)

    rel = threading.Thread(target=releaser)
    rel.start()
    try:
        svc.close()
        # close() fails queued-but-unstarted requests instead of hanging
        # them (a request the worker picked up before the drain completes
        # normally instead — both are legal outcomes)
        try:
            queued.result(timeout=30)
        except OverloadError:
            pass  # drained at close: the documented outcome
    finally:
        stop.set()
        rel.join()
    # a post-close submit is an error, not a silent enqueue
    with pytest.raises(RuntimeError):
        svc.submit(ScanRequest(files[0]))


# ---------------------------------------------------------------------------
# stalled request: watchdog fires, autopsy names it, others unaffected
# ---------------------------------------------------------------------------

def test_stalled_request_watchdog_autopsy(files, tmp_path, monkeypatch):
    dump_path = str(tmp_path / "serve_hang.json")
    monkeypatch.setenv("TPQ_FLIGHT", dump_path)
    stall_target = files[0]
    stores = []

    def factory(f):
        if getattr(f, "name", "") == stall_target:
            st = FaultInjectingStore(
                LocalStore(f), FaultSpec(stall_first=64, stall_s=60.0))
            stores.append(st)
            return st
        return LocalStore(f)

    svc = ScanService(concurrency=3, queue_depth=32, store=factory,
                      hang_s=1.0)
    try:
        stuck = svc.submit(ScanRequest(stall_target))
        healthy = [svc.submit(ScanRequest(files[1 + (i % 2)]))
                   for i in range(6)]
        # the other clients are never wedged by the stalled one
        for t in healthy:
            t.result(timeout=120)
        with pytest.raises(HangError) as ei:
            stuck.result(timeout=120)
        assert ei.value.dump_path and os.path.exists(ei.value.dump_path)
        with open(ei.value.dump_path) as f:
            doc = json.load(f)
        from tpu_parquet.obs import autopsy_dump

        rep = autopsy_dump(doc)
        # the dump's serve sample names the stuck request and its file
        sv = rep.get("serve")
        assert sv is not None and sv["stuck_request"] is not None
        assert sv["stuck_request"]["path"] == str(stall_target)
        assert rep["verdict"] == "network-stall"
        # ... and the CLI prints it
        buf = io.StringIO()
        from tpu_parquet.cli import pq_tool as _pt

        rc = _pt.cmd_autopsy(
            type("A", (), {"file": ei.value.dump_path})(), out=buf)
        assert rc == 0
        assert "stuck request" in buf.getvalue()
        # the service keeps serving after the hang
        after = svc.scan(ScanRequest(files[1]), timeout=120)
        assert after[files[1]]["a"].num_leaf_slots > 0
    finally:
        for st in stores:
            st.release()
        svc.close()


# ---------------------------------------------------------------------------
# footer read-through cache + invalidation (ROADMAP item 4 owed piece)
# ---------------------------------------------------------------------------

def test_footer_cache_local_mutation_invalidates(tmp_path):
    path = _write_file(str(tmp_path / "mut.parquet"), seed=1, groups=1,
                       rows=100)
    cache = PlanCache()
    meta1, _ = cache.footer(path)
    meta1b, _ = cache.footer(path)
    c = cache.counters()
    assert c["footer_misses"] == 1 and c["footer_hits"] == 1
    assert meta1 is meta1b
    # mutate the file between opens: more rows, and a forced mtime bump so
    # the generation moves even on coarse-mtime filesystems
    _write_file(path, seed=2, groups=1, rows=150)
    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    meta2, _ = cache.footer(path)
    assert meta2.num_rows == 150 and meta1.num_rows == 100
    c = cache.counters()
    assert c["footer_misses"] == 2
    assert c["invalidations"] >= 1  # the stale generation was dropped


def test_footer_cache_store_identity_token(files):
    data = open(files[0], "rb").read()

    class _MemStore(FaultInjectingStore):
        def __init__(self, blob, token):
            super().__init__(LocalStore(io.BytesIO(blob)),
                             identity_token=token)

    cache = PlanCache()
    s1 = _MemStore(data, "obj://bucket/f0@etag1")
    meta1, _ = cache.footer(None, store=s1)
    # a RE-OPENED store (new object, same token + size) hits the cache:
    # the footer is parsed once per object generation, not per open
    s2 = _MemStore(data, "obj://bucket/f0@etag1")
    meta2, _ = cache.footer(None, store=s2)
    c = cache.counters()
    assert meta2 is meta1
    assert c["footer_misses"] == 1 and c["footer_hits"] == 1
    # a changed object (new etag) invalidates cleanly
    s3 = _MemStore(data, "obj://bucket/f0@etag2")
    meta3, _ = cache.footer(None, store=s3)
    assert meta3 is not meta1
    assert cache.counters()["footer_misses"] == 2
    # no identity token: never cached, never stale
    s4 = _MemStore(data, None)
    cache.footer(None, store=s4)
    cache.footer(None, store=s4)
    assert cache.counters()["footer_hits"] == 1  # unchanged


def test_plan_cache_lru_eviction(files):
    cache = PlanCache(max_bytes=1)  # everything evicts immediately
    cache.footer(files[0])
    cache.footer(files[1])
    c = cache.counters()
    assert c["evictions"] >= 1
    assert c["entries"] <= 1  # the LRU bound held


def test_scan_files_plan_cache(files):
    from tpu_parquet.device_reader import scan_files

    def collect(**kw):
        out = []
        for cols in scan_files(files, columns=["a"], **kw):
            out.append(np.asarray(cols["a"].to_host()))
        return np.concatenate(out)

    base = collect()
    cache = PlanCache()
    first = collect(plan_cache=cache)
    second = collect(plan_cache=cache)
    np.testing.assert_array_equal(base, first)
    np.testing.assert_array_equal(base, second)
    c = cache.counters()
    assert c["footer_misses"] == len(files)
    assert c["footer_hits"] >= len(files)  # the second sweep re-parsed nothing


# ---------------------------------------------------------------------------
# ScanPlan IR: round-trip, rejection, replay
# ---------------------------------------------------------------------------

def test_scanplan_roundtrip_and_cache_key(files):
    with FileReader(files[0]) as r:
        plan = r._plan
        assert plan is not None
        blob = plan.serialize()
    p2 = ScanPlan.deserialize(blob)
    assert p2.cache_key() == plan.cache_key()
    assert p2.serialize() == blob
    assert [rg.ordinal for rg in p2.row_groups] == [0, 1]
    assert p2.estimated_bytes() == plan.estimated_bytes() > 0


def test_scanplan_rejects_lying_blobs():
    from tpu_parquet.fuzz import crafted_scan_plan_blobs

    blobs = crafted_scan_plan_blobs()
    ScanPlan.deserialize(blobs[0])  # the good one adopts
    for bad in blobs[1:]:
        with pytest.raises(ParquetError):
            ScanPlan.deserialize(bad)


def test_scanplan_route_memo_replay_bit_identical(files):
    from tpu_parquet.device_reader import DeviceFileReader

    with DeviceFileReader(files[0]) as r1:
        base = [{k: np.asarray(v.to_host() if hasattr(v, "to_host") else v)
                 for k, v in g.items()} for g in r1.iter_row_groups()]
        plan = r1._plan
    routes = plan.routes_table()
    assert routes, "first scan must memoize its route choices"
    replay = ScanPlan.deserialize(plan.serialize())
    assert replay.routes_table() == routes
    with DeviceFileReader(files[0], plan=replay) as r2:
        assert r2._plan is replay
        again = [{k: np.asarray(v.to_host() if hasattr(v, "to_host") else v)
                  for k, v in g.items()} for g in r2.iter_row_groups()]
    assert len(base) == len(again)
    for g1, g2 in zip(base, again):
        for k in g1:
            np.testing.assert_array_equal(g1[k], g2[k])


def test_scanplan_mismatched_plan_falls_back(files):
    # a plan built for a different projection must NOT be adopted
    with FileReader(files[0], columns=["a"]) as r:
        narrow_plan = r._plan
    with FileReader(files[0], columns=["a", "s"], plan=narrow_plan) as r2:
        assert r2._plan is not narrow_plan  # rebuilt, not wrongly replayed
        out = r2.read_all()
        assert set(out) == {"a", "s"}


def test_predicate_fingerprint_stability():
    from tpu_parquet.predicate import col

    a = (col("a") > 5) & (col("s") == "x")
    b = (col("a") > 5) & (col("s") == "x")
    assert predicate_fingerprint(a) == predicate_fingerprint(b)
    assert predicate_fingerprint(a) != predicate_fingerprint(col("a") > 6)
    assert predicate_fingerprint(None) is None


def test_device_reader_pruning_memo(files, tmp_path):
    # sorted data so page pruning has stats to work with
    path = str(tmp_path / "sorted.parquet")
    schema = build_schema([data_column("a", Type.INT64, FRT.REQUIRED)])
    with open(path, "wb") as fh:
        with FileWriter(fh, schema, codec=CompressionCodec.SNAPPY) as w:
            w.write_columns({"a": np.arange(4000)})
            w.flush_row_group()
            w.write_columns({"a": np.arange(4000, 8000)})
    from tpu_parquet.device_reader import DeviceFileReader
    from tpu_parquet.predicate import col

    pred = col("a") >= 6000

    def scan(plan=None):
        with DeviceFileReader(path, row_filter=pred, plan=plan) as r:
            out = [np.asarray(g["a"].to_host())
                   for g in r.iter_row_groups()]
            return out, r._plan
    base, plan = scan()
    assert plan.pruning_hint(1) is not None  # memoized on the first scan
    again, _ = scan(plan=ScanPlan.deserialize(plan.serialize()))
    assert len(base) == len(again)
    for x, y in zip(base, again):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# obs wiring: registry section, doctor verdict, serve-stats CLI
# ---------------------------------------------------------------------------

def test_registry_serve_section_and_merge(files):
    with ScanService(concurrency=2) as svc:
        svc.scan(ScanRequest(files[0]))
        reg = svc.obs_registry()
    tree = reg.as_dict()
    sv = tree["serve"]
    assert sv["submitted"] == 1 and sv["completed"] == 1
    assert "cache" in sv and sv["cache"]["footer_misses"] == 1
    assert {"serve.queue_wait", "serve.exec", "serve.request"} <= set(
        tree["histograms"])
    # cross-process style merge: flows add, gauges max
    from tpu_parquet.obs import StatsRegistry

    other = StatsRegistry()
    other.merge_dict(tree)
    other.merge_dict(tree)
    t2 = other.as_dict()
    assert t2["serve"]["submitted"] == 2
    assert t2["serve"]["queue_depth_peak"] == sv["queue_depth_peak"]
    assert (t2["serve"]["cache"]["capacity_bytes"]
            == sv["cache"]["capacity_bytes"])


def test_doctor_admission_bound():
    from tpu_parquet.obs import doctor_registry

    tree = {
        "pipeline": {"stage_seconds": 0.2, "io_seconds": 0.1,
                     "stall_seconds": 0.0},
        "reader": {},
        "serve": {"queue_wait_seconds": 5.0, "exec_seconds": 0.5},
    }
    rep = doctor_registry(tree)
    assert rep["verdict"] == "admission-bound"
    assert rep["dominant_lane"] == "admission"
    # without the serve section the old verdicts are untouched
    rep2 = doctor_registry({"pipeline": {"stage_seconds": 0.2},
                            "reader": {}})
    assert rep2["verdict"] == "link-bound"


def test_serve_stats_cli(files, tmp_path):
    with ScanService(concurrency=2) as svc:
        for _ in range(3):
            svc.scan(ScanRequest(files[0]))
        tree = svc.obs_registry().as_dict()
    path = str(tmp_path / "reg.json")
    with open(path, "w") as f:
        json.dump(tree, f)
    from tpu_parquet.cli import pq_tool

    buf = io.StringIO()
    rc = pq_tool.cmd_serve_stats(
        type("A", (), {"file": path, "config": None})(), out=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "3 submitted" in out and "cache hits" in out and "p95" in out
    # a registry with no serve section is a one-line diagnosis, not a crash
    with open(path, "w") as f:
        json.dump({"obs_version": 1}, f)
    buf2 = io.StringIO()
    rc2 = pq_tool.cmd_serve_stats(
        type("A", (), {"file": path, "config": None})(), out=buf2)
    assert rc2 == 1 and "no `serve` section" in buf2.getvalue()


def test_overload_error_is_not_parquet_error():
    # load shedding must never look like malformed input to the fuzz
    # oracle or to quarantine containment
    assert not issubclass(OverloadError, ParquetError)
    assert not issubclass(OverloadError, IOError)
    e = OverloadError("full", queue_depth=4, in_flight=2)
    assert e.queue_depth == 4 and e.in_flight == 2


def test_service_thread_hygiene(files):
    before = {t.name for t in threading.enumerate()
              if t.name.startswith(("tpq-serve", "tpq-watchdog"))}
    svc = ScanService(concurrency=3, hang_s=60.0)
    svc.scan(ScanRequest(files[0]))
    svc.close()
    time.sleep(0.05)
    after = {t.name for t in threading.enumerate()
             if t.name.startswith(("tpq-serve", "tpq-watchdog"))}
    assert after <= before  # close() leaks no workers or watchdogs


# ---------------------------------------------------------------------------
# multi-tenant QoS (ISSUE 17): fair-share scheduling, tenant accounting
# ---------------------------------------------------------------------------

def test_fair_scheduler_drr_and_fifo():
    from tpu_parquet.serve import FairScheduler

    q = FairScheduler(64, fair=True)
    for i in range(3):
        q.put_nowait("noisy", 1, f"n{i}")
    for i in range(3):
        q.put_nowait("victim", 3, f"v{i}")
    order = [q.get() for _ in range(6)]
    # weight 3 buys the victim a 3-long run per noisy dequeue once its
    # queue is live — the flood cannot fence it out
    assert order.index("v0") <= 1 and order.index("v2") <= 4, order
    fifo = FairScheduler(64, fair=False)
    fifo.put_nowait("noisy", 1, "n0")
    fifo.put_nowait("victim", 3, "v0")
    fifo.put_nowait("noisy", 1, "n1")
    assert [fifo.get() for _ in range(3)] == ["n0", "v0", "n1"]


def test_fair_share_protects_victim_p99(files):
    # one worker + deterministic per-range latency + result cache OFF:
    # the queueing discipline is the only variable.  Noisy requests are
    # CHEAPER than the victim's (one column vs two), so under fair-share
    # the victim pays at most one residual noisy request — within 2x its
    # isolated p99 — while FIFO parks it behind the whole flood.
    from tpu_parquet.iostore import IOConfig

    lat, noisy_n, path = 0.012, 12, files[0]

    def mk(fair):
        svc = ScanService(
            concurrency=1, queue_depth=64, fair=fair, result_cache_mb=0,
            store=lambda f: FaultInjectingStore(
                LocalStore(f), FaultSpec(latency_s=lat),
                config=IOConfig(backoff_ms=1.0)))
        svc.register_tenant("victim", weight=4)
        svc.register_tenant("noisy", weight=1)
        # warm the footer/plan caches so the timed phase is pure data IO
        svc.scan(ScanRequest(path, tenant="victim"), timeout=60)
        return svc

    def victim_p99(svc, flood):
        tickets = [svc.submit(ScanRequest(path, columns=["a"],
                                          tenant="noisy"))
                   for _ in range(noisy_n if flood else 0)]
        walls = []
        for _ in range(4):
            t0 = time.perf_counter()
            svc.scan(ScanRequest(path, tenant="victim"), timeout=60)
            walls.append(time.perf_counter() - t0)
        for t in tickets:
            t.result(60)
        return max(walls)

    # the fair bound sits AT the theoretical residual (victim pays one
    # in-flight noisy request), so in-suite scheduler jitter can tip a
    # single measurement over it — re-measure the whole trio a few times
    # and accept any clean attempt (weather, not discipline, is what a
    # lone miss on this 2-core box measures)
    for attempt in range(3):
        svc = mk(True)
        iso = victim_p99(svc, flood=False)
        svc.close()
        svc = mk(True)
        fair = victim_p99(svc, flood=True)
        tstats = svc.serve_stats()["tenants"]
        svc.close()
        svc = mk(False)
        fifo = victim_p99(svc, flood=True)
        svc.close()
        if fair <= 2.0 * iso < fifo:
            break
    # the acceptance bar: fair-share holds the victim within 2x isolated;
    # FIFO demonstrably does not (same flood, same worker, same costs)
    assert fair <= 2.0 * iso, (iso, fair, fifo)
    assert fifo > 2.0 * iso, (iso, fair, fifo)
    # both tenants really ran, and the registry kept their books apart
    assert tstats["victim"]["submitted"] == 5
    assert tstats["noisy"]["submitted"] == noisy_n


def test_tenant_budget_slices_and_shed_accounting(files):
    from tpu_parquet.errors import CheckpointError  # noqa: F401 (import rail)

    svc = ScanService(concurrency=1, queue_depth=1, max_memory=1 << 20)
    try:
        svc.register_tenant("gold", weight=3)
        svc.register_tenant("bronze", weight=1)
        # budget slices follow weights: gold holds 3/5 of max_memory
        # (default tenant keeps its weight-1 share)
        slices = {n: t.budget.max_bytes
                  for n, t in svc.tenants.tenants().items()}
        assert slices["gold"] == 3 * slices["bronze"]
        # overflow rejections land on the SUBMITTING tenant's book, and
        # the typed error names it with a backoff hint
        plug = svc.submit(ScanRequest(files[0], tenant="gold"))
        shed = None
        for _ in range(12):
            try:
                svc.submit(ScanRequest(files[0], tenant="bronze"))
            except OverloadError as e:
                shed = e
                break
        plug.result(60)
        assert shed is not None and "bronze" in str(shed)
        assert shed.retry_after_s > 0
        st = svc.serve_stats()
        assert st["tenants"]["bronze"]["rejected"] >= 1
        assert st["tenants"]["gold"]["rejected"] == 0
        assert st["retry_after_hint_s"] > 0
    finally:
        svc.close()


def test_registry_tenants_subtree_and_merge(files):
    with ScanService(concurrency=1) as svc:
        svc.register_tenant("team-a", weight=2, slo_p99_ms=50.0)
        svc.scan(ScanRequest(files[0], tenant="team-a"))
        svc.scan(ScanRequest(files[0]))
        tree = svc.obs_registry().as_dict()
    sv = tree["serve"]
    ta = sv["tenants"]["team-a"]
    assert ta["submitted"] == ta["completed"] == 1
    assert ta["weight"] == 2 and ta["slo_p99_ms"] == 50.0
    assert {"rejected", "sheds", "cache_held_bytes", "budget_bytes",
            "rows"} <= set(ta)
    assert sv["tenants"]["default"]["submitted"] == 1
    assert "serve.tenant.team-a" in tree["histograms"]
    # merge discipline: lifecycle flows add, config/state gauges max
    from tpu_parquet.obs import StatsRegistry

    other = StatsRegistry()
    other.merge_dict(tree)
    other.merge_dict(tree)
    t2 = other.as_dict()["serve"]["tenants"]["team-a"]
    assert t2["submitted"] == 2 and t2["weight"] == 2


def test_doctor_overload_names_offending_tenant():
    from tpu_parquet.obs import OVERLOAD_MIN_REJECTS, doctor_registry

    tree = {
        "pipeline": {"io_seconds": 1.0}, "reader": {},
        "serve": {
            "queue_wait_seconds": 0.2, "rejected": 5,
            "sheds": {"low": 2, "normal": 0}, "retry_after_hint_s": 0.4,
            "tenants": {
                "noisy": {"submitted": 50, "rejected": 1},
                "victim": {"submitted": 2, "rejected": 4},
            },
        },
    }
    rep = doctor_registry(tree)
    ov = rep["overload"]
    assert ov["verdict"] == "overload"
    assert ov["offending_tenant"] == "noisy"  # demand, not reject count
    assert ov["victims"] == ["victim"]
    assert "noisy" in ov["advice"] and ov["retry_after_hint_s"] == 0.4
    # below the threshold the verdict stays silent (routine backpressure)
    tree["serve"]["rejected"] = OVERLOAD_MIN_REJECTS - 1 - 2  # sheds=2 ride
    assert "overload" not in doctor_registry(tree)


def test_serve_stats_cli_tenants(files, tmp_path):
    with ScanService(concurrency=1) as svc:
        svc.register_tenant("team-a", weight=2, slo_p99_ms=75.0)
        svc.scan(ScanRequest(files[0], tenant="team-a"))
        svc.scan(ScanRequest(files[0], stream=True, batch_rows=256))
        tree = svc.obs_registry().as_dict()
    path = str(tmp_path / "reg.json")
    with open(path, "w") as f:
        json.dump(tree, f)
    from tpu_parquet.cli import pq_tool

    buf = io.StringIO()
    rc = pq_tool.cmd_serve_stats(
        type("A", (), {"file": path, "config": None})(), out=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "tenants:" in out and "team-a" in out and "slo 75" in out
    assert "streaming: 1 session(s)" in out
    assert "tenant.team-a" in out  # the per-tenant SLO histogram row


def test_tenant_env_spec(monkeypatch):
    from tpu_parquet.serve import parse_tenant_spec
    from tpu_parquet.serve.tenancy import TenantRegistry, fair_enabled

    # lenient by contract: a bare name defaults to weight 1, a malformed
    # weight clamps to 1 — a bad env var must not take the serve tier down
    assert parse_tenant_spec("a=3, b=1,junk,c=x,=9,") == {
        "a": (3, None), "b": (1, None), "junk": (1, None), "c": (1, None)}
    # optional :deadline_s rides the weight field; malformed or
    # non-positive deadlines degrade to None, never raise
    assert parse_tenant_spec("gold=4:2.5,slow=1:x,neg=2:-1") == {
        "gold": (4, 2.5), "slow": (1, None), "neg": (2, None)}
    monkeypatch.setenv("TPQ_SERVE_TENANTS", "gold=4,bronze=1")
    reg = TenantRegistry(max_memory=6 << 20)
    assert reg.get("gold").weight == 4
    assert reg.get("gold").budget.max_bytes == 4 * (1 << 20)
    monkeypatch.setenv("TPQ_SERVE_FAIR", "0")
    assert not fair_enabled(None)
    assert fair_enabled(True)  # the explicit flag outranks the env


def test_tenants_kwarg_coercion(files):
    # the natural call shapes all land in a real registry: a {name:
    # weight} mapping, a spec string, or a TenantRegistry — and anything
    # else is a TypeError at CONSTRUCTION, not an AttributeError deep in
    # submit()
    with ScanService(concurrency=1,
                     tenants={"gold": 3, "bronze": 1}) as svc:
        svc.scan(ScanRequest(files[0], tenant="gold"))
        svc.scan(ScanRequest(files[0]))  # tenant-less rides "default"
        tens = svc.obs_registry().as_dict()["serve"]["tenants"]
    assert tens["gold"]["weight"] == 3 and tens["gold"]["submitted"] == 1
    assert tens["default"]["submitted"] == 1
    with ScanService(concurrency=1, tenants="gold=3,bronze=1") as svc:
        assert svc.tenants.get("gold").weight == 3
    with pytest.raises(TypeError, match="tenants="):
        ScanService(concurrency=1, tenants=42)


def test_doctor_overload_on_serve_only_registry():
    # an overload where NOTHING got far enough to decode is exactly when
    # the operator reaches for doctor: no lane seconds must not mean no
    # verdict (the early None return lets overload evidence through)
    from tpu_parquet.obs import doctor_registry

    tree = {"serve": {"rejected": 6, "sheds": {"low": 0, "normal": 0},
                      "tenants": {"hog": {"submitted": 25, "rejected": 0},
                                  "v": {"submitted": 1, "rejected": 6}}}}
    rep = doctor_registry(tree)
    assert rep is not None and "lanes" not in rep
    assert rep["overload"]["offending_tenant"] == "hog"
    # a quiet serve-only tree still returns None (nothing to say)
    assert doctor_registry({"serve": {"rejected": 1}}) is None
