"""Benchmark: device (TPU) columnar decode vs host (NumPy) columnar decode.

Output contract (round-6 artifact plumbing — the r04/r05 one-line JSON
overflowed the driver's 2000-char tail window, leaving the binding record
unparseable):

- FULL results are written as indented multi-line JSON to the artifact file
  (``BENCH_JSON`` env, default ``BENCH_LOCAL_latest.json`` next to this
  script);
- stdout's LAST line is ONE compact JSON summary, guaranteed < 2000 chars:
    {"metric": ..., "value": N, "unit": "rows/s", "vs_baseline": N,
     "artifact": ..., "configs": {<scalar highlights only>}}
Everything else goes to stderr.

Configs mirror BASELINE.md (sizes scaled to keep a driver run in minutes;
scale with BENCH_SCALE):

  1 plain_int64    single INT64 PLAIN column, SNAPPY
  2 delta_ints     INT32 + INT64 DELTA_BINARY_PACKED
  3 dict_strings   BYTE_ARRAY STRING dictionary, RLE_DICTIONARY indices
  4 lineitem16     TPC-H lineitem, all 16 columns, mixed encodings  [headline]
  5 nested         LIST + MAP logical types (pyarrow-written, NYC-taxi-like)

Per config: device rows/s + decoded MB/s, host rows/s, device/host ratio.
The headline "value"/"vs_baseline" is config 4 — the full-width mixed schema.

"value" is end-to-end device-path decode throughput: file open → footer → per
chunk IO → host decompress + native structure parse → XLA kernels → device
arrays, blocked until ready (columns stay on device; that is the product).
"vs_baseline" divides by the host NumPy columnar decoder on the same file — a
*stricter* denominator than the pure-Go reference (value-at-a-time,
interface-dispatched, one boxed value per datum; SURVEY.md §3.1 hot loops),
which cannot run here (no Go toolchain in the image).  pyarrow (Arrow C++) is
additionally timed on the identical files as an independent cross-check
denominator.  Since round 3, PLAIN BYTE_ARRAY value streams also decode on
device (host walks only the length prefixes — device_reader.py), so no
config carries a host-bound value-decode share anymore.

Sampling protocol (disclosed here and in README) — SYMMETRIC since round 5:
- the within-sample estimator is the MEDIAN on BOTH sides of every ratio:
  a device window's median of reps vs the baselines' median of
  BENCH_BASELINE_REPS reps — no side gets min-of-n noise rejection the
  other lacks (the round-1..4 asymmetry).
- across WINDOWS the device estimate is the best window median.  Windows
  exist because the early remote backend's link suffered exogenous
  multi-minute congestion that does not touch the CPU-bound baselines; selecting the
  cleanest window selects measurement CONDITIONS, not lucky reps — the
  within-window median still rejects per-rep noise.  Every window's full
  rep list and its link probe ship in the JSON (device_windows_s,
  host_reps_s, pyarrow_reps_s, link_mb_per_sec_*), so any other estimator
  can be recomputed from the artifact.
- EVERY config's device reps are sampled in up to 1 + BENCH_RESAMPLE
  time-separated windows (default 3 total) — because the early remote
  backend's link showed transient multi-minute congestion (its probes
  recorded 93 MB/s and 1.5 GB/s within one run); a single burst of back-to-back
  reps samples only one weather window.  The best-window selection
  above spans them.
  Resample windows stop early at 60% of the time budget so the baselines
  (phase B) always fit.
- link bandwidth is probed (one 64 MB transfer) before and after phase A and
  recorded in the JSON, so a depressed headline is attributable from the
  artifact itself.

A ``pipeline`` section (BENCH_PIPELINE=0 to skip) benches the overlapped
chunk pipeline at host decode prefetch={0,4} — on the headline file AND on
plain_int64 (the round-4 ≥0.9×-host target, re-measured against the overlap
path) — with the per-stage counters (overlap efficiency = busy/wall) from
``FileReader.pipeline_stats()``.  A ``loader`` section (BENCH_LOADER=0 to
skip) measures one shuffled ``data.DataLoader`` epoch over the headline
file's fixed-width columns at prefetch={0,4} vs a raw ``scan_files`` pass.

Env knobs: BENCH_SCALE (default 1.0), BENCH_DEVICE_REPS (default 4),
BENCH_BASELINE_REPS (default: one below device reps, capped at 3),
BENCH_CONFIGS (comma list, default "4,2,3,1,5" — headline banked first),
BENCH_RESAMPLE (default 2 — extra sampling windows over all configs),
BENCH_JSON (artifact path).

Run ledger + regression gate (round-10 — tpu_parquet/ledger.py): every run
appends its full record (config, git rev, env fingerprint, registry trees,
per-rep timings) to an append-only ``ledger.jsonl`` next to the artifact
(``TPQ_LEDGER`` overrides; ``--no-ledger`` skips).  ``--check-against
BASELINE`` (a bench artifact, a ledger, or ``ledger.jsonl#N``) gates the
run: per-metric deltas with noise bounds from rep variance
(BENCH_CHECK_FLOOR, default 0.30), exit 2 on a regression beyond noise —
the compact stdout line is ALWAYS emitted first, so the driver still gets
its record.  A run that FAILS the gate is not recorded to the ledger
(its numbers still land in the artifact + compact line): with the ledger
as the baseline, recording the red run would make it the next run's
baseline and ratchet the regression in after a single red build.  ``--smoke`` shrinks to one tiny config with every optional
section off: the end-to-end plumbing exercise CI runs in seconds.
"""

import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))
# device reps are cheap (~0.1-1s each warm); best-of-4 rides out the
# link-congestion windows that can depress a single rep 2-4x
REPS = int(os.environ.get("BENCH_DEVICE_REPS", "4"))
# baselines are the slow half of the budget: one rep fewer than the device
# (the asymmetry is disclosed in the module docstring and the output JSON)
BASELINE_REPS = int(os.environ.get("BENCH_BASELINE_REPS",
                                   str(max(min(REPS - 1, 3), 1))))
# two extra windows by default: the early remote backend's logs show the link swinging
# 136->1500 MB/s across minutes; the window loop is budget-guarded, so a
# slow run simply takes fewer windows
RESAMPLE = int(os.environ.get("BENCH_RESAMPLE", "2"))
WHICH = os.environ.get("BENCH_CONFIGS", "4,2,3,1,5").split(",")
# soft wall-clock budget: finish the current config, then emit JSON with
# whatever was measured (the driver must ALWAYS get its one line)
TIME_BUDGET = float(os.environ.get("BENCH_TIME_BUDGET", "600"))
_T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tpu_parquet.datagen import (  # noqa: E402
    gen_lineitem16, pool_col as _pool_col, strings_col as _strings_col,
    writer as _writer,
)


# ---------------------------------------------------------------------------
# generators (cached in /tmp, one-time)
# ---------------------------------------------------------------------------

def gen_plain_int64(path, rows):
    import numpy as np
    from tpu_parquet.format import FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column

    rng = np.random.default_rng(1)
    schema = build_schema([data_column("v", Type.INT64, FRT.REQUIRED)])
    with _writer(path, schema, use_dictionary=False) as w:
        for lo in range(0, rows, 2_000_000):
            n = min(2_000_000, rows - lo)
            w.write_columns({"v": rng.integers(-(1 << 62), 1 << 62, n)})


def gen_delta_ints(path, rows):
    import numpy as np
    from tpu_parquet.format import Encoding, FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column

    rng = np.random.default_rng(2)
    schema = build_schema([
        data_column("k64", Type.INT64, FRT.REQUIRED),
        data_column("d32", Type.INT32, FRT.REQUIRED),
    ])
    with _writer(
        path, schema, use_dictionary=False,
        column_encodings={"k64": Encoding.DELTA_BINARY_PACKED,
                          "d32": Encoding.DELTA_BINARY_PACKED},
    ) as w:
        key = 0
        for lo in range(0, rows, 2_000_000):
            n = min(2_000_000, rows - lo)
            keys = key + np.cumsum(rng.integers(1, 9, n))
            key = int(keys[-1])
            w.write_columns({
                "k64": keys.astype(np.int64),
                "d32": (10000 + rng.integers(0, 5000, n)).astype(np.int32),
            })


def gen_dict_strings(path, rows):
    import numpy as np
    from tpu_parquet.format import (
        ConvertedType, FieldRepetitionType as FRT, LogicalType, StringType, Type,
    )
    from tpu_parquet.schema.core import ColumnParameters, build_schema, data_column

    rng = np.random.default_rng(3)
    pool = [f"supplier_name_{i:04d}".encode() for i in range(1000)]
    schema = build_schema([
        data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED, ColumnParameters(
            logical_type=LogicalType(STRING=StringType()),
            converted_type=ConvertedType.UTF8)),
    ])
    with _writer(path, schema, use_dictionary=True) as w:
        for lo in range(0, rows, 2_000_000):
            n = min(2_000_000, rows - lo)
            w.write_columns({"s": _strings_col(rng, n, pool)})


def gen_nested(path, rows):
    """NYC-taxi-like nested shapes, written by pyarrow (foreign writer).

    TWO files (BASELINE config 5 is a multi-file row-group scan); the bench
    paths discover the `.part2` sibling and scan both."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    # the .part2 sibling is written FIRST: the main file is the generation
    # cache key, so its existence must imply the sibling exists too
    for part, (seed, out) in enumerate([(6, path + ".part2"), (5, path)]):
        rng = np.random.default_rng(seed)
        n = rows // 2 if part == 0 else rows - rows // 2
        lens = rng.integers(0, 5, n)
        flat = rng.integers(0, 300, int(lens.sum()))
        offs = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(lens, out=offs[1:])
        zones = pa.ListArray.from_arrays(pa.array(offs), pa.array(flat))
        keys = ["fare", "tip", "tolls"]
        mk = [{k: float(rng.uniform(1, 60)) for k in keys[: rng.integers(1, 4)]}
              for _ in range(256)]
        t = pa.table({
            "trip_id": np.arange(n, dtype=np.int64),
            "zones": zones,
            "charges": pa.array([mk[i % 256] for i in range(n)],
                                type=pa.map_(pa.string(), pa.float64())),
            "distance": rng.uniform(0.3, 40.0, n),
        })
        pq.write_table(t, out, compression="snappy", row_group_size=1 << 20)


def _bench_paths(path):
    """The config's file set: the main file plus the multi-file siblings."""
    sib = path + ".part2"
    return [path, sib] if os.path.exists(sib) else [path]


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _uncompressed_mb(path):
    from tpu_parquet.reader import FileReader

    total = 0
    for p in _bench_paths(path):
        with FileReader(p) as r:
            total += sum(
                cc.meta_data.total_uncompressed_size or 0
                for rg in r.metadata.row_groups for cc in rg.columns
            )
    return total / 1e6


def _device_run(path):
    import jax
    from tpu_parquet.device_reader import scan_files

    outs = []
    # one continuous pipeline across the config's whole file set (the
    # multi-file dataset scan of BASELINE config 5)
    for cols in scan_files(_bench_paths(path)):
        outs.extend(cols.values())
    arrs = [a for o in outs
            for a in (o.values, o.offsets, o.heap,
                      getattr(o, "indices", None))
            if a is not None]
    jax.block_until_ready(arrs)


def device_reps(path, rows, reps, tag=""):
    """Timed device reps (caller ensures executables are warm); returns the
    list of rep times (the caller pools samples across windows and takes the
    MEDIAN — see the sampling-protocol docstring)."""
    out = []
    for i in range(reps):
        t0 = time.perf_counter()
        _device_run(path)
        dt = time.perf_counter() - t0
        log(f"  device rep{tag} {i}: {dt:.3f}s ({rows/dt/1e6:.2f} M rows/s)")
        out.append(dt)
    return out


def _median(xs):
    xs = sorted(xs)
    m = len(xs) // 2
    return xs[m] if len(xs) % 2 else 0.5 * (xs[m - 1] + xs[m])


def _best_window(windows):
    """THE device estimator (see the sampling-protocol docstring): median
    within each window, cleanest window across.  Single definition so the
    resample loop and every phase-B ratio can never diverge."""
    return min(_median(w) for w in windows)


def probe_link(mb=64):
    """One host→device transfer of ``mb`` MB, recorded in the output JSON so a
    congested-link run is attributable from the artifact itself.  Doubles as
    the transfer warm-up (the link ramps up over the first transfers)."""
    import jax
    import numpy as np

    a = np.zeros(mb << 20, dtype=np.uint8)
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(a))
    rate = mb / (time.perf_counter() - t0)
    log(f"link probe: {rate:.0f} MB/s ({mb} MB)")
    return round(rate, 1)


def bench_device(path, rows, name=""):
    from tpu_parquet.device_reader import DeviceFileReader
    from tpu_parquet.obs import StatsRegistry, Tracer

    _device_run(path)  # warm: XLA executables cached after this
    samples = device_reps(path, rows, REPS)
    # observability from one instrumented pass (SURVEY.md §5.5), accumulated
    # over every file of the config (multi-file nested scan) into ONE
    # obs.StatsRegistry tree (histograms + ship feedback included — the
    # artifact carries the planner's predicted-vs-measured lane seconds).
    # The ship-planner counters (per-route link bytes — ship.py) prove the
    # link-byte cut from the artifact alone: `link_bytes_shipped` vs
    # `link_bytes_logical` is the transfer the planner removed.
    # With TPQ_TRACE=<base> set, the instrumented pass additionally writes a
    # Perfetto-loadable trace artifact per config at <base>.<config>.json.
    ship = {"link_bytes_shipped": 0, "link_bytes_logical": 0,
            "ship_routes": {}}
    reg = StatsRegistry()
    trace_base = (_TRACE_BASE if _TRACE_BASE is not None
                  else os.environ.get("TPQ_TRACE", ""))
    tracer = Tracer(path=f"{trace_base}.{name}.json") if trace_base else None
    for p in _bench_paths(path):
        with DeviceFileReader(p, trace=tracer) as r:
            for cols in r.iter_row_groups():
                pass
            d = r.stats().as_dict()
            log(f"  reader stats[{os.path.basename(p)}]: {d}")
            reg.merge_from(r.obs_registry())
            ship["link_bytes_shipped"] += d["link_bytes_shipped"]
            ship["link_bytes_logical"] += d["link_bytes_logical"]
            for route, c in d["ship_routes"].items():
                agg = ship["ship_routes"].setdefault(
                    route, {"streams": 0, "logical": 0, "shipped": 0})
                for k in agg:
                    agg[k] += c[k]
    if ship["link_bytes_logical"]:
        ship["link_bytes_ratio"] = round(
            ship["link_bytes_shipped"] / ship["link_bytes_logical"], 4)
    if tracer is not None:
        log(f"  trace artifact: {tracer.write(registry=reg)}")
    ship["obs"] = reg.as_dict()
    # the per-route device dispatch counters (device time per kernel
    # family comes from a profiler capture)
    dev = ship["obs"].get("device")
    if dev:
        log(f"  device lanes: dispatches={dev.get('dispatches')} "
            f"bytes_out={dev.get('bytes_out')} "
            f"routes={sorted((dev.get('routes') or {}))} "
            f"h2d_bytes={(dev.get('h2d') or {}).get('bytes')}")
    else:
        log("  device lanes: n/a")
    return samples, ship


def bench_pyarrow(path, rows):
    """Independent cross-check denominator: pyarrow.parquet.read_table on the
    identical files (Apache Arrow C++, multi-threaded).  The self-measured
    NumPy host decoder stays the primary vs_baseline denominator (it mirrors
    the reference's single-threaded decode loop); this number anchors it
    against code this repo didn't write."""
    import pyarrow.parquet as pq

    def run():
        for p in _bench_paths(path):
            pq.read_table(p)

    run()
    samples = []
    for i in range(BASELINE_REPS):
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        log(f"  pyarrow rep {i}: {dt:.3f}s ({rows/dt/1e6:.2f} M rows/s)")
        samples.append(dt)
    return samples


def bench_host(path, rows, upload=False):
    """Host NumPy decode; with ``upload``, decoded arrays are also staged to
    the device — the apples-to-apples pipeline baseline, since the device
    path's output is already HBM-resident."""
    import jax
    import numpy as np
    from tpu_parquet.column import ByteArrayData
    from tpu_parquet.reader import FileReader

    def run():
        staged = []
        for p in _bench_paths(path):
            with FileReader(p) as r:
                for rg in r.iter_row_groups():
                    if upload:
                        for cd in rg.values():
                            v = cd.values
                            if isinstance(v, ByteArrayData):
                                staged.append(jax.device_put(v.offsets))
                                staged.append(jax.device_put(v.heap))
                            else:
                                staged.append(
                                    jax.device_put(np.ascontiguousarray(v)))
        if staged:
            jax.block_until_ready(staged)

    run()
    samples = []
    for i in range(BASELINE_REPS):
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        tag = "host+upload" if upload else "host"
        log(f"  {tag} rep {i}: {dt:.3f}s ({rows/dt/1e6:.2f} M rows/s)")
        samples.append(dt)
    return samples


CONFIGS = {
    "1": ("plain_int64", gen_plain_int64, 10_000_000),
    "2": ("delta_ints", gen_delta_ints, 10_000_000),
    "3": ("dict_strings", gen_dict_strings, 10_000_000),
    "4": ("lineitem16", gen_lineitem16, 5_000_000),
    "5": ("nested", gen_nested, 2_000_000),
}


def bench_writes(rows=2_000_000, reps=2):
    """Writer throughput (host encode; the reference ships write benchmarks,
    floor/writer_test.go:606-647, but records no numbers).  Data is built
    in memory first so the timing covers ONLY the write; pyarrow writes the
    identical data as the independent denominator."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from tpu_parquet.format import (
        ConvertedType, FieldRepetitionType as FRT, LogicalType, StringType,
        Type,
    )
    from tpu_parquet.schema.core import (
        ColumnParameters, build_schema, data_column,
    )

    rng = np.random.default_rng(7)
    S = lambda: ColumnParameters(
        logical_type=LogicalType(STRING=StringType()),
        converted_type=ConvertedType.UTF8)

    def strings(pool):
        idx = rng.integers(0, len(pool), rows)
        return _pool_col(idx, pool), pa.array([pool[i].decode() for i in idx])

    pool = [f"supplier_name_{i:04d}".encode() for i in range(1000)]
    scol, sarr = strings(pool)
    ints = rng.integers(-(1 << 62), 1 << 62, rows)
    li_np = {
        "l_orderkey": np.cumsum(rng.integers(1, 5, rows)).astype(np.int64),
        "l_partkey": rng.integers(1, 200_000, rows),
        "l_quantity": rng.integers(1, 51, rows),
        "l_extendedprice": rng.uniform(900, 105_000, rows),
    }
    mcol, marr = strings([b"AIR", b"FOB", b"MAIL", b"RAIL", b"SHIP"])
    cases = {
        "write_plain_int64": (
            build_schema([data_column("v", Type.INT64, FRT.REQUIRED)]),
            {"v": ints}, dict(use_dictionary=False),
            pa.table({"v": ints}), dict(use_dictionary=False),
        ),
        "write_dict_strings": (
            build_schema([data_column("s", Type.BYTE_ARRAY, FRT.REQUIRED,
                                      S())]),
            {"s": scol}, dict(use_dictionary=True),
            pa.table({"s": sarr}), {},
        ),
        "write_lineitem5": (
            build_schema(
                [data_column(k, Type.DOUBLE if v.dtype == np.float64
                             else Type.INT64, FRT.REQUIRED)
                 for k, v in li_np.items()]
                + [data_column("l_shipmode", Type.BYTE_ARRAY, FRT.REQUIRED,
                               S())]),
            {**li_np, "l_shipmode": mcol}, dict(use_dictionary=True),
            pa.table({**li_np, "l_shipmode": marr}), {},
        ),
    }
    out = {}
    import io as _io

    for name, (schema, data, kw, patab, pakw) in cases.items():
        best = pa_best = float("inf")
        for _ in range(reps):
            # memory sinks on BOTH sides: the doc contract is "timing covers
            # ONLY the write", and this VM's disk writeback (85-156 ms per
            # 16 MB, with truncate-flush stalls on rewrite) was the
            # dominant, weather-like term for whichever writer ran second
            t0 = time.perf_counter()
            with _writer(_io.BytesIO(), schema, **kw) as w:
                w.write_columns(data)
            best = min(best, time.perf_counter() - t0)
            t0 = time.perf_counter()
            pq.write_table(patab, pa.BufferOutputStream(),
                           compression="snappy", **pakw)
            pa_best = min(pa_best, time.perf_counter() - t0)
        out[name] = {
            "rows": rows,
            "write_rows_per_sec": round(rows / best, 1),
            "pyarrow_write_rows_per_sec": round(rows / pa_best, 1),
            "write_vs_pyarrow": round(pa_best / best, 3),
        }
        log(f"{name}: {rows / best / 1e6:.1f} M rows/s "
            f"({pa_best / best:.2f}x pyarrow write)")
    return out


def bench_write_scale(smoke=False):
    """ISSUE 15 acceptance: the write side of scale.

    Two phases, banked to the ledger like every section:

    - ``encode``: N-worker sharded encode (write.write_sharded, the merged
      single-file layout — bit-identity with the single writer is the
      tier-1 test's job, the bench banks throughput) vs the single-writer
      baseline over the SAME batches; ``encode_speedup`` is the headline.
    - ``compaction``: a fragmented many-small-files dataset compacted to
      few large through the ship planner's codec replanning; banks
      before/after file counts and the planner-modeled link-byte ratio.

    Skip with BENCH_WRITE=0; ``--smoke`` runs it tiny.  The exit-3
    thread-leak gate is unchanged: the encode pool joins inside
    write_sharded, nothing daemonized outlives the section.
    """
    import shutil
    import tempfile

    import numpy as np
    from tpu_parquet.format import FieldRepetitionType as FRT, Type
    from tpu_parquet.schema.core import build_schema, data_column
    from tpu_parquet.write import WriteStats, compact, write_sharded
    from tpu_parquet.writer import FileWriter

    rng = np.random.default_rng(11)
    rows_per_rg = 20_000 if smoke else 500_000
    n_rgs = 4 if smoke else 12
    workers = int(os.environ.get("BENCH_WRITE_WORKERS",
                                 str(min(os.cpu_count() or 1, 8))))
    schema = build_schema([
        data_column("k", Type.INT64, FRT.REQUIRED),
        data_column("v", Type.DOUBLE, FRT.REQUIRED),
    ])
    batches = [{"k": rng.integers(0, 1 << 40, rows_per_rg).astype(np.int64),
                "v": rng.random(rows_per_rg)} for _ in range(n_rgs)]
    total_rows = rows_per_rg * n_rgs
    tmp = tempfile.mkdtemp(prefix="tpq-bench-write-")
    out = {}
    try:
        # single-writer baseline (same batches, same row-group cuts)
        single = os.path.join(tmp, "single.parquet")
        t0 = time.perf_counter()
        with FileWriter(single, schema) as w:
            for b in batches:
                w.write_columns(b)
                w.flush_row_group()
        single_s = time.perf_counter() - t0

        st = WriteStats()
        merged = os.path.join(tmp, "merged.parquet")
        t0 = time.perf_counter()
        res = write_sharded(merged, schema, batches, workers=workers,
                            stats=st)
        sharded_s = time.perf_counter() - t0
        same = (os.path.getsize(single) == os.path.getsize(merged))
        out["encode"] = {
            "rows": total_rows,
            "row_groups": n_rgs,
            "workers": st.workers,
            "single_writer_s": round(single_s, 4),
            "sharded_s": round(sharded_s, 4),
            "encode_speedup": round(single_s / sharded_s, 3),
            "sharded_rows_per_sec": round(total_rows / sharded_s, 1),
            "bytes_written": res.bytes_written,
            "size_matches_single": bool(same),
            "stall_seconds": round(st.stall_seconds, 4),
        }
        log(f"write_scale encode: {workers} workers "
            f"{total_rows / sharded_s / 1e6:.2f} M rows/s "
            f"({single_s / sharded_s:.2f}x single writer)")

        # compaction: fragment the same data into many small files first
        frag = os.path.join(tmp, "frag")
        os.makedirs(frag)
        small = []
        for i, b in enumerate(batches):
            for j, lo in enumerate(range(0, rows_per_rg,
                                         max(rows_per_rg // 4, 1))):
                hi = min(lo + max(rows_per_rg // 4, 1), rows_per_rg)
                p = os.path.join(frag, f"in-{i:03d}-{j}.parquet")
                with FileWriter(p, schema) as w:
                    w.write_columns({k: v[lo:hi] for k, v in b.items()})
                small.append(p)
        t0 = time.perf_counter()
        rep = compact(small, out=frag, workers=workers)
        compact_s = time.perf_counter() - t0
        d = rep.as_dict()
        d["compact_s"] = round(compact_s, 4)
        out["compaction"] = d
        log(f"write_scale compaction: {d['files_before']} -> "
            f"{d['files_after']} files, link ratio "
            f"{d['link_bytes_ratio']:.3f} in {compact_s:.2f}s")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_pipeline(path, rows, reps=3):
    """Overlapped-chunk-pipeline bench (ISSUE 1 acceptance gate): host
    decode of the lineitem16 file at prefetch={0,4} — same file, same
    decoder, only the pipeline depth differs — plus the per-stage counters
    that make the speedup attributable (overlap efficiency = sum of stage
    seconds / wall seconds; 1.0 is perfectly serial)."""
    from tpu_parquet.reader import FileReader

    out = {"rows": rows}
    for k in (0, 4):
        best = float("inf")
        best_stats = None
        for i in range(reps):
            t0 = time.perf_counter()
            with FileReader(path, prefetch=k) as r:
                r.read_all()
                st = r.pipeline_stats()
            dt = time.perf_counter() - t0
            log(f"  pipeline prefetch={k} rep {i}: {dt:.3f}s "
                f"({rows/dt/1e6:.2f} M rows/s)")
            if dt < best:
                best, best_stats = dt, st.as_dict()
        out[f"prefetch{k}_s"] = round(best, 3)
        out[f"prefetch{k}_rows_per_sec"] = round(rows / best, 1)
        if k:
            for key in ("io_seconds", "decompress_seconds", "stall_seconds",
                        "busy_seconds", "overlap_efficiency",
                        "peak_in_flight_bytes"):
                out[key] = best_stats[key]
    out["pipeline_speedup"] = round(out["prefetch0_s"] / out["prefetch4_s"], 3)
    log(f"pipeline: {out['pipeline_speedup']:.2f}x at prefetch=4 "
        f"(overlap efficiency {out['overlap_efficiency']:.2f})")
    return out


def bench_loader(path, rows, reps=None):
    """Training-input loader bench (ISSUE 2 acceptance gate): one shuffled
    epoch of ``data.DataLoader`` over the lineitem16 fixed-width columns at
    prefetch={0,4} — same files, same shuffle seed, only the overlap depth
    differs — plus a raw ``scan_files`` pass over the same columns as the
    no-shuffle/no-batch reference.  Reps INTERLEAVE the two depths (this
    VM's weather — page-cache drops, CPU steal — lasts seconds to minutes,
    so alternating reps exposes both sides to the same conditions; own
    back-to-back trials have recorded the same config at 3.0s and 6.8s)."""
    import jax
    from tpu_parquet.data import DataLoader
    from tpu_parquet.device_reader import scan_files

    if reps is None:
        reps = int(os.environ.get("BENCH_LOADER_REPS", "4"))
    reps = max(reps, 1)  # 0 reps would leave the medians/stats unpopulated
    # (skip the section with BENCH_LOADER=0 instead)
    cols = ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
            "l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_shipdate", "l_commitdate", "l_receiptdate"]
    # dedicated file with TRAINING-shaped row groups (~250k rows each, vs the
    # decode bench's single-transfer-optimized 1M-row groups): the loader
    # pipelines at unit granularity, and a 5-unit file spends 15% of its
    # wall on the first unit's cold decode that lookahead can never hide
    # layout-stamped name: a cached file from a build with different group
    # sizing can never be silently reused
    lpath = f"{path}.loader_rg250k"
    if not os.path.exists(lpath):
        t0 = time.perf_counter()
        gen_lineitem16(lpath, rows, rows_per_group=250_000)
        log(f"generated {lpath} in {time.perf_counter()-t0:.1f}s")
    path = lpath
    out = {"rows": rows, "batch_size": 8192, "columns": len(cols),
           "rows_per_group": 250_000}
    for p in _bench_paths(path):  # warm the page cache off the timed path
        with open(p, "rb", buffering=0) as f:
            while f.read(32 << 20):
                pass
    warm = DataLoader(_bench_paths(path), 8192, columns=cols, shuffle=True,
                      seed=11, prefetch=2, shuffle_window=1 << 16,
                      drop_remainder=True)
    for _ in warm:  # one untimed epoch: allocator/thread warmup off both sides
        pass
    times = {0: [], 4: []}
    last_stats = None
    emitted = 0
    for i in range(reps):
        for k in (0, 4):
            loader = DataLoader(_bench_paths(path), 8192, columns=cols,
                                shuffle=True, seed=11, prefetch=k,
                                shuffle_window=1 << 16, drop_remainder=True)
            t0 = time.perf_counter()
            emitted = 0
            for batch in loader:
                emitted += len(batch["l_orderkey"])
            dt = time.perf_counter() - t0
            log(f"  loader prefetch={k} rep {i}: {dt:.3f}s "
                f"({emitted/dt/1e6:.2f} M rows/s)")
            times[k].append(dt)
            if k:
                last_stats = loader.stats().as_dict()
                last_obs = loader.obs_registry().as_dict()
    # MEDIAN of the interleaved reps on BOTH sides (the repo's symmetric-
    # estimator rule): best-of would hand the ratio to whichever depth got
    # the one quiet window on this weather-prone VM
    for k in (0, 4):
        out[f"prefetch{k}_s"] = round(_median(times[k]), 3)
        out[f"prefetch{k}_reps_s"] = [round(t, 3) for t in times[k]]
        out[f"prefetch{k}_rows_per_sec"] = round(emitted / _median(times[k]), 1)
    out["decode_wait_seconds"] = last_stats["decode_wait_seconds"]
    out["window_peak_rows"] = last_stats["window_peak_rows"]
    out["obs"] = last_obs  # registry tree (histograms incl.) for the artifact
    out["rows_emitted"] = emitted
    out["loader_speedup"] = round(out["prefetch0_s"] / out["prefetch4_s"], 3)
    # raw device scan of the identical columns: what the loader's shuffle +
    # batch assembly + host residency cost against the bare multi-file scan.
    # MEDIAN of reps, like the loader sides above — the symmetric-estimator
    # rule applies to this ratio too.
    try:
        scans = []
        for _ in range(reps):
            t0 = time.perf_counter()
            arrs = []
            for colsd in scan_files(_bench_paths(path), columns=cols):
                arrs.extend(v.values for v in colsd.values()
                            if v.values is not None)
            jax.block_until_ready(arrs)
            scans.append(time.perf_counter() - t0)
        out["scan_files_reps_s"] = [round(t, 3) for t in scans]
        out["scan_files_rows_per_sec"] = round(rows / _median(scans), 1)
        out["loader_vs_scan"] = round(
            (emitted / _median(times[4]))
            / (rows / _median(scans)), 3)
    except Exception as e:  # noqa: BLE001 — reference only
        log(f"loader scan reference FAILED: {e!r}")
    log(f"loader: {out['loader_speedup']:.2f}x at prefetch=4 "
        f"({out['prefetch4_rows_per_sec']/1e6:.2f} M rows/s shuffled)")
    return out


def bench_io_faults(path, rows, reps=3):
    """Fault-tolerant IO backend bench (ISSUE 7 acceptance gate): the
    lineitem16 host decode through three store configurations —

    - ``local``: the default ``LocalStore`` path.  Banked to the ledger so
      ``--check-against`` guards the zero-fault overhead of the store
      indirection (the pre-PR pipeline numbers are the same file/decoder).
    - ``generic``: a zero-fault ``FaultInjectingStore`` (the
      GenericRangeStore machinery + range coalescing, nothing injected) —
      the pure cost of the retry/coalescing bookkeeping.
    - ``faults``: fixed injected latency per store round trip plus one
      transient error on ~1/8 of ranges — overlap efficiency shows how
      much of the injected latency the prefetch pool hides, and the retry
      counters prove the faults actually fired.
    """
    from tpu_parquet.iostore import (FaultInjectingStore, FaultSpec,
                                     IOConfig, LocalStore)
    from tpu_parquet.reader import FileReader

    inject_s = 2e-4
    cfg = IOConfig(retries=4, backoff_ms=1.0, retry_budget=0)
    flaky = FaultSpec(latency_s=inject_s, fail_first=1,
                      match=lambda off, size: (off >> 12) % 8 == 0)
    stores = {
        "local": None,
        "generic": lambda f: FaultInjectingStore(
            LocalStore(f), FaultSpec(), config=cfg, seed=0),
        "faults": lambda f: FaultInjectingStore(
            LocalStore(f), flaky, config=cfg, seed=0),
    }
    out = {"rows": rows, "injected_latency_s": inject_s}
    for tag, factory in stores.items():
        best, best_tree = float("inf"), None
        for i in range(reps):
            t0 = time.perf_counter()
            with FileReader(path, prefetch=4, store=factory) as r:
                r.read_all()
                tree = r.obs_registry().as_dict()
            dt = time.perf_counter() - t0
            log(f"  io_faults {tag} rep {i}: {dt:.3f}s "
                f"({rows/dt/1e6:.2f} M rows/s)")
            if dt < best:
                best, best_tree = dt, tree
        out[f"{tag}_s"] = round(best, 3)
        out[f"{tag}_rows_per_sec"] = round(rows / best, 1)
        out[f"{tag}_overlap_efficiency"] = (
            best_tree["pipeline"]["overlap_efficiency"])
        if best_tree["io"] is not None:
            io_tree = best_tree["io"]
            out[f"{tag}_retries"] = io_tree["retries"]
            out[f"{tag}_coalesced_spans"] = io_tree["coalesced_spans"]
            out[f"{tag}_store_reads"] = io_tree["reads"]
    # the two ratios the section exists for: indirection cost on the local
    # path (gate target <= 1.02x) and the injected-fault recovery cost
    out["store_overhead_ratio"] = round(out["generic_s"] / out["local_s"], 3)
    out["fault_overhead_ratio"] = round(out["faults_s"] / out["local_s"], 3)
    log(f"io_faults: store overhead {out['store_overhead_ratio']:.3f}x, "
        f"with faults {out['fault_overhead_ratio']:.3f}x "
        f"({out.get('faults_retries', 0)} retries recovered)")
    return out


def bench_data_faults(path, rows, reps=3):
    """Corruption-containment bench (ISSUE 8 acceptance gate), two halves:

    - the clean path: the lineitem16 host decode with validation OFF vs the
      round-13 default (``validate="crc"``; bench files carry CRCs) —
      ``validate_overhead_ratio`` is the <1.03x guard the default-on tier
      must hold;
    - the dirty path: a copy of the file with ~1 corrupt page per 100 is
      read under ``skip_unit`` — ``quarantined`` proves the faults fired
      and were contained, ``faulty_s`` what a degraded scan costs.
    """
    import shutil

    from tpu_parquet.reader import FileReader
    from tpu_parquet.writer import corrupt_page

    out = {"rows": rows}
    for tag, validate in (("novalidate", False), ("validate", "crc")):
        best = float("inf")
        for i in range(reps):
            t0 = time.perf_counter()
            with FileReader(path, prefetch=4, validate_crc=validate) as r:
                r.read_all()
            dt = time.perf_counter() - t0
            log(f"  data_faults {tag} rep {i}: {dt:.3f}s "
                f"({rows/dt/1e6:.2f} M rows/s)")
            best = min(best, dt)
        out[f"{tag}_s"] = round(best, 3)
        out[f"{tag}_rows_per_sec"] = round(rows / best, 1)
    out["validate_overhead_ratio"] = round(
        out["validate_s"] / out["novalidate_s"], 3)

    dirty = path + ".corrupt"
    shutil.copyfile(path, dirty)
    try:
        from tpu_parquet.footer import read_file_metadata

        with open(dirty, "rb") as f:
            md = read_file_metadata(f)
        n_cols = len(md.row_groups[0].columns or [])
        corrupted = 0
        for gi in range(len(md.row_groups)):
            # ~1 corrupt page per 100 columns-chunks, deterministic spread
            for ci in range(n_cols):
                if (gi * n_cols + ci) % 100 == 0:
                    corrupt_page(dirty, row_group=gi, column=ci,
                                 mode="bitflip", seed=gi * 131 + ci)
                    corrupted += 1
        best, q = float("inf"), None
        for i in range(reps):
            t0 = time.perf_counter()
            with FileReader(dirty, prefetch=4,
                            on_data_error="skip_unit") as r:
                r.read_all()
                q = r.quarantine
            dt = time.perf_counter() - t0
            log(f"  data_faults skip_unit rep {i}: {dt:.3f}s "
                f"({q.units_skipped} unit(s) skipped)")
            best = min(best, dt)
        out["faulty_s"] = round(best, 3)
        out["pages_corrupted"] = corrupted
        out["quarantined"] = len(q.log)
        out["units_skipped"] = q.units_skipped
    finally:
        os.unlink(dirty)
    log(f"data_faults: validate overhead "
        f"{out['validate_overhead_ratio']:.3f}x (gate <= 1.03), "
        f"{out['quarantined']}/{out['pages_corrupted']} corruptions "
        f"quarantined under skip_unit")
    return out


def bench_serve(path, rows, clients_sweep=(1, 4, 16)):
    """High-QPS scan service bench (ISSUE 10): a concurrency sweep over ONE
    shared ScanService vs the same queries run sequentially one-shot.

    Each of N client threads runs Q queries (rotating column projections,
    host decode) through a shared service whose PlanCache holds footers,
    ScanPlan IR, and decoded dictionaries; the one-shot baseline opens a
    fresh FileReader per query — paying the footer parse, the plan build,
    and the dictionary decode every time.  Reports per-clients wall +
    p50/p95 request latency + cache hit rate, and ``plan_cache_speedup``:
    one-shot per-query wall / served-at-1-client per-query wall (same
    concurrency, so the delta IS the shared-state win).  Skip with
    BENCH_SERVE=0; ``--smoke`` exercises it end to end.
    """
    import threading

    from tpu_parquet.reader import FileReader
    from tpu_parquet.serve import ScanRequest, ScanService

    q_per_client = int(os.environ.get("BENCH_SERVE_QUERIES", "6"))
    with FileReader(path) as r0:
        cols = [".".join(l.path) for l in r0.schema.selected_leaves()]
    projections = [None, cols[: max(len(cols) // 2, 1)], cols[:1]]
    out = {"rows": rows, "queries_per_client": q_per_client}

    # one-shot baseline: fresh reader per query, nothing shared
    t0 = time.perf_counter()
    for i in range(q_per_client):
        with FileReader(path, columns=projections[i % len(projections)]) as r:
            r.read_all()
    oneshot_s = time.perf_counter() - t0
    out["oneshot_wall_s"] = round(oneshot_s, 4)
    out["oneshot_per_query_s"] = round(oneshot_s / q_per_client, 5)
    log(f"  serve one-shot: {q_per_client} queries in {oneshot_s:.3f}s")

    for clients in clients_sweep:
        svc = ScanService(concurrency=min(clients, 8),
                          queue_depth=max(2 * clients, 4))
        errors = []

        def run_client(ci):
            try:
                for i in range(q_per_client):
                    svc.scan(ScanRequest(
                        path, columns=projections[(ci + i)
                                                  % len(projections)]))
            except Exception as e:  # noqa: BLE001 — reported, not fatal
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=run_client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        tree = svc.obs_registry().as_dict()
        svc.close()
        sv = tree["serve"]
        cache = sv["cache"]
        hits = sum(cache[f"{k}_hits"] for k in ("footer", "plan", "dict"))
        total = hits + sum(cache[f"{k}_misses"]
                           for k in ("footer", "plan", "dict"))
        hist = (tree.get("histograms") or {}).get("serve.request") or {}
        nq = clients * q_per_client
        from tpu_parquet.obs import LatencyHistogram as _LH
        p99_s = _LH.from_dict(hist).quantile(0.99) if hist else 0.0
        entry = {
            "wall_s": round(wall, 4),
            "per_query_s": round(wall / nq, 5),
            "queries": nq,
            "p50_ms": round(float(hist.get("p50_seconds", 0.0)) * 1e3, 3),
            "p95_ms": round(float(hist.get("p95_seconds", 0.0)) * 1e3, 3),
            "p99_ms": round(p99_s * 1e3, 3),
            "cache_hit_rate": round(hits / total, 4) if total else 0.0,
            "queue_wait_s": sv["queue_wait_seconds"],
        }
        if errors:
            entry["errors"] = errors[:3]
        out[f"clients{clients}"] = entry
        log(f"  serve {clients} client(s): {nq} queries in {wall:.3f}s "
            f"(p95 {entry['p95_ms']:.1f}ms, p99 {entry['p99_ms']:.1f}ms, "
            f"hit rate {entry['cache_hit_rate']:.0%})")
    c1 = out.get("clients1")
    if c1 and c1["per_query_s"]:
        out["plan_cache_speedup"] = round(
            out["oneshot_per_query_s"] / c1["per_query_s"], 3)
        log(f"serve: plan_cache_speedup "
            f"{out['plan_cache_speedup']:.2f}x (shared plan/footer/dict "
            f"cache vs one-shot opens)")
    return out


def bench_serve_cache(path, rows, smoke=False):
    """Tiered result-cache A/B over the serve tier (ISSUE 14).

    Three phases, all against real ``ScanService`` instances:

    1. **hot/cold A/B** — the same repeated scan of the bench file with the
       result tier OFF (``result_cache_mb=0`` — the PR 10 plan/footer/dict
       cache baseline) vs ON; banks per-phase p50 and
       ``warm_speedup_p50`` (cold p50 / warm p50 — the decode work a hot
       request no longer does);
    2. **zipfian mix** — a hot-set + long-tail access pattern over K small
       generated files with the cache sized to hold roughly the hot set:
       banks p50/p95/p99 and per-tier hit rates (the realistic "millions
       of users re-scan hot files" shape);
    3. **mutation mid-sweep** — a warmed file is rewritten in place
       (generation moves): banks the exact ``invalidations`` delta and
       proves the served bytes are the NEW file's, never stale.

    Skip with BENCH_SERVE_CACHE=0; ``--smoke`` runs every phase tiny.
    """
    import shutil
    import tempfile

    import numpy as np

    from tpu_parquet.obs import LatencyHistogram as _LH
    from tpu_parquet.serve import ScanRequest, ScanService

    reps = 6 if smoke else int(os.environ.get("BENCH_SERVE_CACHE_QUERIES",
                                              "16"))
    out = {"rows": rows, "queries": reps}

    def latencies(svc, reqs):
        lat = []
        for rq in reqs:
            t0 = time.perf_counter()
            svc.scan(rq)
            lat.append(time.perf_counter() - t0)
        lat.sort()
        return lat

    def q(lat, f):
        return lat[min(int(f * len(lat)), len(lat) - 1)] if lat else 0.0

    # -- phase 1: hot/cold A/B on the bench file ---------------------------
    with ScanService(concurrency=2, result_cache_mb=0) as svc:
        svc.scan(ScanRequest(path))  # warm the plan/footer/dict cache
        cold = latencies(svc, [ScanRequest(path) for _ in range(reps)])
    with ScanService(concurrency=2, result_cache_mb=1024) as svc:
        svc.scan(ScanRequest(path))  # one populating scan
        warm = latencies(svc, [ScanRequest(path) for _ in range(reps)])
        ch = svc.cache.results.counters()["host"]
    out["cold_p50_ms"] = round(q(cold, 0.5) * 1e3, 3)
    out["warm_p50_ms"] = round(q(warm, 0.5) * 1e3, 3)
    out["warm_speedup_p50"] = round(
        q(cold, 0.5) / q(warm, 0.5), 2) if q(warm, 0.5) else 0.0
    out["warm_hit_rate"] = round(
        ch["hits"] / (ch["hits"] + ch["misses"]), 4) \
        if ch["hits"] + ch["misses"] else 0.0
    log(f"  serve_cache A/B: cold p50 {out['cold_p50_ms']:.2f}ms, warm p50 "
        f"{out['warm_p50_ms']:.2f}ms ({out['warm_speedup_p50']:.1f}x, "
        f"hit rate {out['warm_hit_rate']:.0%})")

    # -- small generated files for the zipf + mutation phases --------------
    def write_small(p, seed, n):
        from tpu_parquet.format import (CompressionCodec,
                                        FieldRepetitionType as FRT, Type)
        from tpu_parquet.schema.core import build_schema, data_column
        from tpu_parquet.writer import FileWriter

        rng = np.random.default_rng(seed)
        schema = build_schema([data_column("a", Type.INT64, FRT.REQUIRED),
                               data_column("b", Type.INT64, FRT.REQUIRED)])
        with open(p, "wb") as fh:
            with FileWriter(fh, schema,
                            codec=CompressionCodec.SNAPPY) as w:
                for _g in range(2):
                    w.write_columns({
                        "a": rng.integers(-(1 << 40), 1 << 40, n // 2),
                        "b": rng.integers(0, 1 << 20, n // 2)})
                    w.flush_row_group()
        return p

    tmp = tempfile.mkdtemp(prefix="tpq_serve_cache_")
    try:
        n_files = 5 if smoke else 8
        n_rows = 2_000 if smoke else 50_000
        zq = 40 if smoke else 200
        files = [write_small(os.path.join(tmp, f"z{i}.parquet"), i, n_rows)
                 for i in range(n_files)]
        # size the cache to ~2.5 files' decoded bytes (rounded UP to the
        # MB knob granularity): the hot set fits, the long tail churns —
        # the shape the tier exists for
        per_file = max(n_rows * 16, 1)
        cache_mb = max(-(-int(2.5 * per_file) // (1 << 20)), 1)
        rng = np.random.default_rng(7)
        ranks = np.minimum(rng.zipf(1.3, zq) - 1, n_files - 1)
        with ScanService(concurrency=2, result_cache_mb=cache_mb) as svc:
            lat = latencies(svc, [ScanRequest(files[r]) for r in ranks])
            tree = svc.obs_registry().as_dict()
        ct = tree["cache"]["host"]
        hist = (tree.get("histograms") or {}).get("serve.request") or {}
        zipf = {
            "files": n_files, "queries": zq, "cache_mb": cache_mb,
            "p50_ms": round(q(lat, 0.5) * 1e3, 3),
            "p95_ms": round(q(lat, 0.95) * 1e3, 3),
            "p99_ms": round(
                _LH.from_dict(hist).quantile(0.99) * 1e3
                if hist else q(lat, 0.99) * 1e3, 3),
            "host_hit_rate": round(
                ct["hits"] / (ct["hits"] + ct["misses"]), 4)
            if ct["hits"] + ct["misses"] else 0.0,
            "evictions": ct["evictions"],
        }
        out["zipf"] = zipf
        log(f"  serve_cache zipf: {zq} queries over {n_files} files, p50 "
            f"{zipf['p50_ms']:.2f}ms p99 {zipf['p99_ms']:.2f}ms, host hit "
            f"rate {zipf['host_hit_rate']:.0%}, "
            f"{zipf['evictions']} evictions")

        # -- phase 3: mutation mid-sweep ----------------------------------
        mut = os.path.join(tmp, "mut.parquet")
        write_small(mut, 100, n_rows)
        with ScanService(concurrency=2, result_cache_mb=cache_mb) as svc:
            first = svc.scan(ScanRequest(mut))[mut]
            svc.scan(ScanRequest(mut))  # provably warm
            inv0 = svc.cache.results.counters()["host"]["invalidations"]
            write_small(mut, 101, n_rows)  # new generation, new bytes
            st = os.stat(mut)
            os.utime(mut, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
            after = svc.scan(ScanRequest(mut))[mut]
            inv1 = svc.cache.results.counters()["host"]["invalidations"]
        stale = bool(np.array_equal(first["a"].values, after["a"].values))
        out["mutation"] = {"invalidations": inv1 - inv0,
                           "stale_served": stale}
        log(f"  serve_cache mutation: {inv1 - inv0} invalidations, "
            f"stale_served={stale}")
        if stale or inv1 - inv0 <= 0:
            raise RuntimeError(
                f"result-cache mutation phase failed: stale={stale}, "
                f"invalidations={inv1 - inv0}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_fused(files, smoke=False):
    """Fused-vs-unfused decode A/B per dominant kernel family (ISSUE 13).

    For the ``plain`` family (plain_int64's fixed-width lane; the fused
    narrow_snappy kernel went in PR 21 — Mosaic cannot lower its
    gathers) one forced-route scan per side (``TPQ_FORCE_ROUTE`` accepts
    the fused name exactly for this A/B), banking the registry ``device`` section's per-route
    ``device_seconds`` / ``dispatches`` / ``device_passes`` plus the
    degrade counter.  The structural bar holds in ANY mode: fused routes
    must show device_passes == dispatches (one pass per (row group,
    column)) where the unfused twin shows >= 3 per dispatch.  The TIMING
    bar (fused device_seconds <= unfused) only binds on compiled (Mosaic)
    runs — ``pallas_mode`` rides the record so the ledger knows which
    kind this was; interpret-mode seconds are not kernel measurements.
    Skip with BENCH_FUSED=0; --smoke runs it tiny.
    """
    from tpu_parquet.device_reader import DeviceFileReader
    from tpu_parquet.pallas_kernels import pallas_mode

    def one(path, route):
        # save/restore, not pop: an operator-forced route must survive this
        # section for the later ones and the ledger env fingerprint
        prev = os.environ.get("TPQ_FORCE_ROUTE")
        os.environ["TPQ_FORCE_ROUTE"] = route
        try:
            t0 = time.perf_counter()
            with DeviceFileReader(path) as r:
                for _ in r.iter_row_groups():
                    pass
                wall = time.perf_counter() - t0
                st = r.stats().as_dict()
                dev = (r.obs_registry().as_dict().get("device")
                       or {}).get("routes") or {}
        finally:
            if prev is None:
                os.environ.pop("TPQ_FORCE_ROUTE", None)
            else:
                os.environ["TPQ_FORCE_ROUTE"] = prev
        c = dev.get(route) or {}
        return {
            "route": route,
            "wall_seconds": round(wall, 4),
            "device_seconds": c.get("device_seconds", 0.0),
            "dispatches": c.get("dispatches", 0),
            "device_passes": c.get("device_passes", 0),
            "streams": (st["ship_routes"].get(route) or {}).get("streams", 0),
            "fused_fallbacks": st.get("fused_fallbacks", 0),
        }

    prev_fuse = os.environ.get("TPQ_FUSE")
    os.environ["TPQ_FUSE"] = "1"
    out = {"pallas_mode": pallas_mode(), "families": {}}
    try:
        for family, fused_route, path in (
                ("plain", "fused_plain", files.get("plain_int64")),):
            if path is None:
                continue
            fused = one(path, fused_route)
            unfused = one(path, family)
            fam = {"fused": fused, "unfused": unfused}
            if fused["dispatches"]:
                fam["fused_passes_per_dispatch"] = round(
                    fused["device_passes"] / fused["dispatches"], 3)
            if unfused["dispatches"]:
                fam["unfused_passes_per_dispatch"] = round(
                    unfused["device_passes"] / unfused["dispatches"], 3)
            if fused["device_seconds"] and unfused["device_seconds"]:
                fam["device_seconds_ratio"] = round(
                    fused["device_seconds"] / unfused["device_seconds"], 4)
            out["families"][family] = fam
            log(f"  fused[{family}]: fused {fused['dispatches']} disp/"
                f"{fused['device_passes']} passes "
                f"{fused['device_seconds']:.6f}s (fallbacks "
                f"{fused['fused_fallbacks']}) vs unfused "
                f"{unfused['dispatches']} disp/{unfused['device_passes']} "
                f"passes {unfused['device_seconds']:.6f}s")
    finally:
        if prev_fuse is None:
            os.environ.pop("TPQ_FUSE", None)
        else:
            os.environ["TPQ_FUSE"] = prev_fuse
    return out


def bench_serve_faults(path, rows, smoke=False):
    """Fault-injected serve sweep (ISSUE 11): the same shared ScanService
    under a seeded stall storm, hedging OFF vs ON.

    Every 4th KiB-aligned range's FIRST attempt stalls (the
    FaultInjectingStore ``stall_first`` shape — retries recover, so
    results stay bit-identical); without hedging each stalled range costs
    ~stall_s of tail, with hedging the duplicate fetch (attempt 2 at the
    same offset: clean) wins the race after ``hedge_ms``.  Banks p50/p95/
    p99 per mode, the hedge win-rate + wasted bytes that justify it, a
    brownout micro-phase's shed counts, and the leaked-thread count (the
    hedge duplicate path rides the exit-3 gate).  Skip with
    BENCH_SERVE_FAULTS=0; ``--smoke`` runs a tiny phase.
    """
    import threading

    from tpu_parquet.errors import OverloadError
    from tpu_parquet.iostore import (FaultInjectingStore, FaultSpec,
                                     IOConfig, LocalStore)
    from tpu_parquet.obs import LatencyHistogram
    from tpu_parquet.reader import FileReader
    from tpu_parquet.serve import (PRIORITY_HIGH, PRIORITY_LOW, ScanRequest,
                                   ScanService)

    clients = 2 if smoke else 4
    q_per_client = 2 if smoke else int(
        os.environ.get("BENCH_SERVE_FAULT_QUERIES", "6"))
    stall_s = 0.08 if smoke else 0.3
    hedge_ms = 10.0
    spec = FaultSpec(stall_first=1, stall_s=stall_s,
                     match=lambda o, s: (o >> 10) % 4 == 0)
    with FileReader(path) as r0:
        cols = [".".join(l.path) for l in r0.schema.selected_leaves()]
        expect = r0.read_all()
    out = {"rows": rows, "stall_s": stall_s, "hedge_ms": hedge_ms,
           "queries": clients * q_per_client}

    for mode, h_ms in (("hedge_off", 0.0), ("hedge_on", hedge_ms)):
        cfg = IOConfig(retries=4, backoff_ms=1.0, hedge_ms=h_ms,
                       hedge_max=8)
        svc = ScanService(
            concurrency=min(clients, 4), queue_depth=max(4 * clients, 8),
            store=lambda f: FaultInjectingStore(LocalStore(f), spec,
                                                config=cfg))
        errors = []

        def run_client(ci):
            try:
                for i in range(q_per_client):
                    svc.scan(ScanRequest(
                        path, columns=[cols[(ci + i) % len(cols)]]),
                        timeout=600)
            except Exception as e:  # noqa: BLE001 — reported, not fatal
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=run_client, args=(ci,))
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        # bit-identity proof: a full-response scan through the faulted
        # (and possibly hedged) path must match the clean one-shot read
        # byte for byte — exactly the lie hedge_mismatches exists to catch
        import numpy as _np

        res = svc.scan(ScanRequest(path), timeout=600)[path]
        for name, want in expect.items():
            got = res[name]
            parts = got if isinstance(got, list) else [got]
            got_rows = sum(p.num_leaf_slots for p in parts)
            assert got_rows == want.num_leaf_slots, \
                f"{mode}: {name} rows {got_rows} != {want.num_leaf_slots}"
            wv = want.values
            if hasattr(wv, "heap"):
                got_heap = _np.concatenate(
                    [_np.asarray(p.values.heap) for p in parts])
                assert _np.array_equal(got_heap, _np.asarray(wv.heap)), \
                    f"{mode}: {name} heap bytes diverged"
            else:
                got_vals = _np.concatenate(
                    [_np.asarray(p.values) for p in parts])
                assert (got_vals.view(_np.uint8).tobytes()
                        == _np.asarray(wv).view(_np.uint8).tobytes()), \
                    f"{mode}: {name} value bytes diverged"
        tree = svc.obs_registry().as_dict()
        svc.close()
        hist = (tree.get("histograms") or {}).get("serve.request") or {}
        h = LatencyHistogram.from_dict(hist) if hist else LatencyHistogram()
        io = tree.get("io") or {}
        issued = int(io.get("hedges_issued", 0))
        entry = {
            "wall_s": round(wall, 4),
            "p50_ms": round(h.quantile(0.5) * 1e3, 3),
            "p95_ms": round(h.quantile(0.95) * 1e3, 3),
            "p99_ms": round(h.quantile(0.99) * 1e3, 3),
            "hedges_issued": issued,
            "hedges_won": int(io.get("hedges_won", 0)),
            "hedge_win_rate": (round(io.get("hedges_won", 0) / issued, 3)
                               if issued else 0.0),
            "hedges_wasted_bytes": int(io.get("hedges_wasted_bytes", 0)),
            "retries": int(io.get("retries", 0)),
        }
        if errors:
            entry["errors"] = errors[:3]
        out[mode] = entry
        log(f"  serve_faults {mode}: p99 {entry['p99_ms']:.1f}ms "
            f"(p50 {entry['p50_ms']:.1f}ms), {issued} hedges, "
            f"win rate {entry['hedge_win_rate']:.0%}")
    if out["hedge_off"]["p99_ms"]:
        out["p99_cut_ratio"] = round(
            out["hedge_on"]["p99_ms"] / out["hedge_off"]["p99_ms"], 3)
        log(f"serve_faults: hedged p99 is "
            f"{out['p99_cut_ratio']:.2f}x of unhedged under the stall "
            f"storm (lower is better)")

    # brownout micro-phase: a burst past capacity sheds LOW with a
    # retry_after_s hint while HIGH still admits
    svc = ScanService(concurrency=1, queue_depth=4, brownout=0.25,
                      store=lambda f: FaultInjectingStore(
                          LocalStore(f),
                          FaultSpec(latency_s=0.03),
                          config=IOConfig(backoff_ms=1.0)))
    tickets, shed_hint = [], None
    for i in range(12):
        try:
            tickets.append(svc.submit(ScanRequest(
                path, columns=[cols[0]], priority=PRIORITY_LOW)))
        except OverloadError as e:
            shed_hint = e.retry_after_s
    high_ok = True
    try:
        tickets.append(svc.submit(ScanRequest(
            path, columns=[cols[0]], priority=PRIORITY_HIGH)))
    except OverloadError:
        high_ok = False
    for t in tickets:
        try:
            t.result(600)
        except Exception:  # noqa: BLE001 — shed accounting is the product
            pass
    sheds = svc.serve_stats()["sheds"]
    svc.close()
    out["brownout"] = {"sheds": sheds, "high_admitted": high_ok,
                      "retry_after_s": shed_hint}
    log(f"  serve_faults brownout: shed {sheds} "
        f"(high admitted: {high_ok}, retry_after {shed_hint})")
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("tpq-hedge")]
    out["leaked_hedge_threads"] = len(leaked)
    assert not leaked, f"hedge racers leaked: {leaked}"
    return out


def bench_serve_tenants(path, rows, smoke=False):
    """Noisy-neighbor QoS A/B (ISSUE 17): a victim tenant's request
    latency isolated, then under a noisy tenant's flood with the global
    FIFO queue, then under weighted deficit-round-robin fair-share.

    One worker (concurrency=1) + a fixed per-range injected latency +
    result cache OFF make each request's cost deterministic, so the
    queueing discipline is the ONLY variable: under FIFO the victim's
    burst waits behind the whole flood; under fair-share (victim weight 3
    vs noisy 1) it overtakes after at most a quantum.  Banks victim
    p50/p95/p99 per phase and the fifo/fair degradation ratios, plus the
    per-tenant serve accounting that proves both tenants ran.  Streaming
    sessions ride the same tpq-serve workers, so this phase's clean-close
    assertion (and the exit-3 gate's ``tpq-serve`` prefix) covers them.
    Skip with BENCH_SERVE_TENANTS=0; ``--smoke`` runs a tiny phase.
    """
    import threading

    from tpu_parquet.iostore import (FaultInjectingStore, FaultSpec,
                                     IOConfig, LocalStore)
    from tpu_parquet.reader import FileReader
    from tpu_parquet.serve import ScanRequest, ScanService

    lat = 0.004 if smoke else 0.02
    noisy_n = 6 if smoke else 20
    victim_n = 3 if smoke else 6
    rounds = 1 if smoke else 2
    with FileReader(path) as r0:
        col = ".".join(r0.schema.selected_leaves()[0].path)

    def mk_svc(fair):
        svc = ScanService(
            concurrency=1, queue_depth=4 * (noisy_n + victim_n),
            fair=fair, result_cache_mb=0,
            store=lambda f: FaultInjectingStore(
                LocalStore(f), FaultSpec(latency_s=lat),
                config=IOConfig(backoff_ms=1.0)))
        svc.register_tenant("victim", weight=3)
        svc.register_tenant("noisy", weight=1)
        return svc

    def quantile(walls, q):
        s = sorted(walls)
        return s[min(int(q * len(s)), len(s) - 1)]

    def victim_burst(svc):
        walls = []
        for _ in range(victim_n):
            t0 = time.perf_counter()
            svc.scan(ScanRequest(path, columns=[col], tenant="victim"),
                     timeout=600)
            walls.append(time.perf_counter() - t0)
        return walls

    out = {"rows": rows, "latency_s": lat, "noisy_requests": noisy_n,
           "victim_requests": victim_n * rounds, "victim_weight": 3}
    for phase, fair in (("isolated", True), ("fifo", False), ("fair", True)):
        svc = mk_svc(fair)
        walls, noisy_tickets = [], []
        for _ in range(rounds):
            if phase != "isolated":
                noisy_tickets += [
                    svc.submit(ScanRequest(path, columns=[col],
                                           tenant="noisy"))
                    for _ in range(noisy_n)]
            walls += victim_burst(svc)
        for t in noisy_tickets:
            t.result(600)
        stats = svc.serve_stats()
        svc.close()
        out[phase] = {
            "p50_ms": round(quantile(walls, 0.5) * 1e3, 3),
            "p95_ms": round(quantile(walls, 0.95) * 1e3, 3),
            "p99_ms": round(quantile(walls, 0.99) * 1e3, 3),
            "victim_submitted": stats["tenants"]["victim"]["submitted"],
            "noisy_submitted": stats["tenants"].get(
                "noisy", {}).get("submitted", 0),
        }
        log(f"  serve_tenants {phase}: victim p99 "
            f"{out[phase]['p99_ms']:.1f}ms (p50 {out[phase]['p50_ms']:.1f}"
            f"ms)")
    base = out["isolated"]["p99_ms"] or 1e-9
    out["fifo_ratio"] = round(out["fifo"]["p99_ms"] / base, 3)
    out["fair_ratio"] = round(out["fair"]["p99_ms"] / base, 3)
    log(f"serve_tenants: victim p99 degradation under flood — FIFO "
        f"{out['fifo_ratio']:.1f}x vs fair-share {out['fair_ratio']:.1f}x "
        f"of isolated (lower is better)")
    # structural bar: with one worker and a deterministic per-request
    # cost, fair-share MUST beat FIFO for the victim — equality means the
    # scheduler isn't actually discriminating by tenant
    assert out["fair"]["p99_ms"] < out["fifo"]["p99_ms"], out

    # streaming slot-yield A/B (ISSUE 20): the same single worker, but the
    # noisy tenant holds a LONG streaming session instead of a flood.
    # Slot-pinned (stream_yield=False), the session owns the only worker
    # until the whole file has streamed and every victim one-shot queues
    # behind it; with batch-granular yielding the session re-queues itself
    # whenever another tenant is waiting (DRR at batch granularity), so
    # the victim overtakes after at most one batch.
    batch_rows = max(rows // 32, 1)
    for phase, yield_on in (("stream_pinned", False), ("stream_yield", True)):
        svc = ScanService(
            concurrency=1, queue_depth=4 * (noisy_n + victim_n),
            fair=True, result_cache_mb=0, stream_yield=yield_on,
            store=lambda f: FaultInjectingStore(
                LocalStore(f), FaultSpec(latency_s=lat),
                config=IOConfig(backoff_ms=1.0)))
        svc.register_tenant("victim", weight=3)
        svc.register_tenant("noisy", weight=1)
        session = svc.submit(ScanRequest(
            path, columns=[col], tenant="noisy", stream=True,
            batch_rows=batch_rows)).result(600)
        batches = []
        consumer = threading.Thread(
            target=lambda: batches.extend(1 for _ in session),
            name="bench-stream-drain")
        consumer.start()
        walls = victim_burst(svc)
        consumer.join(600)
        stats = svc.serve_stats()
        svc.close()
        out[phase] = {
            "p50_ms": round(quantile(walls, 0.5) * 1e3, 3),
            "p99_ms": round(quantile(walls, 0.99) * 1e3, 3),
            "stream_batches": len(batches),
            "slot_yields": stats.get("stream_yields", 0),
        }
        log(f"  serve_tenants {phase}: victim p99 "
            f"{out[phase]['p99_ms']:.1f}ms over {len(batches)} streamed "
            f"batch(es), {out[phase]['slot_yields']} slot yield(s)")
    out["stream_yield_ratio"] = round(
        out["stream_yield"]["p99_ms"]
        / (out["stream_pinned"]["p99_ms"] or 1e-9), 3)
    # structural bar: yielding MUST improve the victim's p99 against the
    # slot-pinned stream, and the yield counter must prove the mechanism
    # actually fired (not a lucky scheduling accident)
    assert out["stream_yield"]["p99_ms"] < out["stream_pinned"]["p99_ms"], out
    assert out["stream_yield"]["slot_yields"] > 0, out["stream_yield"]
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("tpq-serve")]
    out["leaked_serve_threads"] = len(leaked)
    assert not leaked, f"serve workers leaked: {leaked}"
    return out


def bench_io_scale(path, rows, smoke=False):
    """IO-concurrency scaling A/B (ISSUE 18): the async fetch engine vs a
    blocking-read thread pool, sweeping the in-flight target under a fixed
    per-range injected latency.

    Each leg fetches k ranges through a 50ms-latency store.  The threaded
    leg uses a pool capped at 32 workers — the realistic decode-worker
    ceiling the old path had (in the pipeline, ``prefetch=`` bounds it);
    the engine leg multiplexes all k as futures on ONE loop thread with
    ``max_inflight=k``.  At k=8 the legs tie; by k=256 the pool is queue-
    bound at its thread cap while the engine overlaps everything — the
    banked ratio is the headline.  Results must be byte-identical between
    legs and no engine/pool thread may survive the phase.  Skip with
    BENCH_IOSCALE=0; ``--smoke`` runs a tiny sweep.
    """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from tpu_parquet.iostore import (FaultInjectingStore, FaultSpec,
                                     IOConfig, LocalStore)
    from tpu_parquet.iostore_async import FetchEngine

    lat = 0.01 if smoke else 0.05
    sweep = (4, 16) if smoke else (8, 64, 256)
    pool_cap = 32
    rsize = 4096
    fsize = os.path.getsize(path)

    def ranges_for(k):
        step = max((fsize - rsize) // max(k, 1), 1)
        return [((i * step) % max(fsize - rsize, 1), rsize)
                for i in range(k)]

    def mk_store(f):
        return FaultInjectingStore(
            LocalStore(f), FaultSpec(latency_s=lat),
            config=IOConfig(backoff_ms=1.0))

    def quantile(walls, q):
        s = sorted(walls)
        return s[min(int(q * len(s)), len(s) - 1)]

    out = {"rows": rows, "latency_s": lat, "pool_threads": pool_cap,
           "range_bytes": rsize}
    for k in sweep:
        want = ranges_for(k)
        fobj = open(path, "rb")
        st_t = mk_store(fobj)
        walls_t = []

        def read_one(r, _st=st_t, _w=walls_t):
            t0 = time.perf_counter()
            buf = _st.read_range(*r)
            _w.append(time.perf_counter() - t0)
            return bytes(buf)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=min(k, pool_cap)) as ex:
            got_t = list(ex.map(read_one, want))
        wall_t = time.perf_counter() - t0

        st_e = mk_store(fobj)
        eng = FetchEngine(max_inflight=k)
        walls_e, done_at = [], {}
        try:
            t0 = time.perf_counter()
            futs = [eng.submit(st_e, o, s) for o, s in want]
            for f in futs:
                f.add_done_callback(
                    lambda _f, _t0=t0: done_at.setdefault(
                        id(_f), time.perf_counter() - _t0))
            got_e = [bytes(f.result(timeout=600)) for f in futs]
            wall_e = time.perf_counter() - t0
            walls_e = [done_at[id(f)] for f in futs]
            peak = eng.stats.inflight_peak
        finally:
            eng.close()
            fobj.close()
        assert got_t == got_e, \
            f"engine leg diverged from threaded leg at k={k}"
        ratio = wall_t / wall_e if wall_e else 0.0
        out[f"k{k}"] = {
            "threaded_s": round(wall_t, 4), "engine_s": round(wall_e, 4),
            "ratio": round(ratio, 3),
            "threaded_p99_ms": round(quantile(walls_t, 0.99) * 1e3, 2),
            "engine_p99_ms": round(quantile(walls_e, 0.99) * 1e3, 2),
            "engine_inflight_peak": peak,
        }
        log(f"  io_scale k={k}: threaded {wall_t:.3f}s vs engine "
            f"{wall_e:.3f}s ({ratio:.1f}x), engine peak {peak} in flight")
        if not smoke and k > pool_cap:
            # structural bar: past the pool's thread cap the engine MUST
            # win — parity there means it isn't actually multiplexing
            assert ratio >= (4.0 if k >= 8 * pool_cap else 1.2), out
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("tpq-fetch")]
    out["leaked_engine_threads"] = len(leaked)
    assert not leaked, f"fetch-engine threads leaked: {leaked}"
    return out


def bench_obs_overhead(path, rows, smoke=False):
    """Tracing-cost A/B (ISSUE 19): the serve workload with request
    tracing disabled (``TPQ_TRACE_TAIL=0``), tail-sampled at the default
    rate, and retain-all (``TPQ_TRACE_TAIL=1``).

    Each leg runs the same warmed multi-client query mix through a fresh
    ``ScanService`` and banks p50/p99 request latency from the service's
    own histogram; the headline is ``tail_p50_overhead`` (tail-sampled
    p50 / tracing-off p50 — the cost every production request pays).  The
    acceptance figure is <=1.03; the asserted bar is looser because
    sub-millisecond p50s are scheduler-noise-dominated at bench scale.
    The retain-all leg additionally proves the export ring honours its
    byte bound and that the off leg creates no traces at all.  The
    ``fleet`` leg (ISSUE 20) re-runs the tail-sampled mix with the
    cross-process spool armed (``TPQ_OBS_SPOOL``, fast cadence) — its
    headline ``fleet_p50_overhead`` is the snapshot publisher's cost on
    top of tail sampling (acceptance figure <=1.03), and the leg proves
    the published generations aggregate cleanly.  Skip with BENCH_OBS=0;
    ``--smoke`` runs a tiny mix.
    """
    import shutil
    import tempfile
    import threading

    from tpu_parquet.reader import FileReader
    from tpu_parquet.serve import ScanRequest, ScanService

    q_per_client = (4 if smoke
                    else int(os.environ.get("BENCH_OBS_QUERIES", "24")))
    clients = 2 if smoke else 4
    with FileReader(path) as r0:
        cols = [".".join(l.path) for l in r0.schema.selected_leaves()]
    projections = [None, cols[: max(len(cols) // 2, 1)], cols[:1]]
    out = {"rows": rows, "queries": clients * q_per_client}
    saved = os.environ.get("TPQ_TRACE_TAIL")
    saved_spool = {k: os.environ.get(k)
                   for k in ("TPQ_OBS_SPOOL", "TPQ_OBS_SPOOL_S")}
    spool_dir = tempfile.mkdtemp(prefix="tpq-bench-spool-")
    try:
        for leg, val in (("off", "0"), ("tail", None), ("retain_all", "1"),
                         ("fleet", None)):
            if val is None:
                os.environ.pop("TPQ_TRACE_TAIL", None)
            else:
                os.environ["TPQ_TRACE_TAIL"] = val
            if leg == "fleet":
                os.environ["TPQ_OBS_SPOOL"] = spool_dir
                os.environ["TPQ_OBS_SPOOL_S"] = "0.2"
            else:
                os.environ.pop("TPQ_OBS_SPOOL", None)
                os.environ.pop("TPQ_OBS_SPOOL_S", None)
            svc = ScanService(concurrency=min(clients, 8),
                              queue_depth=max(2 * clients, 4))
            errors = []

            def run_client(ci, _svc=svc, _errs=errors):
                try:
                    for i in range(q_per_client):
                        _svc.scan(ScanRequest(
                            path,
                            columns=projections[(ci + i)
                                                % len(projections)]))
                except Exception as e:  # noqa: BLE001 — reported
                    _errs.append(repr(e))

            # warm the plan/footer/dict cache first so every leg measures
            # the same steady state — the first-open footer parse would
            # swamp a percent-level tracing delta
            svc.scan(ScanRequest(path))
            t0 = time.perf_counter()
            threads = [threading.Thread(target=run_client, args=(ci,))
                       for ci in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            tree = svc.obs_registry().as_dict()
            trace = svc.serve_stats()["trace"]
            svc.close()
            hist = (tree.get("histograms") or {}).get("serve.request") or {}
            from tpu_parquet.obs import LatencyHistogram as _LH
            p99_s = _LH.from_dict(hist).quantile(0.99) if hist else 0.0
            entry = {
                "wall_s": round(wall, 4),
                "p50_ms": round(
                    float(hist.get("p50_seconds", 0.0)) * 1e3, 3),
                "p99_ms": round(p99_s * 1e3, 3),
                "traces_offered": trace["offered"],
                "traces_retained": trace["retained"],
                "ring_bytes": trace["retained_bytes"],
            }
            if errors:
                entry["errors"] = errors[:3]
            assert trace["retained_bytes"] <= trace["ring_capacity_bytes"], \
                f"export ring over its byte bound in {leg} leg: {trace}"
            if leg == "fleet":
                # the spool must have published generations that aggregate
                # cleanly — otherwise the leg measured an inert spool
                from tpu_parquet.obs_fleet import FleetAggregator
                snap = FleetAggregator(spool_dir=spool_dir).scan()
                entry["spool_files"] = snap["files_scanned"]
                entry["spool_rejected"] = snap["rejected"]
                entry["spool_processes"] = len(snap["processes"])
                assert snap["files_scanned"] > 0 and snap["rejected"] == 0 \
                    and any(p.get("role") == "serve"
                            for p in snap["processes"].values()), snap
            out[leg] = entry
            log(f"  obs_overhead {leg}: {wall:.3f}s wall, "
                f"p50 {entry['p50_ms']:.3f}ms p99 {entry['p99_ms']:.3f}ms, "
                f"{trace['retained']}/{trace['offered']} traces retained")
    finally:
        if saved is None:
            os.environ.pop("TPQ_TRACE_TAIL", None)
        else:
            os.environ["TPQ_TRACE_TAIL"] = saved
        for k, v in saved_spool.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(spool_dir, ignore_errors=True)
    off = out["off"]
    if off["p50_ms"]:
        for leg in ("tail", "retain_all", "fleet"):
            out[f"{leg}_p50_overhead"] = round(
                out[leg]["p50_ms"] / off["p50_ms"], 4)
            out[f"{leg}_p99_overhead"] = (round(
                out[leg]["p99_ms"] / off["p99_ms"], 4)
                if off["p99_ms"] else 0.0)
        log(f"obs_overhead: tail-sampled p50 "
            f"{out['tail_p50_overhead']:.3f}x of tracing-off (acceptance "
            f"figure <=1.03), retain-all "
            f"{out['retain_all_p50_overhead']:.3f}x, spool-armed "
            f"{out['fleet_p50_overhead']:.3f}x (acceptance <=1.03)")
        if not smoke:
            # generous structural bar — percent-level deltas drown in
            # scheduler noise here; the banked ratio is the honest figure,
            # this only catches a gross regression
            assert out["tail_p50_overhead"] <= 1.5, out
            assert out["fleet_p50_overhead"] <= 1.5, out
    # off must be genuinely off (zero traces created), retain-all must
    # actually retain — otherwise the A/B measured nothing
    assert off["traces_offered"] == 0, off
    assert out["retain_all"]["traces_retained"] > 0, out["retain_all"]
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(("tpq-serve", "tpq-metricsdump",
                                    "tpq-spool"))]
    assert not leaked, f"serve/dumper/spool threads leaked: {leaked}"
    return out


def _enable_compile_cache():
    """Persistent XLA compilation cache (one implementation: the library's —
    device_reader._enable_compile_cache uses JAX_COMPILATION_CACHE_DIR when
    set, else <checkout>/.jax_cache/ on a TPU, else nothing)."""
    import jax
    from tpu_parquet.device_reader import _enable_compile_cache as lib_enable

    lib_enable()
    log(f"compilation cache: {jax.config.jax_compilation_cache_dir}")


def _pallas_microbench(width=13, n=8_000_000):
    """Best-of-5 fixed-width unpack: Mosaic plane kernel vs XLA gather path."""
    import jax
    import numpy as np

    from tpu_parquet import jax_kernels as K
    from tpu_parquet.jax_decode import pad_buffer
    from tpu_parquet.kernels import bitpack
    from tpu_parquet.pallas_kernels import (
        bp_groups_pad, pallas_available, unpack_bp_groups,
    )

    rng = np.random.default_rng(1)
    vals = rng.integers(0, 1 << width, n, dtype=np.uint64)
    packed = np.frombuffer(bitpack.pack(vals, width), np.uint8)
    gpad = bp_groups_pad(-(-n // 8))
    staged = jax.device_put(np.pad(packed, (0, gpad * width - len(packed))))
    buf_dev = pad_buffer(packed)
    interp = not pallas_available()

    def pallas():
        return unpack_bp_groups(staged, 0, width, gpad, interpret=interp)

    with jax.enable_x64():
        jax.block_until_ready(K.unpack_bits(buf_dev, width, n))
    jax.block_until_ready(pallas())
    t_xla = t_pl = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        with jax.enable_x64():
            jax.block_until_ready(K.unpack_bits(buf_dev, width, n))
        t_xla = min(t_xla, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(pallas())
        t_pl = min(t_pl, time.perf_counter() - t0)
    return {
        "width": width,
        "xla_mvals_per_sec": round(n / t_xla / 1e6, 1),
        "pallas_mvals_per_sec": round(n / t_pl / 1e6, 1),
        "pallas_speedup": round(t_xla / t_pl, 2),
    }


# per-config scalar keys worth repeating on the compact stdout line; rep
# lists, window arrays, and sampling metadata live only in the artifact file
_SUMMARY_KEYS = (
    "rows", "device_rows_per_sec", "device_mb_per_sec", "device_vs_host",
    "device_vs_pyarrow", "device_vs_host_pipeline", "host_rows_per_sec",
    "pyarrow_rows_per_sec", "pipeline_speedup", "prefetch0_rows_per_sec",
    "prefetch4_rows_per_sec", "overlap_efficiency", "loader_speedup",
    "loader_vs_scan", "scan_files_rows_per_sec", "device_vs_host_prefetch4",
    "pallas_speedup", "link_bytes_shipped", "link_bytes_logical",
    "link_bytes_ratio",
)
_SUMMARY_LIMIT = 1990  # < the driver's 2000-char tail window, with margin


def parse_args(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="tpu-parquet benchmark (see the module docstring for "
                    "the env knobs)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny single-config run (plain_int64, ~20k rows, "
                        "optional sections off) exercising the full "
                        "artifact/ledger/gate plumbing end to end")
    p.add_argument("--check-against", metavar="BASELINE", default=None,
                   help="regression gate: compare this run against a prior "
                        "bench artifact / ledger / ledger.jsonl#N; exit 2 "
                        "when a metric regresses beyond its noise bound")
    p.add_argument("--no-ledger", action="store_true",
                   help="skip the automatic ledger.jsonl append")
    return p.parse_args(argv)


def _ledger_and_check(record, args, artifact_path):
    """Gate the run against a baseline, then append it to the ledger.

    Mutates ``record`` (adds ``ledger``/``check`` keys, surfaced on the
    compact line by emit_results); returns the exit code the caller should
    use AFTER emitting — the driver's JSON line always comes first.

    The gate runs BEFORE the append, and a failed gate (regression,
    unloadable baseline, nothing comparable) skips the append entirely:
    with ``--check-against ledger.jsonl`` the baseline is the previous
    recorded run, so recording a regressed run would make it the very
    baseline the NEXT run is compared against — one red build and the
    regression is ratcheted in as the new normal.  (This ordering also
    keeps a self-comparison impossible: the record this run would write
    can never be its own ratio-1.0 baseline.)  The run's numbers are
    still banked in the BENCH artifact and the compact line.
    """
    rc = _check_gate(record, args)
    if not args.no_ledger:
        from tpu_parquet import ledger as _ledger

        if rc == 0:
            # smoke runs default to their OWN ledger file: a tiny-config
            # record appended to the full-run ledger.jsonl would become the
            # last record — i.e. the `--check-against ledger.jsonl` baseline
            # — and every full run after it would gate rows-incomparable
            # (exit 2, never recorded), wedging CI until someone hand-edits
            # the ledger.  An explicit TPQ_LEDGER still wins.
            default_name = ("ledger.smoke.jsonl" if args.smoke
                            else "ledger.jsonl")
            lpath = os.environ.get("TPQ_LEDGER") or os.path.join(
                os.path.dirname(os.path.abspath(artifact_path)),
                default_name)
            try:
                seq = _ledger.append(lpath, _ledger.make_record(record))
                record["ledger"] = {"path": lpath, "seq": seq}
                log(f"ledger: appended run #{seq} to {lpath}")
            except OSError as e:
                log(f"ledger append FAILED ({lpath}): {e!r}")
        else:
            log("ledger: gate failed — run NOT recorded (a regressed run "
                "must never become the next run's baseline)")
    return rc


def _check_gate(record, args) -> int:
    """The ``--check-against`` evaluation alone: sets ``record['check']``,
    returns the gate exit code (0 pass, 2 fail)."""
    from tpu_parquet import ledger as _ledger

    if not args.check_against:
        return 0
    baseline = baseline_error = None
    try:
        baseline = _ledger.load_side(args.check_against)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        baseline_error = e
    if baseline_error is not None:
        # an unloadable baseline must FAIL the gate: a typo'd path silently
        # passing CI is the worst failure mode a gate can have
        log(f"check-against: cannot load baseline "
            f"{args.check_against}: {baseline_error!r}")
        record["check"] = {"baseline": args.check_against,
                           "error": str(baseline_error), "regressions": []}
        return 2
    floor_env = os.environ.get("BENCH_CHECK_FLOOR", "")
    try:
        floor = float(floor_env) if floor_env else _ledger.DEFAULT_CHECK_FLOOR
    except ValueError:
        # a malformed knob must not take down the emit contract (the driver
        # line always comes first) — fall back and say so
        log(f"check-against: unparseable BENCH_CHECK_FLOOR={floor_env!r}, "
            f"using default {_ledger.DEFAULT_CHECK_FLOOR}")
        floor = _ledger.DEFAULT_CHECK_FLOOR
    d = _ledger.diff(baseline, record, floor=floor)
    record["check"] = {
        "baseline": args.check_against,
        "floor": floor,
        "compared": d["compared"],
        "regressions": d["regressions"],
        "improvements": d["improvements"],
        "incomparable": d["incomparable"],
    }
    log(_ledger.format_diff(d, args.check_against, "this run").rstrip())
    if d["compared"] == 0:
        # a gate that compared nothing checked nothing: a loadable but
        # wrong-shape baseline (a trace artifact, a full-scale record vs a
        # smoke run) must fail just as loudly as a typo'd path
        log("check-against: 0 comparable metrics — the baseline does not "
            "cover this run's configs/rows; failing the gate")
        record["check"]["error"] = "no comparable metrics"
        return 2
    if d["regressions"]:
        log(f"check-against: {len(d['regressions'])} regression(s) beyond "
            f"noise bounds — exiting nonzero")
        return 2
    return 0


def _artifact_path():
    """ONE resolution of the artifact location — emit_results writes it and
    the ledger lands next to it, so the two must never diverge."""
    return os.environ.get("BENCH_JSON") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_LOCAL_latest.json")


def emit_results(record, out_path=None):
    """VERDICT r5 blocker fix: the full results go to a BENCH artifact file
    as INDENTED multi-line JSON, and stdout's LAST line is a compact
    single-line summary guaranteed under the driver's 2000-char tail window
    (the r04/r05 one-line JSON overflowed it: ``parsed: null`` two rounds
    running).  ``BENCH_JSON`` overrides the artifact path."""
    out_path = out_path or _artifact_path()
    artifact_name = os.path.basename(out_path)
    try:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        log(f"full results: {out_path}")
    except OSError as e:
        log(f"artifact write FAILED ({out_path}): {e!r}")
        # never point the summary at a stale file from an earlier round
        artifact_name = None
    compact = {k: record[k] for k in ("metric", "value", "unit",
                                      "vs_baseline")}
    compact["artifact"] = artifact_name
    # ledger/check summaries stay a few chars each on the compact line;
    # the full entries (attributions included) live in the artifact
    led = record.get("ledger")
    if led:
        compact["ledger"] = f"{os.path.basename(led['path'])}#{led['seq']}"
    chk = record.get("check")
    if chk is not None:
        if chk.get("error"):
            # distinguish the two gate-failure shapes for whoever triages
            # from the compact line alone: a baseline that never loaded vs
            # one that loaded but covered none of this run's configs/rows
            # (only the latter carries the diff's "compared" count)
            compact["check"] = ("incomparable_baseline" if "compared" in chk
                                else "baseline_unloadable")
        elif chk.get("regressions"):
            compact["check"] = f"{len(chk['regressions'])} regressions"
        else:
            compact["check"] = f"ok ({chk.get('compared', 0)} compared)"
    cfgs = {}
    for name, r in record.get("configs", {}).items():
        if not isinstance(r, dict):
            continue
        c = {k: r[k] for k in _SUMMARY_KEYS
             if isinstance(r.get(k), (int, float))}
        if c:
            cfgs[name] = c
    compact["configs"] = cfgs
    line = json.dumps(compact, separators=(",", ":"))
    while len(line) > _SUMMARY_LIMIT and cfgs:
        # shed the bulkiest config until the line fits; the artifact file
        # keeps everything
        bulkiest = max(cfgs, key=lambda n: len(json.dumps(cfgs[n])))
        del cfgs[bulkiest]
        line = json.dumps(compact, separators=(",", ":"))
    if len(line) > _SUMMARY_LIMIT:
        compact.pop("configs", None)
        line = json.dumps(compact, separators=(",", ":"))
    print(line)


_TRACE_BASE: "str | None" = None  # main() moves TPQ_TRACE here (see below)


def main(argv=None):
    global _TRACE_BASE, SCALE, REPS, BASELINE_REPS, RESAMPLE, WHICH
    import jax

    args = parse_args(argv)
    if args.smoke:
        # one tiny config, optional sections off, unless the env explicitly
        # says otherwise — the end-to-end plumbing run, not a measurement
        SCALE = float(os.environ.get("BENCH_SCALE", "0.002"))
        REPS = int(os.environ.get("BENCH_DEVICE_REPS", "2"))
        BASELINE_REPS = int(os.environ.get("BENCH_BASELINE_REPS", "1"))
        RESAMPLE = int(os.environ.get("BENCH_RESAMPLE", "0"))
        WHICH = os.environ.get("BENCH_CONFIGS", "1").split(",")
        for knob in ("BENCH_PIPELINE", "BENCH_LOADER", "BENCH_WRITES",
                     "BENCH_PALLAS", "BENCH_IOFAULTS", "BENCH_DATAFAULTS"):
            os.environ.setdefault(knob, "0")
        # the smoke/tier-1 gate path runs with the hang watchdog ARMED (a
        # generous deadline: it must never fire on a slow box, only on a
        # true wedge) so recorder+watchdog wiring is exercised on every
        # gate run; the zero-daemon-thread assert at the end of main()
        # proves every reader stopped it
        os.environ.setdefault("TPQ_HANG_S", "300")

    # Claim TPQ_TRACE for the per-config artifacts and UNSET it: left in the
    # env it would enable the process-global tracer inside every TIMED rep —
    # live span recording perturbing the samples the benchmark reports, and
    # every rep's events buffering until exit.  Only bench_device's
    # instrumented pass (its own per-config Tracer) records.
    _TRACE_BASE = os.environ.pop("TPQ_TRACE", "")

    _enable_compile_cache()
    log(f"jax devices: {jax.devices()}")
    results = {}
    failed = []  # configs that failed to generate or to run: exit 4
    headline = None
    dev_times = {}   # name -> (dev_t, path, rows, key)
    meta = {"device_reps": REPS, "baseline_reps": BASELINE_REPS}
    try:
        meta["link_mb_per_sec_start"] = probe_link()
        # feed the MEASURED link speed to the ship planner (ship.py reads
        # TPQ_LINK_MBPS) so route choices below reflect this run's weather,
        # not the default planning point; an explicit env wins
        if "TPQ_LINK_MBPS" not in os.environ:
            os.environ["TPQ_LINK_MBPS"] = str(meta["link_mb_per_sec_start"])
            meta["planner_link_mbps"] = meta["link_mb_per_sec_start"]
    except Exception as e:  # noqa: BLE001 — diagnostics only
        log(f"link probe FAILED: {e!r}")

    def over_budget():
        # never trips before the first result exists: the driver must always
        # get at least one measured config in its JSON line
        return bool(results) and time.perf_counter() - _T_START > TIME_BUDGET

    # ------------------------------------------------------------------
    # Phase A: every config's DEVICE measurement, banked first in clean
    # air.  Device scans barely affect each other, but a config's baseline
    # phases (especially the host+upload burst: hundreds of MB of
    # device_put) depress subsequent transfer throughput for tens of
    # seconds on the early remote backend — measured 4x on config 2 when the
    # phases were interleaved.  Baselines therefore run in phase B, after
    # every device number is already recorded.
    # ------------------------------------------------------------------
    for key in WHICH:
        key = key.strip()
        if key not in CONFIGS:
            continue
        if over_budget():
            log(f"time budget {TIME_BUDGET}s reached; skipping config {key}")
            continue
        name, gen, base_rows = CONFIGS[key]
        rows = int(base_rows * SCALE)
        path = f"/tmp/tpq_bench_{name}_{rows}.parquet"
        # the nested config is multi-file: ALL parts must exist or the scan
        # quietly under-reads while `rows` stays the full denominator
        required = [path] + ([path + ".part2"] if name == "nested" else [])
        if not all(os.path.exists(p) for p in required):
            t0 = time.perf_counter()
            try:
                gen(path, rows)
            except Exception as e:  # noqa: BLE001 — the run exits 4 below
                log(f"config {key} {name} generation FAILED: {e!r}; skipping")
                failed.append(name)
                if os.path.exists(path):
                    os.unlink(path)
                continue
            gen_mb = sum(os.path.getsize(p) for p in required) / 1e6
            log(f"generated {path} ({len(required)} file(s)): {gen_mb:.1f} MB "
                f"in {time.perf_counter()-t0:.1f}s")
        mb = _uncompressed_mb(path)
        log(f"config {key} {name}: {rows} rows, {mb:.0f} MB uncompressed")
        try:
            samples, ship = bench_device(path, rows, name=name)
        except Exception as e:  # noqa: BLE001 — one bad config must not
            # cost the driver its JSON line; the run still exits 4 below
            log(f"config {key} {name} FAILED: {e!r}; continuing")
            failed.append(name)
            continue
        dev_t = _median(samples)
        results[name] = {
            "rows": rows,
            "device_rows_per_sec": round(rows / dev_t, 1),
            "device_mb_per_sec": round(mb / dev_t, 1),
            "device_windows_s": [[round(t, 3) for t in samples]],
            **ship,
        }
        dev_times[name] = ([samples], path, rows, key, mb)
        log(f"config {key} {name}: device "
            f"{results[name]['device_rows_per_sec']/1e6:.1f} M rows/s "
            f"({results[name]['device_mb_per_sec']:.0f} MB/s)")
        if name == "lineitem16":
            headline = results[name]

    # ------------------------------------------------------------------
    # Phase A': extra sampling windows over every config.  Transient
    # congestion on the early remote backend's link lasted minutes (its
    # probes recorded 93 MB/s and 1.5 GB/s within one run); re-sampling each
    # config's device reps later in the run gives the best-window-median estimator more
    # weather windows.  Same metric, same estimator — sampled at several
    # points in time.  Windows stop at 60% of the budget: the phase-B
    # baselines (the vs_baseline denominator the driver records) must
    # always fit.
    # ------------------------------------------------------------------
    resample_reps = max(REPS - 2, 2)
    meta["resample_windows"] = 0
    meta["resample_reps"] = resample_reps

    def windows_over_budget():
        return (bool(results)
                and time.perf_counter() - _T_START > 0.6 * TIME_BUDGET)

    for rs in range(RESAMPLE):
        if not dev_times or windows_over_budget():
            break
        try:  # probe failure must not forfeit the sampling window itself
            meta[f"link_mb_per_sec_w{rs + 1}"] = probe_link()
        except Exception as e:  # noqa: BLE001 — diagnostics only
            log(f"window link probe FAILED: {e!r}")
        # headline first (banked before the budget can run out), then the
        # rest — the early remote link swung 150→1500 MB/s
        # within one run, so every config deserves a second window
        order = sorted(dev_times, key=lambda n: n != "lineitem16")
        window_complete = True
        for name in order:
            if windows_over_budget():
                window_complete = False
                break
            windows, path, rows, key, mb = dev_times[name]
            try:
                extra = device_reps(path, rows, resample_reps,
                                    tag=f".{name}.w{rs + 1}")
            except Exception as e:  # noqa: BLE001
                log(f"{name} resample FAILED: {e!r}")
                continue
            meta[f"w{rs + 1}_sampled"] = meta.get(f"w{rs + 1}_sampled", 0) + 1
            windows.append(extra)
            # best WINDOW median (see the sampling-protocol docstring):
            # median within a window, cleanest weather window across
            t = _best_window(windows)
            r = results[name]
            r["device_rows_per_sec"] = round(rows / t, 1)
            r["device_mb_per_sec"] = round(mb / t, 1)
            r["device_windows_s"] = [[round(x, 3) for x in w]
                                     for w in windows]
            log(f"{name} best window median after window {rs + 1}: "
                f"{r['device_rows_per_sec'] / 1e6:.1f} M rows/s")
        if window_complete:
            meta["resample_windows"] = rs + 1

    # ------------------------------------------------------------------
    # Phase B: baselines (host decode, pyarrow, host decode + upload).
    # host/pyarrow are CPU-bound and indifferent to link state; the
    # upload baselines run last so their transfer bursts cannot poison any
    # measurement that matters.
    # ------------------------------------------------------------------
    for name, (windows, path, rows, key, mb) in dev_times.items():
        r = results[name]
        dev_t = _best_window(windows)
        if over_budget():
            log(f"time budget reached; skipping baselines for {name}")
            continue
        try:
            hs = bench_host(path, rows)
            host_t = _median(hs)
            r["host_rows_per_sec"] = round(rows / host_t, 1)
            r["host_reps_s"] = [round(x, 3) for x in hs]
            r["device_vs_host"] = round(host_t / dev_t, 3)
        except Exception as e:  # noqa: BLE001 — keep the paid-for device
            # numbers even when the host baseline dies
            log(f"config {key} host baseline FAILED: {e!r}")
        try:
            ps_ = bench_pyarrow(path, rows)
            pa_t = _median(ps_)
            r["pyarrow_rows_per_sec"] = round(rows / pa_t, 1)
            r["pyarrow_reps_s"] = [round(x, 3) for x in ps_]
            r["device_vs_pyarrow"] = round(pa_t / dev_t, 3)
        except Exception as e:  # noqa: BLE001 — independent denominator only
            log(f"config {key} pyarrow baseline FAILED: {e!r}")
    for name, (windows, path, rows, key, mb) in dev_times.items():
        r = results[name]
        dev_t = _best_window(windows)
        if over_budget():
            log(f"time budget reached; skipping upload baseline for {name}")
            continue
        # both paths ending device-resident (the training-pipeline view);
        # skippable under time pressure — the primary metrics above are
        # never discarded once measured
        try:
            pipe_t = _median(bench_host(path, rows, upload=True))
            r["device_vs_host_pipeline"] = round(pipe_t / dev_t, 3)
        except Exception as e:  # noqa: BLE001
            log(f"config {key} upload baseline FAILED: {e!r}")
        vs = r.get("device_vs_host")
        pipe = r.get("device_vs_host_pipeline")
        log(f"config {key} {name}: device {r['device_rows_per_sec']/1e6:.1f} M rows/s "
            f"({r['device_mb_per_sec']:.0f} MB/s)"
            + (f", {vs:.1f}x host" if vs is not None else "")
            + (f", {pipe:.1f}x host+upload pipeline" if pipe is not None else ""))

    def _config_file(cfg_key):
        """The config's bench file (reusing the measured path, else
        generating); returns (path, rows)."""
        name, gen, base_rows = CONFIGS[cfg_key]
        entry = dev_times.get(name)
        if entry is not None:
            _w, ppath, prows, _k, _mb = entry
            return ppath, prows
        prows = int(base_rows * SCALE)
        ppath = f"/tmp/tpq_bench_{name}_{prows}.parquet"
        if not os.path.exists(ppath):
            gen(ppath, prows)
        return ppath, prows

    # Overlapped chunk pipeline: host decode prefetch={0,4} on the headline
    # file (ISSUE 1 acceptance: >= 1.3x sequential) AND on plain_int64 (the
    # round-4 ≥0.9x-host target, re-measured against the overlap path —
    # ISSUE 2 satellite).  Skip: BENCH_PIPELINE=0.
    if os.environ.get("BENCH_PIPELINE", "1") != "0" and not over_budget():
        for cfg_key, out_name in (("4", "pipeline"),
                                  ("1", "pipeline_plain_int64")):
            try:
                ppath, prows = _config_file(cfg_key)
                results[out_name] = bench_pipeline(ppath, prows)
                if cfg_key == "1":
                    dev = results.get("plain_int64", {}).get(
                        "device_rows_per_sec")
                    if dev:
                        # the round-4 target ratio, with the overlapped host
                        # decode as the denominator
                        results[out_name]["device_vs_host_prefetch4"] = round(
                            dev / results[out_name]["prefetch4_rows_per_sec"],
                            3)
            except Exception as e:  # noqa: BLE001
                log(f"pipeline bench ({out_name}) FAILED: {e!r}")
            if over_budget():
                break

    # Training-input loader: shuffled-epoch throughput at prefetch={0,4} on
    # the headline file's fixed-width columns.  Skip: BENCH_LOADER=0.
    if os.environ.get("BENCH_LOADER", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["loader"] = bench_loader(ppath, prows)
        except Exception as e:  # noqa: BLE001
            log(f"loader bench FAILED: {e!r}")

    # Fault-tolerant IO backend: store indirection overhead + injected-
    # fault recovery on the headline file.  Skip with BENCH_IOFAULTS=0.
    if os.environ.get("BENCH_IOFAULTS", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["io_faults"] = bench_io_faults(ppath, prows)
        except Exception as e:  # noqa: BLE001
            log(f"io_faults bench FAILED: {e!r}")

    # Corruption containment: default-on validation overhead (<1.03x gate)
    # + seeded-corruption skip_unit accounting.  Skip with BENCH_DATAFAULTS=0.
    if os.environ.get("BENCH_DATAFAULTS", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["data_faults"] = bench_data_faults(ppath, prows)
        except Exception as e:  # noqa: BLE001
            log(f"data_faults bench FAILED: {e!r}")

    # High-QPS scan service: concurrency sweep over a shared ScanService
    # vs sequential one-shot opens (plan/footer/dict cache win + p50/p95
    # SLOs).  Skip with BENCH_SERVE=0; smoke DOES run it (cheap, and the
    # service's thread lifecycle rides the leak gate below).
    if os.environ.get("BENCH_SERVE", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["serve"] = bench_serve(ppath, prows)
        except Exception as e:  # noqa: BLE001
            log(f"serve bench FAILED: {e!r}")

    # Tiered result cache (ISSUE 14): hot/cold A/B (warm-vs-cold speedup),
    # zipfian hot-set + long-tail mix, and mutation-mid-sweep invalidation
    # accounting.  Skip with BENCH_SERVE_CACHE=0; smoke runs it tiny.
    if (os.environ.get("BENCH_SERVE_CACHE", "1") != "0"
            and not over_budget()):
        try:
            ppath, prows = _config_file("4")
            entry = bench_serve_cache(ppath, prows, smoke=args.smoke)
            if isinstance(results.get("serve"), dict):
                results["serve"]["result_cache"] = entry
            else:
                results["serve"] = {"result_cache": entry}
        except Exception as e:  # noqa: BLE001
            log(f"serve_cache bench FAILED: {e!r}")

    # Request-lifecycle resilience: the serve sweep under a seeded stall
    # storm, hedging off vs on (p99 cut + win rate), a brownout shed
    # phase, and the hedge thread-leak assertion.  Skip with
    # BENCH_SERVE_FAULTS=0; smoke runs a tiny phase.
    if os.environ.get("BENCH_SERVE_FAULTS", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["serve_faults"] = bench_serve_faults(
                ppath, prows, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"serve_faults bench FAILED: {e!r}")

    # Multi-tenant fair-share QoS (ISSUE 17): victim-tenant p99 isolated
    # vs under a noisy flood, FIFO vs weighted DRR — the fairness win in
    # one ratio.  Streaming sessions ride tpq-serve workers, so the
    # exit-3 leak gate below covers them via the existing prefix.  Skip
    # with BENCH_SERVE_TENANTS=0; smoke runs a tiny phase.
    if (os.environ.get("BENCH_SERVE_TENANTS", "1") != "0"
            and not over_budget()):
        try:
            ppath, prows = _config_file("4")
            results["serve_tenants"] = bench_serve_tenants(
                ppath, prows, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"serve_tenants bench FAILED: {e!r}")

    # IO-concurrency scaling (ISSUE 18): async fetch engine vs blocking-
    # read thread pool under 50ms injected latency, sweeping in-flight
    # {8, 64, 256} — byte-identity and the no-leaked-threads bar are
    # asserted inside.  Skip with BENCH_IOSCALE=0; smoke runs a tiny sweep.
    if os.environ.get("BENCH_IOSCALE", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["io_scale"] = bench_io_scale(
                ppath, prows, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"io_scale bench FAILED: {e!r}")

    # Tracing-cost A/B (ISSUE 19): the serve workload with request tracing
    # off / tail-sampled / retain-all — banks p50/p99 overhead ratios and
    # asserts the export-ring byte bound + the zero-traces-when-off bar.
    # Skip with BENCH_OBS=0; smoke runs a tiny mix.
    if os.environ.get("BENCH_OBS", "1") != "0" and not over_budget():
        try:
            ppath, prows = _config_file("4")
            results["obs_overhead"] = bench_obs_overhead(
                ppath, prows, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"obs_overhead bench FAILED: {e!r}")

    # Fused-vs-unfused device decode A/B on the dominant kernel families
    # (ISSUE 13): forced-route scans banking device_seconds + dispatch/
    # pass counts per side.  Skip with BENCH_FUSED=0; smoke runs it tiny
    # (the structural pass-count bar holds even in interpret mode).
    if os.environ.get("BENCH_FUSED", "1") != "0" and not over_budget():
        try:
            fused_files = {}
            for cfg_key, cname in (("1", "plain_int64"), ("4", "lineitem16")):
                try:
                    fused_files[cname] = _config_file(cfg_key)[0]
                except Exception as e:  # noqa: BLE001
                    log(f"fused bench: no {cname} file: {e!r}")
            results["fused"] = bench_fused(fused_files, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"fused bench FAILED: {e!r}")

    # Writer throughput (host encode; ~10s).  Skip with BENCH_WRITES=0.
    if os.environ.get("BENCH_WRITES", "1") != "0" and not over_budget():
        try:
            results["writes"] = bench_writes()
        except Exception as e:  # noqa: BLE001
            log(f"write bench FAILED: {e!r}")

    # Write-at-scale: N-worker sharded encode vs single writer + the
    # compaction pass's file-count and planner link-byte ratio (ISSUE 15).
    # Skip with BENCH_WRITE=0; --smoke runs it tiny.
    if os.environ.get("BENCH_WRITE", "1") != "0" and not over_budget():
        try:
            results["write_scale"] = bench_write_scale(smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            log(f"write_scale bench FAILED: {e!r}")

    # Pallas vs XLA bit-unpack microbench (the L1 primitive).
    # Cheap (~5s); skip with BENCH_PALLAS=0.
    if os.environ.get("BENCH_PALLAS", "1") != "0" and not over_budget():
        try:
            results["pallas_unpack"] = _pallas_microbench()
            log(f"pallas unpack microbench: {results['pallas_unpack']}")
        except Exception as e:  # noqa: BLE001
            log(f"pallas microbench FAILED: {e!r}")

    try:
        meta["link_mb_per_sec_end"] = probe_link()
    except Exception as e:  # noqa: BLE001
        log(f"end link probe FAILED: {e!r}")
    if args.smoke:
        meta["smoke"] = True
    results["sampling"] = meta

    headline_name = "lineitem16"
    if headline is None:  # config 4 not run: fall back to the first DECODE
        # result (the pallas microbench entry has no rows/s and must never
        # become the headline)
        decode_results = {k: v for k, v in results.items()
                          if "device_rows_per_sec" in v}
        if not decode_results:
            emit_results({"metric": "no_valid_configs", "value": 0.0,
                          "unit": "rows/s", "vs_baseline": 0.0,
                          "configs": results})
            sys.exit(1)
        headline_name, headline = next(iter(decode_results.items()))
    record = {
        "metric": f"{headline_name}_decode_rows_per_sec_device",
        "value": headline["device_rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": headline.get("device_vs_host", 0.0),
        "configs": results,
    }
    artifact_path = _artifact_path()
    # ledger + gate run BEFORE emit (their summaries ride the compact line)
    # but the exit happens AFTER: the driver always gets its JSON line
    rc = _ledger_and_check(record, args, artifact_path)
    emit_results(record, artifact_path)
    # obs daemon hygiene: every sampler/watchdog any reader started must be
    # stopped by now (readers close in their benches) — a leak here is a
    # thread-lifecycle regression the smoke gate must catch.  The
    # tpq-serve prefix also covers streaming scan sessions: they execute
    # ON the service's worker threads, so a session close() leaving its
    # producer wedged shows up here as a leaked worker.  After emit: the
    # driver always gets its JSON line first.
    import threading

    # the shared fetch engine is process-lived by design (scans reuse its
    # loop thread); benches are done with it here, so shut it down and hold
    # it to the same zero-leak bar as every other daemon
    from tpu_parquet.iostore_async import shutdown_default_engine

    shutdown_default_engine()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith(("tpq-sampler", "tpq-watchdog",
                                    "tpq-hedge",
                                    "tpq-serve", "tpq-fetch",
                                    "tpq-metricsdump", "tpq-spool"))]
    if leaked:
        log(f"FAIL: obs daemon threads leaked after completion: {leaked}")
        sys.exit(3)
    if rc:
        sys.exit(rc)
    if failed:
        log(f"FAIL: configs failed: {failed}")
        sys.exit(4)


if __name__ == "__main__":
    main()
