#!/usr/bin/env python3
"""Bring-up check of the device read path on the chip.

    python chip_smoke.py [--seed N]             # one chip (what the driver runs)
    python chip_smoke.py --chips 4 [--seed N]   # the multi-chip path only

One chip: generate TPC-H lineitem at SF1 from ``--seed`` (16 columns, 1M-row
groups, SNAPPY, page CRCs), scan it twice with ``scan_files`` and compare
every column bit for bit with ``pyarrow.parquet.read_table``, then send four
``serve.ScanService`` requests (full scan, 3-column projection, a filter on
``l_shipdate``, a repeat that must hit the plan cache) and compare each.

Four chips: eight lineitem part files split over the devices by
``parallel.plan_shards``, each shard scanned on its own device, stitched
into one row-sharded array reduced by a jitted global sum/min/max, plus
``parallel.global_column_array`` on a 4-device mesh — all against pyarrow.

There is no CPU fallback: without a TPU the script exits non-zero before
doing anything.  One process drives every chip and starts no JAX children.
The last stdout line is the JSON verdict; everything else comes before it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke")  # generated data; listed in .gitignore
FILTER_DAY = 9500  # l_shipdate >= this keeps ~42% of the uniform dates
PROJECTION = ["l_orderkey", "l_extendedprice", "l_shipmode"]


def log(*a) -> None:
    print(*a, flush=True)


def require_tpu(count: int):
    """The devices to run on; exits non-zero unless JAX reports a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (platform {d.platform!r}); "
                 f"there is no CPU fallback")
    if len(devs) < count:
        sys.exit(f"chip_smoke: need {count} chips, JAX reports {len(devs)}")
    return devs[:count]


def probe_link(mb: int = 256, reps: int = 3) -> float:
    """Median host->device rate (MB/s) of ``reps`` transfers of ``mb`` MiB."""
    import jax
    import numpy as np

    a = np.ones(mb << 20, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(a[: 1 << 20]))
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        x = jax.block_until_ready(jax.device_put(a))
        rates.append((mb << 20) / 1e6 / (time.perf_counter() - t0))
        x.delete()
    return sorted(rates)[len(rates) // 2]


def log_cache() -> None:
    """Print the persistent compile cache's directory and size."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    size = sum(os.path.getsize(os.path.join(r, f))
               for r, _, fs in os.walk(d or "") for f in fs)
    log(f"compile cache: {d}, {size} bytes")


# ---------------------------------------------------------------------------
# comparison with pyarrow (the independent reference)
# ---------------------------------------------------------------------------

def _host_parts(parts):
    """Device column parts (one per row group) -> host arrays/ByteArrayData."""
    from tpu_parquet.column import ByteArrayData
    import numpy as np

    hosts = [p.to_host() for p in parts]
    if not isinstance(hosts[0], ByteArrayData):
        return np.concatenate(hosts)
    offs, heaps, base = [np.zeros(1, np.int64)], [], 0
    for h in hosts:
        o = np.asarray(h.offsets, np.int64)
        offs.append(o[1:] - o[0] + base)
        heaps.append(np.asarray(h.heap)[o[0]:o[-1]])
        base += int(o[-1] - o[0])
    return ByteArrayData(offsets=np.concatenate(offs),
                         heap=np.concatenate(heaps))


def _take(col, idx):
    from tpu_parquet.column import ByteArrayData

    return col.take(idx) if isinstance(col, ByteArrayData) else col[idx]


def _same(name: str, got, ref) -> None:
    """Bit-for-bit equality of one host column with a pyarrow column."""
    import numpy as np
    import pyarrow as pa
    from tpu_parquet.column import ByteArrayData

    arr = ref.combine_chunks() if isinstance(ref, pa.ChunkedArray) else ref
    if isinstance(got, ByteArrayData):
        arr = arr.cast(pa.large_binary())
        o = np.frombuffer(arr.buffers()[1], np.int64)[
            arr.offset: arr.offset + len(arr) + 1]
        heap = np.frombuffer(arr.buffers()[2], np.uint8)[o[0]:o[-1]]
        go = np.asarray(got.offsets, np.int64)
        ok = (len(go) == len(o) and np.array_equal(go - go[0], o - o[0])
              and np.array_equal(np.asarray(got.heap)[go[0]:go[-1]], heap))
    else:
        want = arr.to_numpy(zero_copy_only=False)
        ok = (got.dtype == want.dtype and got.shape == want.shape
              and np.array_equal(got.view(np.uint8), want.view(np.uint8)))
    if not ok:
        raise AssertionError(f"column {name}: device result differs from "
                             f"pyarrow")


def check_groups(groups, table, columns=None) -> int:
    """Compare scanned row groups ``[{col: DeviceColumnData}]`` with a
    pyarrow table; returns the rows compared."""
    names = columns or table.column_names
    if set(groups[0]) != set(names):
        raise AssertionError(f"columns {sorted(groups[0])} != {sorted(names)}")
    rows = 0
    for name in names:
        got = _host_parts([g[name] for g in groups])
        _same(name, got, table.column(name))
        rows = len(got)
    return rows


def _arrays(groups):
    return [a for g in groups for c in g.values()
            for a in (c.values, c.offsets, c.heap,
                      getattr(c, "indices", None), getattr(c, "dict_u8", None),
                      getattr(c, "dict_offsets", None),
                      getattr(c, "dict_heap", None))
            if a is not None]


def timed_scan(paths, **kw):
    """One ``scan_files`` pass ended by ``block_until_ready``."""
    import jax
    from tpu_parquet.device_reader import scan_files

    t0 = time.perf_counter()
    groups = list(scan_files(paths, **kw))
    jax.block_until_ready(_arrays(groups))
    return groups, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(seed: int, dev) -> None:
    import jax
    import numpy as np
    import pyarrow.parquet as pq
    from tpu_parquet import datagen
    from tpu_parquet.pallas_kernels import pallas_mode
    from tpu_parquet.serve import ScanRequest, ScanService

    path = os.path.join(WORK, f"lineitem_sf1_seed{seed}.parquet")
    rows = datagen.LINEITEM_SF1_ROWS
    t0 = time.perf_counter()
    datagen.gen_lineitem16(path, rows, seed=seed)
    log(f"generated lineitem: {rows} rows, {os.path.getsize(path)} bytes, "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    ref = pq.read_table(path)
    log(f"pyarrow read_table: {time.perf_counter() - t0:.3f} s, "
        f"{ref.nbytes} bytes")

    trace = os.path.join(WORK, "cold_scan_trace.json")
    groups, cold = timed_scan([path], trace=trace)
    n = check_groups(groups, ref)
    log(f"scan 1 (cold): {cold:.3f} s, {len(groups)} row groups, {n} rows, "
        f"16 columns bit-identical to pyarrow")
    log_cache()
    with open(trace) as f:
        reader = json.load(f)["otherData"]["registry"]["reader"]
    log("ship routes: " + json.dumps(reader["ship_routes"], sort_keys=True))
    log(f"fused fallbacks: {reader.get('fused_fallbacks')}")
    del groups
    groups, warm = timed_scan([path])
    check_groups(groups, ref)
    log(f"scan 2 (warm): {warm:.3f} s, bit-identical to pyarrow")
    log(f"scan wall seconds: cold {cold:.3f} warm {warm:.3f} "
        f"(compile share ~{max(cold - warm, 0.0):.3f} s)")
    del groups
    mode = pallas_mode()
    log(f"pallas_mode: {mode}")
    if mode != "compiled":
        raise AssertionError(f"pallas_mode {mode!r} on a TPU")

    with ScanService(concurrency=1) as svc:
        def ask(label, **kw):
            t = time.perf_counter()
            out = svc.scan(ScanRequest([path], device=True, **kw))[path]
            groups = [{k: (v[i] if isinstance(v, list) else v)
                       for k, v in out.items()}
                      for i in range(max(len(v) if isinstance(v, list) else 1
                                         for v in out.values()))]
            jax.block_until_ready(_arrays(groups))
            log(f"serve {label}: {time.perf_counter() - t:.3f} s")
            return groups

        check_groups(ask("full scan"), ref)
        check_groups(ask("projection", columns=PROJECTION), ref, PROJECTION)
        got = ask("filter", filter=f"l_shipdate >= {FILTER_DAY}")
        host = {k: _host_parts([g[k] for g in got]) for k in got[0]}
        keep = np.flatnonzero(host["l_shipdate"] >= FILTER_DAY)
        want = pq.read_table(path, filters=[("l_shipdate", ">=", FILTER_DAY)])
        if len(host["l_shipdate"]) < want.num_rows:
            raise AssertionError("filtered scan dropped matching rows")
        for k, col in host.items():
            _same(k, _take(col, keep), want.column(k))
        log(f"serve filter: {len(keep)} matching rows of "
            f"{len(host['l_shipdate'])} returned, bit-identical to pyarrow")
        before = svc.cache.counters()["plan_hits"]
        check_groups(ask("repeat", columns=None), ref)
        hits = svc.cache.counters()["plan_hits"] - before
        if hits < 1:
            raise AssertionError("repeat request missed the plan cache")
        log(f"serve repeat: plan cache hits +{hits}; all 4 requests "
            f"bit-identical to pyarrow")
    stats = dev.memory_stats() or {}
    log(f"peak HBM bytes in use: {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_multi_chip(seed: int, devs) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tpu_parquet import datagen, parallel as par
    from tpu_parquet.jax_kernels import enable_x64
    from tpu_parquet.reader import FileReader

    n_dev, n_files = len(devs), 8
    rows = datagen.LINEITEM_SF1_ROWS
    per = -(-rows // n_files)
    paths = []
    t0 = time.perf_counter()
    for i in range(n_files):
        p = os.path.join(WORK, f"lineitem_part{i}_seed{seed}.parquet")
        datagen.gen_lineitem16(p, min(per, rows - i * per),
                               seed=seed * 1000 + i, key_start=i << 32)
        paths.append(p)
    log(f"generated {n_files} lineitem parts: {rows} rows, "
        f"{time.perf_counter() - t0:.3f} s")
    plan = par.plan_shards([os.path.getsize(p) for p in paths], n_dev)
    log(f"plan_shards: {plan}")

    shards, owners = [], set()
    for s, files in enumerate(plan):
        with jax.default_device(devs[s]):
            groups, dt = timed_scan([paths[i] for i in files])
        where = {d for a in _arrays(groups) for d in a.devices()}
        if where != {devs[s]}:
            raise AssertionError(f"shard {s} arrays on {where}, want "
                                 f"{devs[s]}")
        owners.add(devs[s])
        ref = pa.concat_tables([pq.read_table(paths[i]) for i in files])
        check_groups(groups, ref)
        log(f"shard {s} on {devs[s]}: files {files}, {ref.num_rows} rows "
            f"in {dt:.3f} s, bit-identical to pyarrow")
        shards.append(groups)
    if len(owners) != n_dev:
        raise AssertionError(f"shards on {len(owners)} devices, want {n_dev}")
    log(f"{len(owners)} distinct devices hold shards")

    mesh = Mesh(np.asarray(devs), ("data",))
    with enable_x64():
        # l_quantity is dictionary-encoded: the gather runs on each device
        cols = [[g["l_quantity"].materialize() for g in groups]
                for groups in shards]
        parts = [jnp.concatenate([c.values[: c.num_values] for c in cs])
                 for cs in cols]
        valid = [int(p.shape[0]) for p in parts]
        cap = max(valid)
        pieces = [jax.device_put(jnp.pad(p, (0, cap - p.shape[0])), d)
                  for p, d in zip(parts, devs)]
        gq = jax.make_array_from_single_device_arrays(
            (n_dev * cap,), NamedSharding(mesh, P("data")), pieces)
        nv = jax.device_put(np.asarray(valid, np.int64),
                            NamedSharding(mesh, P()))

        @jax.jit
        def reduce(a, nv):
            m = (jnp.arange(a.shape[0]) % cap) < jnp.repeat(
                nv, cap, total_repeat_length=a.shape[0])
            big = jnp.iinfo(a.dtype).max
            return (jnp.sum(jnp.where(m, a, 0)),
                    jnp.min(jnp.where(m, a, big)),
                    jnp.max(jnp.where(m, a, -big)))

        got = [int(x) for x in jax.block_until_ready(reduce(gq, nv))]
    q = np.concatenate([pq.read_table(p, columns=["l_quantity"])
                        .column(0).to_numpy() for p in paths])
    want = [int(q.sum()), int(q.min()), int(q.max())]
    if got != want:
        raise AssertionError(f"global sum/min/max {got} != pyarrow {want}")
    log(f"stitched l_quantity over {len(gq.sharding.device_set)} devices: "
        f"sum/min/max {got} equal pyarrow")

    with FileReader(paths[0]) as r:
        arr, nrows = par.global_column_array(r, "l_orderkey", mesh)
    held = {s.device for s in arr.addressable_shards}
    ref = pq.read_table(paths[0], columns=["l_orderkey"]).column(0).to_numpy()
    with enable_x64():
        host = np.asarray(arr)[:nrows]
    if len(held) != n_dev or not np.array_equal(host, ref):
        raise AssertionError("global_column_array differs from pyarrow")
    log(f"global_column_array: {nrows} rows over {len(held)} devices, "
        f"bit-identical to pyarrow")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    from tpu_parquet import native

    lib = native.load()
    log(f"native library: {getattr(lib, '_name', None)}")
    if lib is None:
        raise AssertionError("native library did not load")
    log(f"link probe: {probe_link():.1f} MB/s host->device "
        f"(256 MiB device_put, median of 3)")

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            run_one_chip(args.seed, devs[0])
        else:
            run_multi_chip(args.seed, devs)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    log_cache()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
