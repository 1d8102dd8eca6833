"""The gather family's share of the HBM roofline: the least time in which
the chips could write the bytes its programs returned over the window's
scans (``device.kernels.gather.bytes_out`` of the ``scan_files(trace=)``
registries) once at peak HBM bandwidth, over the device seconds of its
programs (``jit_tpq_gather*``, profiler trace).  Nothing is read where the
registries carry no such counter or no such program ran."""

import window_trace


def read(ctx):
    t = window_trace.load(ctx)
    secs = t.module_s("jit_tpq_gather") if t is not None else 0.0
    written = 0
    for lp in ctx["window"].get("loops", []):
        for reg in lp["registries"]:
            kernels = (reg.get("device") or {}).get("kernels") or {}
            written += (kernels.get("gather") or {}).get("bytes_out", 0)
    if secs <= 0 or written <= 0:
        return None
    return 100.0 * written / ctx["peaks"]["hbm_bytes_per_s"] / secs
