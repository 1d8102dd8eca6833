"""Cost-based link-byte ship planner: choose HOW a chunk's bytes reach HBM.

The whole device reader is engineered around one scarce resource — the
host→device link, whose rate per device kind is ``LINK_MBPS`` below.  Until this module the
"ship fewer bytes" decisions were scattered route gates inside
``device_reader._ChunkAssembler``: device-snappy only for PLAIN fixed-width
SNAPPY pages, narrow transcode only as its fallback, everything else shipped
fully decompressed.  This module centralizes the decision as an explicit
cost model over the five routes a chunk's value stream can take:

===============  ============================================================
route            what ships over the link
===============  ============================================================
plain            the decompressed host bytes, as-is
narrow           ``(v - min)`` truncated to k bytes/value (PLAIN INT only)
narrow_snappy    the narrow transcode, then snappy over the truncated bytes
device_snappy    the file's own snappy page payloads, decompressed on device
recompress       host re-compresses the stream to snappy, ships compressed
===============  ============================================================

A FUSED variant (``fused_plain``) ships exactly its twin's bytes but runs
the device half as one Pallas megakernel pass (pallas_kernels): no
inter-stage HBM spill term in the cost model, one dispatch in the
registry's ``device`` section.  Offered when ``TPQ_FUSE`` permits
(default: exactly when the backend compiles Mosaic natively) and the
stream is fused-eligible (``fused_eligible``); at equal modeled cost the
planner prefers the fused variant.

Cost per route = host prep time + link time + device resolve time, each a
bytes/throughput term.  Link bandwidth comes from ``TPQ_LINK_MBPS`` when set,
else from ``LINK_MBPS`` for the default device's kind; the host/device terms are
calibrated constants, overridable for experiments.  The model only ROUTES —
every route decodes bit-identically, so a mis-ranked route costs time, never
correctness.

``TPQ_FORCE_ROUTE=<route>`` pins the choice for deterministic CI and A/B
debugging; infeasible forces (narrow on a float column, device_snappy on a
gzip file) fall back to ``plain``.

Per-route decisions and shipped-byte counters surface in
``device_reader.ReaderStats`` (``ship_routes``, ``link_bytes_shipped``,
``link_bytes_logical``) and ride the bench artifact.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass

ROUTE_PLAIN = "plain"
ROUTE_NARROW = "narrow"
ROUTE_NARROW_SNAPPY = "narrow_snappy"
ROUTE_DEVICE_SNAPPY = "device_snappy"
ROUTE_RECOMPRESS = "recompress"
# fused megakernel variant (pallas_kernels): the SAME bytes over the link
# as its unfused twin, but the device half runs as ONE Pallas pass instead
# of a chain of XLA calls with an HBM round trip between each stage
ROUTE_FUSED_PLAIN = "fused_plain"
# THE route-name registry: planner ranking, device_reader dispatch, the
# TPQ_FORCE_ROUTE validation, and the ScanPlan route memo all share this
# one table (parse_route below is the one env-validation entry point), so
# a fused name added here is automatically legal at every site.
ROUTES = (ROUTE_PLAIN, ROUTE_NARROW, ROUTE_NARROW_SNAPPY,
          ROUTE_DEVICE_SNAPPY, ROUTE_RECOMPRESS, ROUTE_FUSED_PLAIN)
# fused route -> the unfused twin whose link bytes / host work it shares
UNFUSED_OF = {ROUTE_FUSED_PLAIN: ROUTE_PLAIN}
FUSED_OF = {v: k for k, v in UNFUSED_OF.items()}
FUSED_ROUTES = tuple(UNFUSED_OF)

# host->device link rate (MB/s) the model assumes per jax ``device_kind``
# when TPQ_LINK_MBPS is unset.  A kind missing here raises (see
# device_link_mbps): a rate guessed for a device nobody measured would
# mis-rank every route.
LINK_MBPS = {
    # the tier-1 tests' planning point: the CPU backend has no link, and
    # this value only fixes the route rankings the tests assert
    "cpu": 350.0,
    # TPU v5e: chip_smoke.py's link probe (256 MiB device_put, median of
    # 3) on one chip, PR 21
    "TPU v5 lite": 6066.8,
}
# host-side throughputs (vectorized native passes; absolute values matter
# less than their RATIO to the link — every term here is GB/s-class while
# the link is hundreds of MB/s, which is the whole reason shrinking the
# payload wins)
HOST_TRANSCODE_MBPS = 2500.0   # min/max + truncating copy (native)
HOST_COMPRESS_MBPS = 1500.0    # native snappy_compress
HOST_DECOMPRESS_MBPS = 1400.0  # native snappy_decompress (lazy pages only)
# device-side op-table resolve (searchsorted + pointer-doubling gathers over
# the output space), charged per OUTPUT byte, per jax ``device_kind`` like
# LINK_MBPS (a missing kind raises).  One rate prices every device term:
# the snappy resolve, the narrow widen and the fused pass.
# TPQ_DEVICE_MBPS overrides it at planner construction — the device twin of
# TPQ_LINK_MBPS, fed back by `pq_tool doctor` when the measured per-route
# device lane (obs device timing) disagrees beyond DOCTOR_ERROR_BAND.
DEVICE_RESOLVE_MBPS = {
    # the tier-1 tests' planning point
    "cpu": 3000.0,
    # TPU v5e: device_snappy resolved SF1 lineitem's two PLAIN 8-byte
    # columns, 48,000,000 output bytes, in 7.025 device s (a host thread's
    # completion wait, one chip, PR 21).  The narrow widen and fused_plain ran at
    # ~3 GB/s there, so this rate overprices them; they lose to plain at
    # the v5e link rate regardless (warm SF1: plain x12 2.546 s, narrow x6
    # + plain x6 2.752 s, same run)
    "TPU v5 lite": 6.8,
}
# a compressed route must beat plain shipping by at least this ratio or the
# builder falls through (the op tables + resolve cost eat thin wins)
SNAPPY_WORTH_RATIO = 0.92
# streams smaller than this never pay a recompression attempt: the op-table
# fixed overhead rivals the payload
MIN_COMPRESS_BYTES = 1 << 16
# assumed compression ratios used only for RANKING (the builder measures the
# real ratio and falls back when the estimate was wrong — a wrong guess
# costs one GB/s-class host pass on the overlapped pool, never link bytes)
EST_NARROW_SNAPPY_RATIO = 0.6  # narrow output: low-entropy residuals
EST_RECOMPRESS_RATIO = 0.5     # strings/dates/ids under snappy
# inter-stage HBM spill the UNFUSED decode chain pays beyond its resolve
# term: each extra XLA stage re-reads and re-writes the output-sized
# intermediate (PR 9's per-kernel device timing is what made this term
# attributable).  Used only for the fused-vs-unfused device prediction
# (`unfused_device_costs` → the doctor's `fusion-win` line), never for
# ranking the unfused routes against each other — their relative order is
# untouched by the fusion work.
HBM_SPILL_PASSES = 2


def _kind_row(table: dict, kind: "str | None", what: str, env: str) -> float:
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    try:
        return table[kind]
    except KeyError:
        raise ValueError(
            f"no {what} rate for device kind {kind!r}: measure it on the "
            f"device (chip_smoke.py) and add a row, or set {env}") from None


def device_link_mbps(kind: "str | None" = None) -> float:
    """The ``LINK_MBPS`` row of ``kind`` (default: the default device's)."""
    return _kind_row(LINK_MBPS, kind, "host->device link", "TPQ_LINK_MBPS")


def device_resolve_mbps(kind: "str | None" = None) -> float:
    """The ``DEVICE_RESOLVE_MBPS`` row of ``kind`` (default: the default
    device's)."""
    return _kind_row(DEVICE_RESOLVE_MBPS, kind, "device resolve",
                     "TPQ_DEVICE_MBPS")


def parse_route(raw, *, source: str = "TPQ_FORCE_ROUTE") -> "str | None":
    """Validate a route name from the environment against the ONE registry
    (``ROUTES``).  Malformed values degrade — one ``warn_env_once`` line,
    then cost-ranked routing — instead of turning every reader
    construction (or a scan already in flight re-reading the env through
    ``default_planner``) into a raise.  Returns the canonical name or
    None."""
    v = (raw or "").strip()
    if not v:
        return None
    if v not in ROUTES:
        from .obs import warn_env_once

        warn_env_once(source, v, "cost-ranked routes (unforced)")
        return None
    return v


def fuse_enabled() -> bool:
    """Whether the planner offers fused megakernel routes (``TPQ_FUSE``).

    Same contract as ``TPQ_PALLAS``: unset → on exactly when the backend
    compiles Mosaic kernels natively (the fused graph is a perf feature,
    not a semantic one); ``1`` forces it on non-TPU backends through the
    Pallas interpreter (tier-1 exercises the fused graph bit-identically
    on CPU this way); ``0`` forces it off everywhere."""
    env = os.environ.get("TPQ_FUSE", "").strip()
    if env == "0":
        return False
    if env == "1":
        return True
    from .pallas_kernels import pallas_available

    return pallas_available()


@dataclass(frozen=True)
class ChunkFacts:
    """Everything the cost model needs to rank routes for one chunk.

    ``logical`` is the decompressed value-stream byte count (what ``plain``
    would ship); ``width`` the fixed value width (0 for byte-array/heap
    streams); ``narrow_k`` the stats-hinted narrow byte width when chunk
    Statistics prove the span fits (0 = unknown or infeasible);
    ``narrow_possible`` whether a narrow PROBE is allowed when no hint
    exists (int column + native library); ``comp_bytes`` the file's own
    snappy payload bytes available to ship as-is (0 = none);
    ``host_bytes_ready`` whether the decompressed host bytes already exist
    (dictionary tables, level-carrying pages) — when False and
    ``comp_bytes`` > 0, every host-bytes route additionally pays the
    decompress the lazy pages skipped.  ``flat`` whether the column is
    required and unrepeated (no def/rep level lanes) — the fused
    megakernel routes claim only flat streams, where "validity" is the
    tail mask the single pass bakes in.
    """

    logical: int
    width: int = 0
    narrow_k: int = 0
    narrow_possible: bool = False
    comp_bytes: int = 0
    native: bool = True
    host_bytes_ready: bool = False
    flat: bool = True


def fused_eligible(f: ChunkFacts) -> "tuple[str, ...]":
    """The fused routes these facts admit — the ONE eligibility predicate
    (planner pricing, device_reader dispatch, and the ``fused_plan`` fuzz
    invariants all call it, so the three sites cannot drift).  A fused row
    additionally requires its unfused twin to be priced feasible (the
    planner checks that; forced-fused on a stream whose twin build fails
    degrades in the builder with a counter, never a crash)."""
    if not f.flat or f.width not in (4, 8) or f.logical <= 0:
        return ()
    return (ROUTE_FUSED_PLAIN,)


class ShipPlanner:
    """Ranks ship routes by modeled wall cost; builders execute in order.

    One instance per reader (reads env at construction, so tests can flip
    ``TPQ_FORCE_ROUTE``/``TPQ_LINK_MBPS`` per reader); stateless after
    construction and safe to share across the prefetch pool's threads.
    """

    def __init__(self, link_mbps: "float | None" = None,
                 force: "str | None" = None,
                 device_mbps: "float | None" = None,
                 fuse: "bool | None" = None):
        from .obs import env_float

        if link_mbps is None:
            link_mbps = (env_float("TPQ_LINK_MBPS", 0.0)
                         or device_link_mbps())
        self.link_mbps = max(float(link_mbps), 1.0)
        if device_mbps is None:
            device_mbps = (env_float("TPQ_DEVICE_MBPS", 0.0)
                           or device_resolve_mbps())
        self.device_mbps = max(float(device_mbps), 1.0)
        if force is None:
            # env values degrade (parse_route: one warning, then unforced)
            # — an env typo must never raise mid-scan; an explicit force=
            # argument is a programming contract and still raises below
            force = parse_route(os.environ.get("TPQ_FORCE_ROUTE", ""))
        elif force not in ROUTES:
            raise ValueError(
                f"forced route {force!r} not one of {ROUTES}")
        self.force = force
        self.fuse = fuse_enabled() if fuse is None else bool(fuse)

    # -- cost terms (seconds) -------------------------------------------------

    @staticmethod
    def _t(nbytes: float, mbps: float) -> float:
        return nbytes / (mbps * 1e6)

    def _link(self, nbytes: float) -> float:
        return self._t(nbytes, self.link_mbps)

    def costs(self, f: ChunkFacts) -> dict:
        """Modeled seconds per FEASIBLE route (infeasible routes absent).

        Each route costs ``max(host lane, link lane, device lane)`` — the
        overlapped pipeline (prefetch pool + staging worker + async
        dispatch) runs host passes, transfers, and device resolves
        CONCURRENTLY, so steady-state cost is the bottleneck lane, not
        the sum.  The device lane (op-table resolve at HBM bandwidth) is
        almost never the bottleneck but keeps pathological op-heavy
        routes honest.

        ``plain`` is always present, so ``min(costs, key=costs.get)`` is
        total.  The narrow guess (no stats hint) only enters when no
        compressed payload exists — with one, the legacy hint contract
        applies: narrow claims the chunk only when Statistics prove the
        span, so a lying-stats file costs at most a wasted decompress.
        """
        L = float(f.logical)
        # every host-bytes route on a lazily-compressed chunk pays the
        # decompress the lazy parse skipped (the device_snappy route's
        # built-in win)
        mat = (self._t(L, HOST_DECOMPRESS_MBPS)
               if f.comp_bytes and not f.host_bytes_ready else 0.0)
        resolve = self._t(L, self.device_mbps)
        out = {ROUTE_PLAIN: max(mat, self._link(L))}
        if L <= 0:
            return out
        k = f.narrow_k
        if not k and f.narrow_possible and not f.comp_bytes:
            k = max(f.width // 2, 1)  # optimistic probe guess
        if k and f.width in (4, 8) and k < f.width:
            narrowed = L * k / f.width
            # the device lane: the widen/re-bias pass writes L output
            # bytes; narrow_snappy additionally resolves the compressed
            # stream over its narrowed output space first — strictly MORE
            # device work than bare narrow (device_costs mirrors these
            # terms exactly, so the calibration predictions and the
            # ranking model can never disagree about the same route)
            out[ROUTE_NARROW] = max(
                mat + self._t(L, HOST_TRANSCODE_MBPS),
                self._link(narrowed),
                self._t(L, self.device_mbps),
            )
            if f.native and narrowed >= MIN_COMPRESS_BYTES:
                out[ROUTE_NARROW_SNAPPY] = max(
                    mat + self._t(L, HOST_TRANSCODE_MBPS)
                    + self._t(narrowed, HOST_COMPRESS_MBPS),
                    self._link(narrowed * EST_NARROW_SNAPPY_RATIO),
                    self._t(L + narrowed, self.device_mbps),
                )
        if f.comp_bytes and f.native:
            out[ROUTE_DEVICE_SNAPPY] = max(
                self._link(float(f.comp_bytes)), resolve)
        if (not f.comp_bytes and f.native and L >= MIN_COMPRESS_BYTES):
            out[ROUTE_RECOMPRESS] = max(
                self._t(L, HOST_COMPRESS_MBPS),
                self._link(L * EST_RECOMPRESS_RATIO),
                resolve,
            )
        if self.fuse:
            # fused megakernel row: SAME host prep and link bytes as the
            # unfused twin, device lane = one single-pass term (no
            # inter-stage HBM spill, one dispatch).  Priced only for
            # fused-eligible facts (fused_eligible); at equal modeled cost
            # the tie goes to the fused variant (plan() below) — strictly
            # fewer dispatches for the same bytes.
            for fr in fused_eligible(f):
                out[fr] = max(mat, self._link(L), resolve)
        return out

    def device_costs(self, f: ChunkFacts, routes=None) -> dict:
        """Modeled DEVICE-lane seconds per feasible route (keys match
        :meth:`costs`; pass ``routes`` — e.g. the cost table a
        :meth:`plan` call just returned — to skip re-running the
        feasibility walk).

        The device lane is a route's kernel time on the device (a
        profiler capture's ``jit_tpq_<family>`` programs).  ``plain``
        models ~0 (reshape +
        bitcast, no compute); the compressed routes charge the op-table
        resolve per OUTPUT byte at ``device_mbps``; ``narrow`` charges the
        widen/re-bias pass the same way.  These ride ReaderStats per route
        (``predicted_device_s``) so ``ship_feedback()`` can put them next
        to the measured device lane — the ``TPQ_DEVICE_MBPS`` calibration
        signal, exactly as the link lane calibrates ``TPQ_LINK_MBPS``.
        """
        c = routes if routes is not None else self.costs(f)
        L = float(f.logical)
        k = f.narrow_k
        if not k and f.narrow_possible and not f.comp_bytes:
            k = max(f.width // 2, 1)
        narrowed = L * k / f.width if (k and f.width) else L
        out = {}
        for r in c:
            if r == ROUTE_PLAIN:
                out[r] = 0.0
            elif r == ROUTE_NARROW_SNAPPY:
                # resolve over the narrowed stream + the widen to L: the
                # SAME term costs() uses — strictly more device work than
                # bare narrow, never less
                out[r] = self._t(L + narrowed, self.device_mbps)
            else:
                # narrow widen / snappy resolve — and the fused route:
                # the megakernel's device lane is one output-sized pass
                out[r] = self._t(L, self.device_mbps)
        return out

    def unfused_device_costs(self, f: ChunkFacts, routes=None) -> dict:
        """Per FUSED route: the modeled device seconds its UNFUSED twin's
        stage chain would pay for the same stream — the twin's
        :meth:`device_costs` term plus ``HBM_SPILL_PASSES`` output-sized
        inter-stage round trips.  Recorded on fused ship records
        (``predicted_unfused_device_s``) so the registry carries the
        prediction the measured fused lane has to beat — the doctor's
        ``fusion-win`` verdict is exactly that comparison.  Never used to
        rank the unfused routes against each other."""
        c = routes if routes is not None else self.costs(f)
        dev = self.device_costs(f, routes=c)
        spill = self._t(float(f.logical) * HBM_SPILL_PASSES,
                        self.device_mbps)
        return {r: dev.get(UNFUSED_OF[r], 0.0) + spill
                for r in c if r in UNFUSED_OF}

    def routes(self, f: ChunkFacts) -> list:
        """Ordered candidate routes, cheapest modeled cost first.

        Builders try them in order and fall through on infeasibility (op
        caps, i32 ceilings, a ratio the estimate got wrong); ``plain`` —
        the route that cannot fail — terminates the walk wherever it
        ranks, so entries after it are dead fallbacks.
        """
        return self.plan(f)[0]

    def plan(self, f: ChunkFacts) -> "tuple[list, dict]":
        """``(routes, costs)``: the ordered candidates of :meth:`routes`
        plus the modeled seconds per feasible route — builders keep the
        costs so the chosen route's *prediction* can ride the obs layer
        next to the measured lanes (TPQ_LINK_MBPS calibration feedback).
        A forced route that the model never priced (infeasible) simply has
        no entry; consumers treat a missing prediction as 0."""
        c = self.costs(f)
        if self.force is not None:
            order = ([self.force, ROUTE_PLAIN] if self.force != ROUTE_PLAIN
                     else [ROUTE_PLAIN])
            return order, c
        # equal-cost tie goes to the fused variant: same bytes, same host
        # work, ONE device dispatch instead of a stage chain (the common
        # fused_plain-vs-plain case on link-bound streams is exactly this
        # tie).  A fused row priced WORSE than its twin (slow device) still
        # ranks after it — the tie-rank only breaks equality.
        return sorted(c, key=lambda r: (c[r], r not in UNFUSED_OF,
                                        ROUTES.index(r))), c

    def decision_table(self, f: ChunkFacts) -> dict:
        """Route → modeled milliseconds (README/debug surface)."""
        return {r: round(t * 1e3, 3) for r, t in self.costs(f).items()}


def recalibrate_link_mbps(link_bytes_per_sec: float) -> "float | None":
    """The ``TPQ_LINK_MBPS`` value a measured staging rate says to re-run
    with (``pq_tool doctor``'s recalibration output): the observed link
    lane in MB/s, floored at the planner's own 1 MB/s clamp.  ``None``
    when nothing was measured — an unmeasured link must never overwrite a
    banked calibration with a guess."""
    if not link_bytes_per_sec or link_bytes_per_sec <= 0:
        return None
    return max(round(link_bytes_per_sec / 1e6, 1), 1.0)


def recalibrate_device_mbps(device_bytes_per_sec: float) -> "float | None":
    """The ``TPQ_DEVICE_MBPS`` value a measured device-resolve rate says to
    re-run with (the device twin of :func:`recalibrate_link_mbps`): logical
    output bytes through the measured per-route device seconds, in MB/s,
    floored at the planner's 1 MB/s clamp.  ``None`` when the device lane
    was never timed — an unmeasured device must never overwrite a banked
    calibration with a guess."""
    if not device_bytes_per_sec or device_bytes_per_sec <= 0:
        return None
    return max(round(device_bytes_per_sec / 1e6, 1), 1.0)


_default: "ShipPlanner | None" = None
_default_lock = threading.Lock()


def default_planner() -> ShipPlanner:
    """Process-wide planner for callers without a reader (decode_chunk_batched
    and the page-at-a-time paths).  Rebuilt when the routing env knobs change
    so monkeypatched tests see their override."""
    global _default
    key = (os.environ.get("TPQ_LINK_MBPS", ""),
           os.environ.get("TPQ_FORCE_ROUTE", ""),
           os.environ.get("TPQ_DEVICE_MBPS", ""),
           os.environ.get("TPQ_FUSE", ""))
    with _default_lock:
        if _default is None or getattr(_default, "_env_key", None) != key:
            _default = ShipPlanner()
            _default._env_key = key
        return _default
