"""Pallas TPU kernels for the hot bit-level decode primitives.

The XLA decode kernels (jax_kernels.py) express bit extraction as per-value
byte *gathers* — fully general (arbitrary per-value positions/widths), which
the RLE-hybrid and delta paths need.  But the single hottest primitive —
fixed-width unpack of an 8-value-aligned stream (the reference's 98 generated
``unpack8intXX_N`` functions, bitbacking32.go/bitpacking64.go) — has an
affine access pattern Pallas can exploit: a tile of 8 values occupies exactly
``width`` contiguous bytes, so every byte a lane needs is a STATIC column of
a (groups, width) byte matrix.  The kernel below is pure strided loads +
shifts + ors: no gathers, no dynamic indexing, VMEM-resident.

Layout: values [g*8+j] live in row g of the (G, width) byte matrix; value j's
bits start at static bit ``j*width`` of the row, so the unroll over j∈[0,8)
bakes byte offsets and shifts into the instruction stream — the same
specialization trick as the reference's generated Go, but one parameterized
kernel instead of 98 source functions, and 8×128 lanes per VPU op instead of
one value per iteration.

On non-TPU backends (CPU tests) the kernel runs through the Pallas
interpreter.  The reader reaches it through :func:`unpack_bp_groups`;
bench.py's microbenchmark A/Bs it against ``jax_kernels.unpack_bits``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["unpack_bp_groups", "bp_groups_pad", "bp_value_index",
           "pallas_available", "pallas_mode",
           "resolve_interpret", "fused_plain_words", "fused_count_pad"]

_GROUPS_PER_TILE = 1024  # 8192 values per grid step; (1024,) = one 8x128 tile

# probed once per process (satellite of ISSUE 13): the backend platform
# cannot change under a live process, and the old per-call probe showed up
# as jax.devices() churn on the dispatch hot path once every fused plan
# asked it.  None = not probed yet.
_AVAILABLE: "bool | None" = None


def pallas_available() -> bool:
    """True when the current default backend can run Mosaic TPU kernels
    (cached after the first probe; ``_reset_available_cache`` un-caches for
    tests that flip backends)."""
    global _AVAILABLE
    if _AVAILABLE is None:
        try:
            plat = jax.devices()[0].platform
        except Exception:  # noqa: BLE001
            plat = None
        _AVAILABLE = plat == "tpu"
    return _AVAILABLE


def _reset_available_cache() -> None:
    global _AVAILABLE
    _AVAILABLE = None


def pallas_mode() -> str:
    """``"compiled"`` (native Mosaic) or ``"interpret"`` — how any Pallas
    kernel reached in this process actually runs.  Recorded in the ledger
    env fingerprint so a banked bench number carries whether its fused
    kernels were compiled or interpreted (an interpret-mode device time is
    not a measurement of the kernel)."""
    return "compiled" if pallas_available() else "interpret"


def resolve_interpret(interpret: "bool | None" = None) -> bool:
    """The ``interpret=`` every Pallas entry point resolves through: unset
    means native Mosaic where the backend compiles it, else the Pallas
    interpreter with ONE process-wide breadcrumb (bit-identical, a perf
    cliff, never a failure).  On a TPU the interpreter is an error, whoever
    asks for it: an interpreted kernel there would hide the device behind
    a bit-identical but unmeasurable path."""
    if interpret is None:
        interpret = not pallas_available()
        if interpret:
            from .obs import warn_env_once

            warn_env_once("TPQ_FUSE", "<no mosaic backend>",
                          "pallas interpret mode (bit-identical, not a "
                          "measurement)")
    if interpret and pallas_available():
        raise RuntimeError("Pallas interpret mode requested on a TPU "
                           "backend; kernels there must compile natively")
    return bool(interpret)


def _unpack_kernel(width: int, in_ref, out_ref):
    """One tile: (width, G) byte PLANES -> (8, G) value planes.

    Plane b holds byte b of every group's packed row (host transposes once).
    Leading-dim static indexing `in_ref[k, :]` is the layout Mosaic lowers
    cleanly — strided u8 column slices of a (G, width) tile miscompile
    (verified on v5e: the `<<16` term of 3-byte accumulations silently
    drops for ~1/4 of the lanes).  The output is value-major for the same
    reason and one more: a (G, 8) result's flattening reshape is a relayout
    that took XLA ~22 s to compile per shape at 1M values (PR 21, described
    v5e), while an (8, G) array flattens for free.

    Static unroll over the 8 values of a group: value j's field starts at bit
    j*width of its row, i.e. byte j*width//8 with shift j*width%8 — all
    Python ints at trace time, so the loop emits straight-line vector code.
    """
    mask = jnp.uint32((1 << width) - 1 if width < 32 else 0xFFFFFFFF)
    for j in range(8):
        start = (j * width) // 8
        shift = (j * width) % 8
        end = (j * width + width - 1) // 8  # inclusive last byte
        acc = in_ref[start, :].astype(jnp.uint32)
        for k in range(start + 1, min(end, start + 3) + 1):
            acc = acc | (in_ref[k, :].astype(jnp.uint32)
                         << jnp.uint32(8 * (k - start)))
        val = acc if shift == 0 else acc >> jnp.uint32(shift)
        if end - start + 1 > 4:  # 5-byte span (width>25, shift>0): straggler
            val = val | (in_ref[start + 4, :].astype(jnp.uint32)
                         << jnp.uint32(32 - shift))
        out_ref[j, :] = val & mask


def _unpack_call(planes, width: int, groups: int, interpret: bool):
    """The one pallas_call site: (width, groups) byte planes -> u32[8, groups]
    (row j holds value j of every group).

    The BlockSpec layout here IS the Mosaic miscompile workaround documented
    on _unpack_kernel (leading-dim plane indexing, never strided u8 column
    slices) — both jit entry points share it so they can't drift apart.
    """
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        functools.partial(_unpack_kernel, width),
        out_shape=jax.ShapeDtypeStruct((8, groups), jnp.uint32),
        grid=(groups // _GROUPS_PER_TILE,),
        in_specs=[pl.BlockSpec((width, _GROUPS_PER_TILE), lambda t: (0, t))],
        out_specs=pl.BlockSpec((8, _GROUPS_PER_TILE), lambda t: (0, t)),
        interpret=interpret,
    )(planes)


def bp_groups_pad(groups: int) -> int:
    """Pad a group count to a whole number of kernel tiles (bucketed first so
    the (width, groups_pad) executable set stays bounded across chunks)."""
    from .jax_decode import _bucket_count

    b = _bucket_count(max(groups, 1))
    return -(-b // _GROUPS_PER_TILE) * _GROUPS_PER_TILE


@functools.partial(
    jax.jit, static_argnames=("width", "groups_pad", "interpret")
)
def _bp_groups_jit(buf, bp_base, *, width, groups_pad, interpret):
    bp = jax.lax.dynamic_slice(buf, (bp_base,), (groups_pad * width,))
    planes = bp.reshape(groups_pad, width).T
    return _unpack_call(planes, width, groups_pad, interpret).reshape(-1)


def bp_value_index(i, groups_pad: int):
    """Where value ``i`` of the stream sits in :func:`unpack_bp_groups`'
    value-major output: value j of group g is element ``j * groups_pad +
    g``."""
    return (i % 8) * groups_pad + i // 8


def unpack_bp_groups(buf_dev, bp_base: int, width: int, groups_pad: int,
                     interpret: bool = False):
    """Unpack ``groups_pad`` 8-value groups of ``width``-bit values starting
    at byte ``bp_base`` of the staged device buffer.

    The production entry point the batched reader routes hybrid bit-packed
    runs through: BP payloads are staged *contiguously* (group-aligned, a
    structural property of the RLE/BP hybrid format — every BP run is whole
    8-value groups starting on a byte boundary), so the unpack is the exact
    fixed-width affine case this kernel exists for — no gathers at all.
    Returns uint32[8 * groups_pad] VALUE-MAJOR (value j of group g at
    ``j * groups_pad + g`` — :func:`bp_value_index`); bytes past the real
    payload decode to garbage values that callers never select (combine
    masks by run table).

    ``groups_pad`` must come from :func:`bp_groups_pad`.  Traced with x64
    disabled regardless of ambient context: the decode paths run under
    scoped_x64, but an x64 trace makes the grid index maps emit i64, which
    Mosaic refuses to legalize ("func.return (i32, i64)"); the uint32
    result is x64-agnostic.
    """
    if not 1 <= width <= 32:
        raise ValueError(f"unpack_bp_groups supports widths 1..32, got "
                         f"{width}")
    if groups_pad % _GROUPS_PER_TILE:
        raise ValueError(f"groups_pad {groups_pad} not a multiple of "
                         f"{_GROUPS_PER_TILE}")
    if isinstance(bp_base, (int, np.integer)):
        bp_base = np.int32(bp_base)  # traced callers pass their own i32
    from .jax_kernels import enable_x64

    # tpq.unpack name scope: the TPQ_XPROF device timeline attributes the
    # Pallas unpack to the same kernel family as the XLA fallback path
    with enable_x64(False), jax.named_scope("tpq.unpack"):
        return _bp_groups_jit(buf_dev, bp_base, width=width,
                              groups_pad=groups_pad,
                              interpret=resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# fused decode megakernel (ISSUE 13 / ROADMAP direction 2): ONE pallas_call
# for the PLAIN fixed-width route instead of the slice → bitcast → tail-mask
# XLA chain.  (A fused narrow+snappy kernel existed until PR 21: its per-lane
# gathers from the VMEM payload and op tables are not a pattern Mosaic can
# lower — "Only 2D gather is supported" — so the route went with it and the
# unfused narrow_snappy chain serves those streams.)  Interpret mode
# (non-TPU backends) executes the SAME graph bit-identically, so tier-1
# proves correctness on CPU; tests/test_chip_compile.py proves it compiles
# for a v5e.
# ---------------------------------------------------------------------------

_FUSED_TILE = 1024      # values per grid step, fused plain kernel


def fused_count_pad(count: int) -> int:
    """Pad a value count to whole fused-plain tiles (bucketed first so the
    executable set stays bounded across chunks — same contract as
    :func:`bp_groups_pad`)."""
    from .jax_decode import _bucket_count

    b = _bucket_count(max(count, 1))
    return -(-b // _FUSED_TILE) * _FUSED_TILE


def _fused_plain_kernel(width, in_ref, nv_ref, out_ref):
    """One tile of the fused PLAIN fixed-width decode: (width, T) byte
    planes -> (T, width//4) finished u32 words, validity tail baked in.

    Same plane layout/indexing contract as :func:`_unpack_kernel` (leading-
    dim static plane reads — never strided u8 column slices).  The only
    dynamic input is ``nv`` (the real value count): lanes at or past it
    write zero words, which is the "validity" the unfused chain leaves to
    a separate tail-mask pass."""
    from jax.experimental import pallas as pl

    nv = nv_ref[0, 0]
    base = pl.program_id(0) * _FUSED_TILE
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (_FUSED_TILE,), 0)
    keep = pos < nv
    for w in range(width // 4):
        acc = in_ref[4 * w, :].astype(jnp.uint32)
        for b in range(1, 4):
            acc = acc | (in_ref[4 * w + b, :].astype(jnp.uint32)
                         << jnp.uint32(8 * b))
        out_ref[:, w] = jnp.where(keep, acc, jnp.uint32(0))


@functools.partial(
    jax.jit, static_argnames=("width", "count_pad", "interpret")
)
def _fused_plain_jit(buf, vbase, nv, *, width, count_pad, interpret):
    from jax.experimental import pallas as pl

    raw = jax.lax.dynamic_slice(buf, (vbase,), (count_pad * width,))
    planes = raw.reshape(count_pad, width).T
    return pl.pallas_call(
        functools.partial(_fused_plain_kernel, width),
        out_shape=jax.ShapeDtypeStruct((count_pad, width // 4), jnp.uint32),
        grid=(count_pad // _FUSED_TILE,),
        in_specs=[
            pl.BlockSpec((width, _FUSED_TILE), lambda t: (0, t)),
            pl.BlockSpec((1, 1), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_FUSED_TILE, width // 4),
                               lambda t: (t, 0)),
        interpret=interpret,
    )(planes, nv.reshape(1, 1))


def fused_plain_words(buf_dev, vbase, n_valid, *, width: int,
                      count_pad: int, interpret: "bool | None" = None):
    """Fused PLAIN fixed-width decode: staged value bytes at ``vbase`` ->
    finished little-endian u32 words (``count_pad`` x ``width//4``), tail
    past ``n_valid`` zeroed — decode and validity in ONE device pass.

    ``count_pad`` must come from :func:`fused_count_pad`.  Traced x64-free
    (Mosaic refuses i64 grid index maps — see unpack_bp_groups); callers
    bitcast the words to their value dtype under their own x64 scope.
    """
    if width not in (4, 8):
        raise ValueError(f"fused plain supports widths 4/8, got {width}")
    if count_pad % _FUSED_TILE:
        raise ValueError(f"count_pad {count_pad} not a multiple of "
                         f"{_FUSED_TILE}")
    interpret = resolve_interpret(interpret)
    if isinstance(vbase, (int, np.integer)):
        vbase = np.int32(vbase)
    if isinstance(n_valid, (int, np.integer)):
        n_valid = np.int32(n_valid)
    from .jax_kernels import enable_x64

    with enable_x64(False), jax.named_scope("tpq.fused"):
        return _fused_plain_jit(buf_dev, vbase, n_valid, width=width,
                                count_pad=count_pad,
                                interpret=bool(interpret))
