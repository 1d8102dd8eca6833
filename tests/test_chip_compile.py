"""The main path's device kernels compile for a TPU v5e (no chip needed).

Each case lowers one kernel at the shapes a 1M-row lineitem row group gives
it and compiles it against a DESCRIBED ``v5e:2x2`` topology: the TPU
compiler is installed here, so what the chip's compiler would refuse (a
gather Mosaic cannot lower, a VMEM overrun, an unaligned slice) fails here
first, at no chip time.  Pallas cases must produce a ``tpu_custom_call``;
XLA cases need only compile.  A compile that passes is not a chip run.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import every test file), and the persistent compilation cache is off
around the compiles — an entry written without a chip cannot be read back.
"""

import functools

import pytest

import jax
import jax.numpy as jnp

from tpu_parquet import device_reader as dr
from tpu_parquet import pallas_kernels as pk
from tpu_parquet.jax_kernels import enable_x64

ROWS = 1 << 20          # one bucketed 1M-row group
BUF = 64 << 20          # a staged row-group arena
GROUPS = ROWS // 8      # its bit-packed indices, 8-value groups


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_unpack_jit = jax.jit(pk._unpack_call,
                      static_argnames=("width", "groups", "interpret"))


def _unpack(width, s):
    return (_unpack_jit, (_sds((width, GROUPS), jnp.uint8, s),),
            dict(width=width, groups=GROUPS, interpret=False), False)


def _bp_groups(width, s):
    return (pk._bp_groups_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int32, s)),
            dict(width=width, groups_pad=pk.bp_groups_pad(GROUPS),
                 interpret=False), False)


def _fused_plain(width, s):
    return (pk._fused_plain_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int32, s),
             _sds((), jnp.int32, s)),
            dict(width=width, count_pad=pk.fused_count_pad(ROWS),
                 interpret=False), False)


def _narrow(k, dtype, s):
    return (dr._plain_narrow_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int64, s),
             _sds((), jnp.dtype(dtype), s)),
            dict(k=k, dtype=dtype, count=ROWS), True)


def _snappy_narrow(k, dtype, s):
    # the unfused narrow+snappy chain: op-table resolve by pointer
    # doubling, byte gather, widen/re-bias (every narrow_snappy stream)
    return (dr._snappy_narrow_staged_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int64, s),
             _sds((), jnp.dtype(dtype), s)),
            dict(n_ops=1 << 14, out_pad=ROWS * k, iters=8, k=k, dtype=dtype,
                 count=ROWS), True)


def _snappy_plain(dtype, s):
    # device_snappy: the file's own snappy pages decompressed on device
    return (dr._snappy_plain_staged_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int64, s)),
            dict(n_ops=1 << 14, out_pad=ROWS * 8, iters=8, dtype=dtype,
                 count=ROWS, n_pages=64), True)


def _delta(bits, s):
    # DELTA_BINARY_PACKED pages (lineitem's order key and dates)
    return (dr._delta_pages_staged_jit,
            (_sds((BUF,), jnp.uint8, s), _sds((), jnp.int64, s)),
            dict(values_per_mini=32, mb=4, count=1 << 14, bits=bits,
                 max_width=bits, total=ROWS, n_pages=64, m_max=512), True)


CASES = {
    "unpack_w1": functools.partial(_unpack, 1),
    "unpack_w13": functools.partial(_unpack, 13),
    "unpack_w32": functools.partial(_unpack, 32),
    "fused_plain_w4": functools.partial(_fused_plain, 4),
    "fused_plain_w8": functools.partial(_fused_plain, 8),
    "bp_groups_w7": functools.partial(_bp_groups, 7),
    "bp_groups_w20": functools.partial(_bp_groups, 20),
    "narrow_k3_int64": functools.partial(_narrow, 3, "int64"),
    "snappy_narrow_k2_int32": functools.partial(_snappy_narrow, 2, "int32"),
    "snappy_narrow_k3_int64": functools.partial(_snappy_narrow, 3, "int64"),
    "snappy_plain_float64": functools.partial(_snappy_plain, "float64"),
    "delta_int64": functools.partial(_delta, 64),
    "delta_int32": functools.partial(_delta, 32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args, static, x64 = CASES[case](one_chip)
    # Pallas kernels trace x64-free (Mosaic refuses i64 grid index maps);
    # the staged XLA decoders run under the reader's scoped x64
    with enable_x64(x64):
        compiled = fn.lower(*args, **static).compile()
    text = compiled.as_text()
    if not x64:
        assert "tpu_custom_call" in text, case
