"""Pallas bit-unpack kernel vs the NumPy reference (interpret mode on CPU).

The Mosaic kernel itself is exercised on real TPU by bench.py's microbench;
here the same kernel body runs through the Pallas interpreter so CI-style
tests cover the unrolled byte/shift logic for every width, including the
5-byte-span widths (26..32 with nonzero shift) and ragged tail tiles.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_parquet.kernels import bitpack
from tpu_parquet.pallas_kernels import (
    bp_groups_pad, bp_value_index, unpack_bp_groups,
)

RNG = np.random.default_rng(7)


def unpack(packed, width, n):
    """``n`` values through the kernel, back in stream order."""
    gpad = bp_groups_pad(-(-n // 8))
    buf = np.zeros(gpad * width, np.uint8)
    buf[: len(packed)] = packed
    out = unpack_bp_groups(jnp.asarray(buf), 0, width, gpad, interpret=True)
    return np.asarray(out)[bp_value_index(np.arange(n), gpad)]


@pytest.mark.parametrize("width", [1, 3, 7, 8, 13, 17, 25, 26, 31, 32])
def test_unpack_parity(width):
    n = 5000
    mask = (1 << width) - 1
    vals = RNG.integers(0, 1 << 32, n, dtype=np.uint64) & mask
    packed = np.frombuffer(bitpack.pack(vals, width), np.uint8)
    got = unpack(packed, width, n)
    want = bitpack.unpack(packed, width, n).astype(np.uint32)
    np.testing.assert_array_equal(got, want)


def test_unpack_tile_boundary():
    # count exactly at and just past the 8192-value tile boundary
    width = 5
    for n in (8192, 8193, 16384 - 1):
        vals = RNG.integers(0, 32, n, dtype=np.uint64)
        packed = np.frombuffer(bitpack.pack(vals, width), np.uint8)
        got = unpack(packed, width, n)
        np.testing.assert_array_equal(
            got, bitpack.unpack(packed, width, n).astype(np.uint32)
        )


def test_unpack_rejects_bad_width():
    with pytest.raises(ValueError):
        unpack_bp_groups(jnp.zeros(8, jnp.uint8), 0, 0, 1024)
    with pytest.raises(ValueError):
        unpack_bp_groups(jnp.zeros(8, jnp.uint8), 0, 33, 1024)
