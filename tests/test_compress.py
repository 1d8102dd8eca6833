"""Codec registry + snappy (native C++ and pure-Python) tests.

Cross-validated against pyarrow's canonical snappy/zstd/gzip codecs, mirroring the
role of compress_test.go in the reference.
"""

import os
import random

import pytest

from tpu_parquet import native
from tpu_parquet.compress import (
    BlockCompressor,
    CompressionError,
    SnappyCompressor,
    _py_snappy_compress,
    _py_snappy_decompress,
    compress_block,
    decompress_block,
    get_codec,
    register_codec,
    registered_codecs,
)
from tpu_parquet.format import CompressionCodec

pa = pytest.importorskip("pyarrow")


def _corpora():
    rng = random.Random(42)
    return [
        b"",
        b"a",
        b"abcd" * 3,
        b"hello world, hello world, hello world!" * 100,
        bytes(rng.randrange(256) for _ in range(10_000)),  # incompressible
        bytes(rng.randrange(4) for _ in range(100_000)),   # compressible
        b"\x00" * 200_000,                                  # highly repetitive
        os.urandom(70_000),                                 # > one 64K block
        b"x" * 65536 + b"y" * 65536 + os.urandom(100),
    ]


@pytest.mark.parametrize(
    "codec",
    [CompressionCodec.UNCOMPRESSED, CompressionCodec.SNAPPY,
     CompressionCodec.GZIP, CompressionCodec.ZSTD],
)
def test_registry_roundtrip(codec):
    from conftest import require_codec

    require_codec(codec)
    for data in _corpora():
        comp = compress_block(data, codec)
        # decompress output is bytes-LIKE (the zero-copy snappy path returns
        # a uint8 array); content equality is the contract
        assert bytes(decompress_block(comp, codec, len(data))) == data


def test_snappy_native_available():
    # The image has g++; the native codec must actually build and load.
    assert native.available(), "native snappy failed to build"


def test_native_stamp_covers_host_target(monkeypatch):
    """The library's stamp hashes the host's -march=native target with the
    sources: a checkout copied to a machine with another CPU builds its own
    library instead of loading one built for this host."""
    import subprocess

    real = subprocess.run
    base = native._stamp()
    assert base == native._stamp()

    def other_cpu(cmd, **kw):
        out = real(cmd, **kw)
        if "--help=target" in cmd:
            out.stdout += b"  -mavx512f  [enabled]\n"
        return out

    monkeypatch.setattr(native.subprocess, "run", other_cpu)
    assert native._stamp() != base


def test_native_snappy_decodes_pyarrow_output():
    for data in _corpora():
        comp = pa.compress(data, codec="snappy", asbytes=True)
        assert bytes(native.snappy_decompress(comp)) == data


def test_pyarrow_decodes_native_snappy_output():
    for data in _corpora():
        comp = native.snappy_compress(data)
        out = pa.decompress(
            comp, decompressed_size=len(data), codec="snappy", asbytes=True
        )
        assert out == data


def test_py_snappy_fallback_matches_native():
    for data in _corpora():
        comp = pa.compress(data, codec="snappy", asbytes=True)
        assert _py_snappy_decompress(comp) == data
        assert _py_snappy_decompress(_py_snappy_compress(data)) == data
        # fallback output must be readable by the canonical codec too
        assert pa.decompress(
            _py_snappy_compress(data), decompressed_size=len(data),
            codec="snappy", asbytes=True,
        ) == data


def test_snappy_compression_actually_compresses():
    data = b"the quick brown fox " * 5000
    comp = native.snappy_compress(data)
    assert len(comp) < len(data) // 4


def test_declared_size_mismatch_raises():
    comp = compress_block(b"hello world", CompressionCodec.SNAPPY)
    with pytest.raises(CompressionError):
        decompress_block(comp, CompressionCodec.SNAPPY, 5)
    with pytest.raises(CompressionError):
        decompress_block(b"hello", CompressionCodec.UNCOMPRESSED, 4)


def test_malformed_snappy_raises():
    bad_inputs = [
        b"\xff\xff\xff\xff\xff\xff",   # huge/invalid varint header
        b"\x05\xfc",                    # copy4 with no offset bytes
        b"\x0a\x01\x02",                # declared 10 bytes, tiny literal
        b"\x05\x09\x00\x10",            # copy with offset beyond output
    ]
    snappy = SnappyCompressor()
    for b in bad_inputs:
        with pytest.raises(CompressionError):
            snappy.decompress_block(b, 10)
        with pytest.raises(CompressionError):
            _py_snappy_decompress(b)


def test_unsupported_codec_raises():
    with pytest.raises(CompressionError):
        get_codec(CompressionCodec.LZO)


def test_pluggable_registry():
    class XorCodec(BlockCompressor):
        def compress_block(self, block):
            return bytes(b ^ 0x5A for b in block)

        def decompress_block(self, block, uncompressed_size):
            return bytes(b ^ 0x5A for b in block)

    register_codec(CompressionCodec.LZ4_RAW, XorCodec())
    try:
        data = b"pluggable codecs work"
        comp = compress_block(data, CompressionCodec.LZ4_RAW)
        assert decompress_block(comp, CompressionCodec.LZ4_RAW, len(data)) == data
        assert int(CompressionCodec.LZ4_RAW) in registered_codecs()
    finally:
        from tpu_parquet import compress as _c

        with _c._registry_lock:
            _c._registry.pop(int(CompressionCodec.LZ4_RAW), None)


def test_gzip_roundtrip_with_pyarrow():
    data = b"gzip interop " * 1000
    comp = compress_block(data, CompressionCodec.GZIP)
    assert pa.decompress(comp, decompressed_size=len(data), codec="gzip",
                         asbytes=True) == data
