"""Seeded data generators shared by ``bench.py`` and ``chip_smoke.py``.

``gen_lineitem16`` writes TPC-H lineitem (all 16 columns) the way the
bench's headline config lays it out: SNAPPY pages with CRCs, one row group
per ``rows_per_group`` rows, DELTA_BINARY_PACKED for the order key and the
three date columns, dictionary-encoded strings and PLAIN doubles.
"""

from __future__ import annotations

import numpy as np

from .column import ByteArrayData, ColumnData
from .format import (
    CompressionCodec, ConvertedType, Encoding, FieldRepetitionType as FRT,
    LogicalType, StringType, Type,
)
from .schema.core import ColumnParameters, build_schema, data_column

# TPC-H scale factor 1: the lineitem row count of the published generator
LINEITEM_SF1_ROWS = 6_001_215


def writer(path, schema, **kw):
    """A ``FileWriter`` with the bench defaults: SNAPPY, 128 MiB groups, and
    a CRC on every page (the default-on ``validate="crc"`` read tier must
    exercise on every read of a generated file)."""
    from .writer import FileWriter

    kw.setdefault("codec", CompressionCodec.SNAPPY)
    kw.setdefault("row_group_size", 128 << 20)
    kw.setdefault("write_crc", True)
    return FileWriter(path, schema, **kw)


def pool_col(idx, pool) -> ColumnData:
    """ColumnData of ``pool[idx]``."""
    lens = np.array([len(pool[i]) for i in range(len(pool))])[idx]
    offs = np.zeros(len(idx) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    heap = np.frombuffer(b"".join(pool[i] for i in idx), dtype=np.uint8).copy()
    return ColumnData(values=ByteArrayData(offsets=offs, heap=heap))


def strings_col(rng, n, pool) -> ColumnData:
    return pool_col(rng.integers(0, len(pool), n), pool)


def lineitem16_schema():
    S = lambda: ColumnParameters(logical_type=LogicalType(STRING=StringType()),  # noqa: E731
                                 converted_type=ConvertedType.UTF8)
    return build_schema([
        data_column("l_orderkey", Type.INT64, FRT.REQUIRED),
        data_column("l_partkey", Type.INT64, FRT.REQUIRED),
        data_column("l_suppkey", Type.INT64, FRT.REQUIRED),
        data_column("l_linenumber", Type.INT32, FRT.REQUIRED),
        data_column("l_quantity", Type.INT64, FRT.REQUIRED),
        data_column("l_extendedprice", Type.DOUBLE, FRT.REQUIRED),
        data_column("l_discount", Type.DOUBLE, FRT.REQUIRED),
        data_column("l_tax", Type.DOUBLE, FRT.REQUIRED),
        data_column("l_returnflag", Type.BYTE_ARRAY, FRT.REQUIRED, S()),
        data_column("l_linestatus", Type.BYTE_ARRAY, FRT.REQUIRED, S()),
        data_column("l_shipdate", Type.INT32, FRT.REQUIRED),
        data_column("l_commitdate", Type.INT32, FRT.REQUIRED),
        data_column("l_receiptdate", Type.INT32, FRT.REQUIRED),
        data_column("l_shipinstruct", Type.BYTE_ARRAY, FRT.REQUIRED, S()),
        data_column("l_shipmode", Type.BYTE_ARRAY, FRT.REQUIRED, S()),
        data_column("l_comment", Type.BYTE_ARRAY, FRT.REQUIRED, S()),
    ])


def gen_lineitem16(path, rows, rows_per_group=1_000_000, seed=4,
                   key_start=0):
    """Write ``rows`` lineitem rows from ``seed``; ``key_start`` offsets the
    order keys so several part files of one table keep keys disjoint."""
    rng = np.random.default_rng(seed)
    flags = [b"A", b"N", b"R"]
    status = [b"F", b"O"]
    instr = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"]
    modes = [b"AIR", b"FOB", b"MAIL", b"RAIL", b"REG AIR", b"SHIP", b"TRUCK"]
    words = [f"word{i}".encode() for i in range(64)]
    # l_comment: free-text-ish plain strings (the host-bound column)
    comment_pool = [b" ".join(words[j % 64] for j in range(i, i + 5))
                    for i in range(256)]
    with writer(
        path, lineitem16_schema(), use_dictionary=True,
        column_encodings={"l_orderkey": Encoding.DELTA_BINARY_PACKED,
                          "l_shipdate": Encoding.DELTA_BINARY_PACKED,
                          "l_commitdate": Encoding.DELTA_BINARY_PACKED,
                          "l_receiptdate": Encoding.DELTA_BINARY_PACKED},
    ) as w:
        key = key_start
        for lo in range(0, rows, rows_per_group):
            n = min(rows_per_group, rows - lo)
            keys = key + np.cumsum(rng.integers(1, 5, n))
            key = int(keys[-1])
            w.write_columns({
                "l_orderkey": keys.astype(np.int64),
                "l_partkey": rng.integers(1, 200_000, n),
                "l_suppkey": rng.integers(1, 10_000, n),
                "l_linenumber": rng.integers(1, 8, n).astype(np.int32),
                "l_quantity": rng.integers(1, 51, n),
                "l_extendedprice": rng.uniform(900, 105_000, n),
                "l_discount": rng.uniform(0, 0.1, n).round(2),
                "l_tax": rng.uniform(0, 0.08, n).round(2),
                "l_returnflag": strings_col(rng, n, flags),
                "l_linestatus": strings_col(rng, n, status),
                "l_shipdate": (8035 + rng.integers(0, 2526, n)).astype(np.int32),
                "l_commitdate": (8035 + rng.integers(0, 2526, n)).astype(np.int32),
                "l_receiptdate": (8035 + rng.integers(0, 2526, n)).astype(np.int32),
                "l_shipinstruct": strings_col(rng, n, instr),
                "l_shipmode": strings_col(rng, n, modes),
                "l_comment": strings_col(rng, n, comment_pool),
            })
            # one row group per chunk of rows_per_group rows
            w.flush_row_group()
