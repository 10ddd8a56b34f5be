"""The bytes a query's algorithm has to read: for every table it reads, the
rows in the files times the width of each column it reads, at the width the
column has on the device. Kept with the benchmark so that no later PR can
change what a roofline share is a share of."""

from __future__ import annotations

import os

import pyarrow.parquet as pq

# bytes a value occupies on the device where that is not its width in the
# Parquet files: a string is a dictionary code there (money, float64 in the
# files, is a scaled int64: 8 either way; a date is days in int32)
DEVICE_WIDTH = {"string": 4, "large_string": 4}


def width(arrow_type) -> int:
    return DEVICE_WIDTH.get(str(arrow_type)) or arrow_type.bit_width // 8


def query_bytes(spec: dict, data_dir: str) -> int:
    total = 0
    for table, columns in spec["reads"].items():
        base = os.path.join(data_dir, table)
        files = [pq.ParquetFile(os.path.join(base, f))
                 for f in sorted(os.listdir(base)) if f.endswith(".parquet")]
        schema = files[0].schema_arrow
        row = sum(width(schema.field(c).type) for c in columns)
        total += row * sum(f.metadata.num_rows for f in files)
    return total
