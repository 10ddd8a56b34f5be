"""TPC-H ``part``: 200,000 rows a scale factor; the retail price is a
function of the key, as in dbgen."""

import numpy as np
import pyarrow as pa

import datagen as dg

SEED_ID = 2
PRIMARY_KEY = "p_partkey"
CONTAINERS = ["SM CASE", "SM BOX", "MED BAG", "MED BOX", "LG CASE", "LG BOX",
              "JUMBO PACK", "WRAP CASE"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "green", "red", "white", "yellow", "ivory"]
PART_NAMES = [f"{c} {n}" for c in COLORS for n in dg.NOUNS]
PART_TYPES = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2
              for c in TYPE_S3]
MFGRS = [f"Manufacturer#{k}" for k in range(1, 6)]

ARROW_SCHEMA = pa.schema([
    ("p_partkey", pa.int64()), ("p_name", pa.string()),
    ("p_mfgr", pa.string()), ("p_brand", pa.string()),
    ("p_type", pa.string()), ("p_size", pa.int32()),
    ("p_container", pa.string()), ("p_retailprice", pa.float64()),
    ("p_comment", pa.string())])


def program_schema():
    from ballista_tpu import Decimal, Int32, Int64, Utf8, schema

    return schema(
        ("p_partkey", Int64), ("p_name", Utf8), ("p_mfgr", Utf8),
        ("p_brand", Utf8), ("p_type", Utf8), ("p_size", Int32),
        ("p_container", Utf8), ("p_retailprice", Decimal(2)),
        ("p_comment", Utf8))


def rows(scale: float) -> int:
    return max(int(200_000 * scale), 20)


def retail_price(key):
    return (90000 + (key % 20001) + 100 * (key % 1000)) / 100.0


def chunk(rng, lo, hi, scale):
    key = np.arange(lo + 1, hi + 1)
    m = hi - lo
    return {"part": [
        pa.array(key),
        dg.strings(rng.integers(0, len(PART_NAMES), m), PART_NAMES),
        dg.strings(rng.integers(0, len(MFGRS), m), MFGRS),
        dg.strings(rng.integers(0, len(BRANDS), m), BRANDS),
        dg.strings(rng.integers(0, len(PART_TYPES), m), PART_TYPES),
        pa.array(rng.integers(1, 51, m).astype(np.int32)),
        dg.strings(rng.integers(0, len(CONTAINERS), m), CONTAINERS),
        pa.array(retail_price(key)), dg.comments(rng, m)]}
