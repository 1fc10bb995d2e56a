"""The chip's peaks, and the least work a query asks of it. Kept with
the benchmark so that no PR that claims a gain can move the yardstick."""
from __future__ import annotations

#: keyed by device_kind as JAX reports it. Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak known for device_kind {device_kind!r}: "
                       f"add it to peaks.PEAKS with its source")
    return PEAKS[device_kind]


def staged_width(column: dict, cardinality: int) -> int:
    """Bytes a row of this column takes once staged on the device: a
    dictionary column its ids at the narrowest of i8/i16/i32 that holds
    the cardinality, a raw INT column 4."""
    if not column["dictionary"]:
        return 4
    return 1 if cardinality <= 127 else 2 if cardinality <= 32767 else 4


def template_columns(template: dict) -> set:
    """Every column the query has to read, once."""
    cols = {w[0] for w in template["where"]} | set(template["group_by"])
    for agg in template["select"]:
        cols |= set(agg[1:])
    return cols


def bytes_per_query(config: dict, cardinalities: dict, template: dict,
                    rows: int) -> int:
    """The bytes the algorithm needs: each column the query reads, once,
    at its staged width, times the rows of the table — whatever kernel
    does the reading."""
    by_name = {c["name"]: c for c in config["columns"]}
    return rows * sum(staged_width(by_name[c], cardinalities[c])
                      for c in template_columns(template))


def least_seconds(device_kind: str, nbytes: float, flops: float = 0.0):
    """(least time the chip could take, which peak bounds it)."""
    p = peak(device_kind)
    by_bytes = nbytes / p["hbm_bytes_per_s"]
    by_flops = flops / p["bf16_flops_per_s"]
    return max(by_bytes, by_flops), \
        "hbm" if by_bytes >= by_flops else "flops"
