"""Published peaks of the chips the benchmark may run on.

One table, keyed by ``jax.devices()[0].device_kind`` (lower-cased
substring match).  A device that is not listed is an error, never a
default: a utilization against an assumed peak is not a measurement.
The program keeps its own table (``sparknet_tpu.common.TPU_PEAK_FLOPS``);
this copy is the yardstick's, so a change to the program's cannot move a
metric.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
# 1,600 Gbit/s inter-chip interconnect per chip.
_V5E = {
    "bf16_flops": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
}

PEAKS = {
    "tpu v5 lite": _V5E,  # what jax 0.9 / libtpu 0.0.34 reports on this host
    "tpu v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    kind = str(device_kind).lower()
    for key, row in PEAKS.items():
        if key in kind:
            return row
    raise KeyError(
        f"device_kind {device_kind!r} is not in benchmarks/harness/peaks.py "
        f"({sorted(PEAKS)}); add its published peaks with their source")
