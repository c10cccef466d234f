"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
interchip interconnect. ``bench.py``'s ``_CHIP_TABLE`` holds the same two
numbers (the copy here is the yardstick's; see PERF.md, Open questions).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}
# the same chip under the name newer runtimes report
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; the table "
            f"in benchmarks/harness/peaks.py has {sorted(PEAKS)}"
        ) from None
