"""Published peaks of the devices the benchmark may run on.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
per chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect. (The same numbers
stand in ``paddle_tpu/monitor`` as ``DEVICE_PEAKS``; this copy is the
yardstick's.) A device that is not in the table is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
    "TPU v5e": {"flops_bf16": 197e12, "ops_int8": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "ici_bits_per_s": 1600e9},
}


def for_device(kind):
    try:
        return PEAKS[kind]
    except KeyError:
        raise LookupError(
            f"device kind {kind!r} has no published peaks in "
            f"benchmark/lib/peaks.py (known: {sorted(PEAKS)})") from None


def share(needed, taken, what):
    """``needed / taken`` as a percentage of a peak or roofline. Over
    100% means the work was counted too high or the time left part of it
    out: that is a fault of the yardstick, so it raises and never prints."""
    if taken <= 0:
        return None
    pct = 100.0 * needed / taken
    if pct > 100.0:
        raise ArithmeticError(
            f"{what}: {pct:.2f}% of its bound — work counted too high or "
            "time counted too low")
    return pct
