"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s.  A kind missing from the table is an
error, never a default: a roofline share against a guessed peak means
nothing.  The table is the benchmark's own, so a change to the program
cannot move the yardstick.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {  # TPU v5e
        "bf16_flops": 197e12,  # FLOP/s
        "int8_ops": 393e12,  # OP/s
        "hbm_bw": 819e9,  # B/s
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
