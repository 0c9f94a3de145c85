"""Published peaks of one chip, keyed by ``device_kind`` (copied from the
repository's ``analysis/hw.py``; source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).

Every roofline and MFU share uses the chip's highest compute peak, so a
later implementation that moves work to a faster datapath cannot read above
100%. A device kind that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    # kind prefix: (highest compute peak ops/s, HBM bytes/s, HBM bytes)
    "tpu v5 lite": (393e12, 819e9, 16e9),
    "tpu v5e": (393e12, 819e9, 16e9),
}


def peaks(device_kind: str) -> dict:
    kind = device_kind.lower()
    for prefix, (ops, bw, mem) in PEAKS.items():
        if kind.startswith(prefix):
            return {"ops_per_s": ops, "bytes_per_s": bw, "hbm_bytes": mem}
    raise ValueError(f"no published peaks for device kind {device_kind!r}")
