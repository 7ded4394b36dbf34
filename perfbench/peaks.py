"""Peak rates of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud TPU documentation, the system-architecture page of
each generation ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s;
"TPU v5p"; "TPU v4"; "TPU v6e"). A copy of the table in
``mercury_tpu/obs/accounting.py`` — the benchmark keeps its own, so that
no later PR can move a utilization by editing the program. A device that
is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

#: ``device_kind`` prefix -> peak dense bf16 FLOP/s and HBM bytes/s of one
#: chip. First match wins, so "TPU v5 lite" stands before "TPU v5".
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1200e9},
    "TPU v6": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
}


def peak(device_kind: str, what: str = "bf16_flops") -> float:
    for prefix, row in PEAKS.items():
        if device_kind.startswith(prefix):
            return row[what]
    raise ValueError(
        f"no peak tabulated for device kind {device_kind!r}; add it to "
        "perfbench/peaks.py with its source")
