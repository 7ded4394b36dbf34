"""Throughput and MFU accounting — the ``benchmarks/mfu_sweep.py``
numbers, available live on the log cadence instead of only offline.

- :data:`PEAK_FLOPS` — the tree's ONE per-device-kind peak (bf16)
  FLOP/s table: the live path, ``bench.py``, the offline sweep and the
  auto-planner all read it here.
- :func:`analytic_flops_per_step` — XLA's cost analysis of the LOWERED
  fused step program (a re-trace, never an XLA compile — see the
  function docstring).
- :class:`ThroughputMeter` — steps/s, examples/s, and the MFU estimate
  between log ticks, as host-side floats ready to merge into the metric
  record.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

#: Peak dense bf16 FLOP/s of one chip, by ``device_kind`` prefix (first
#: match wins, so the longer "TPU v5 lite" precedes "TPU v5"). Source:
#: Google Cloud TPU documentation, the per-generation system-architecture
#: pages ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e").
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e, as jax reports it
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v6": 918e12,        # v6e (Trillium)
}


def peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Peak FLOP/s for a device kind. None on the CPU (jax reports
    ``device_kind == "cpu"``; utilization is "not measured" there) and
    for no device at all; an accelerator kind that is not tabulated is
    an error — a silent default would turn every MFU into 0.0."""
    if not device_kind or device_kind.lower().startswith("cpu"):
        return None
    for prefix, peak in PEAK_FLOPS.items():
        if device_kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no peak FLOP/s tabulated for device kind {device_kind!r}; add "
        "it to mercury_tpu.obs.accounting.PEAK_FLOPS with its source"
    )


def analytic_flops_per_step(step_fn, *args, scan_steps: int = 1
                            ) -> Optional[float]:
    """FLOPs of ONE step of the jitted ``step_fn`` per XLA's cost
    analysis (divided by ``scan_steps`` for chunked programs). Returns
    None when the backend offers no cost model.

    Analyzes the LOWERED module, never ``.compile()``: the AOT compile
    path does not share the jit executable cache, so asking the compiled
    program would silently rebuild the entire fused step (minutes of
    XLA time for a ResNet-scale scan program on CPU) just to read one
    number. Unoptimized-HLO FLOPs are what the MFU estimate needs."""
    try:
        cost = step_fn.lower(*args).cost_analysis()
        flops = float(cost.get("flops", 0.0))
    except Exception:
        return None
    if flops <= 0.0:
        return None
    return flops / max(scan_steps, 1)


class ThroughputMeter:
    """Rolling steps/s, examples/s, and MFU between log ticks.

    ``tick(step)`` returns the ``perf/*`` scalars for the interval since
    the previous tick — host floats, no device work. MFU is analytic
    FLOPs × steps/s against the device's tabulated peak; on the CPU (no
    peak) or before the FLOPs are known it reports 0.0 and the manifest's
    ``peak_flops: null`` marks it as not measured."""

    def __init__(self, examples_per_step: float,
                 flops_per_step: Optional[float] = None,
                 device_kind: Optional[str] = None) -> None:
        if device_kind is None:
            import jax

            device_kind = jax.devices()[0].device_kind
        self.examples_per_step = float(examples_per_step)
        self.flops_per_step = flops_per_step
        self.peak = peak_flops(device_kind)
        self._last_step: Optional[int] = None
        self._last_t = 0.0

    def reset(self, step: int, now: Optional[float] = None) -> None:
        self._last_step = int(step)
        self._last_t = time.perf_counter() if now is None else now

    def tick(self, step: int, now: Optional[float] = None
             ) -> Dict[str, float]:
        now = time.perf_counter() if now is None else now
        if self._last_step is None:
            self.reset(step, now)
            return {}
        dt = max(now - self._last_t, 1e-9)
        steps = max(step - self._last_step, 1)
        self._last_step, self._last_t = int(step), now
        steps_per_s = steps / dt
        out = {
            "perf/steps_per_s": steps_per_s,
            "perf/examples_per_s": steps_per_s * self.examples_per_step,
            "time/step": dt / steps,
            "time/images_per_sec": steps_per_s * self.examples_per_step,
        }
        if self.flops_per_step:
            out["perf/flops_per_step"] = self.flops_per_step
        mfu = 0.0
        if self.flops_per_step and self.peak:
            mfu = self.flops_per_step * steps_per_s / self.peak
        out["perf/mfu"] = mfu
        return out
