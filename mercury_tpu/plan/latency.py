"""Analytic collective-latency model (stdlib-only).

Classic ring-algorithm cost model: a ring allreduce over ``W`` devices
moves ``2 * (W - 1) / W * bytes`` across each link (reduce-scatter +
all-gather phases), an all-gather or reduce-scatter alone moves
``(W - 1) / W * bytes``. Divided by the per-link bandwidth of the device
kind this gives a latency estimate in seconds — the (c) term of the
auto-planner's score (DESIGN.md §16).

This is the canonical implementation; ``mercury_tpu.parallel.collectives``
re-exports it next to the executable collectives so the cost model and
the collectives it prices live on one import surface. It stays here, in
the jax-free ``plan`` package, so the planner (and CI's jax-free leg)
can import it without jax installed.

Bandwidths are per-link, full-duplex, in bytes/second, keyed by device-kind
prefix exactly like ``obs.accounting.PEAK_FLOPS`` keys peak FLOPs: the
longest matching prefix of ``jax.devices()[0].device_kind.lower()`` wins.
TPU numbers are the published ICI per-link figures; the ``cpu`` entry is a
deliberately modest shared-memory figure so CPU-mesh plan rankings still
penalize collective-heavy plans instead of treating communication as free.
A device kind that matches no prefix is an error, not the ``cpu`` figure.
"""

from __future__ import annotations

from typing import Dict

#: Per-link interconnect bandwidth (bytes/second) by device-kind prefix.
#: Longest-prefix match over the lowercased device kind; "cpu" is the
#: host-platform fallback used by the CPU mesh and the jax-free planner.
LINK_BANDWIDTH_BYTES_PER_S: Dict[str, float] = {
    "tpu v6": 448e9,   # Trillium ICI per link
    "tpu v5p": 200e9,
    "tpu v5 lite": 100e9,
    "tpu v5e": 100e9,
    "tpu v4": 100e9,
    "tpu v3": 70e9,
    "tpu v2": 62.5e9,
    "cpu": 10e9,       # shared-memory "link" stand-in for the host mesh
}


def link_bandwidth(device_kind: str) -> float:
    """Per-link bandwidth (bytes/s) for a device kind, longest-prefix
    match. No kind at all means the host mesh (``cpu``); a kind that is
    not tabulated raises — pricing an unknown accelerator's collectives
    at the CPU figure would rank its plans on a made-up number."""
    kind = (device_kind or "cpu").lower()
    matches = [p for p in LINK_BANDWIDTH_BYTES_PER_S if kind.startswith(p)]
    if not matches:
        raise ValueError(
            f"no link bandwidth tabulated for device kind {device_kind!r}; "
            "add it to mercury_tpu.plan.latency.LINK_BANDWIDTH_BYTES_PER_S"
        )
    return LINK_BANDWIDTH_BYTES_PER_S[max(matches, key=len)]


def ring_allreduce_cost_s(payload_bytes: float, axis_size: int,
                          device_kind: str = "cpu") -> float:
    """Ring allreduce latency: 2·(W−1)/W · bytes / link_bw (both phases)."""
    if axis_size <= 1 or payload_bytes <= 0:
        return 0.0
    w = float(axis_size)
    return 2.0 * (w - 1.0) / w * float(payload_bytes) / link_bandwidth(device_kind)


def all_gather_cost_s(payload_bytes: float, axis_size: int,
                      device_kind: str = "cpu") -> float:
    """Ring all-gather latency: (W−1)/W · bytes / link_bw."""
    if axis_size <= 1 or payload_bytes <= 0:
        return 0.0
    w = float(axis_size)
    return (w - 1.0) / w * float(payload_bytes) / link_bandwidth(device_kind)


def reduce_scatter_cost_s(payload_bytes: float, axis_size: int,
                          device_kind: str = "cpu") -> float:
    """Ring reduce-scatter latency — same wire traffic as the all-gather."""
    return all_gather_cost_s(payload_bytes, axis_size, device_kind)


_COLLECTIVE_COSTS = {
    "all-reduce": ring_allreduce_cost_s,
    "all-gather": all_gather_cost_s,
    "reduce-scatter": reduce_scatter_cost_s,
}


def collective_cost_s(kind: str, payload_bytes: float, axis_size: int,
                      device_kind: str = "cpu") -> float:
    """Latency of one collective by HLO kind (``all-reduce`` /
    ``all-gather`` / ``reduce-scatter``); unknown kinds are priced as an
    all-gather (single-phase wire traffic) — conservative, never free."""
    fn = _COLLECTIVE_COSTS.get(kind, all_gather_cost_s)
    return fn(payload_bytes, axis_size, device_kind)
