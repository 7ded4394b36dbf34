"""Roofline share (%) of one kernel: the time the chip's peaks allow for
the work the kernel is there to do in one step, ``max(flops / peak bf16
FLOP/s, bytes / peak HBM bytes/s)``, over the device time per step of the
ops whose name or path matches ``pattern`` (self times, mean over chips,
over the steps as ``device_ms_per_step`` counts them). ``flops_per_step``
and ``bytes_per_step`` count the work and not an implementation (each
operand read once, each result written once, no recomputation): the metric's
file carries them, and a test holds them to the count functions of the
configuration's family file. ``None`` where no op matches (a program that
does not call the kernel) or no peak is tabulated (a rehearsal off the
chip)."""

from perfbench.peaks import peak
from perfbench.reducers.path_scope_share import named_us


def reduce(ctx, pattern, flops_per_step, bytes_per_step):
    capture = ctx["capture"]
    if not (ctx["peak_flops"] and ctx["steps"]):
        return None
    per_plane = [hit for hit, _ in named_us(capture, pattern) if hit]
    if not per_plane:
        return None
    import jax

    bandwidth = peak(jax.devices()[0].device_kind, "hbm_bytes_per_s")
    seconds = (sum(per_plane) / len(per_plane) / 1e6
               / capture.steps_held(ctx["steps"]))
    allowed = max(flops_per_step / ctx["peak_flops"],
                  bytes_per_step / bandwidth)
    return 100.0 * allowed / seconds
