"""Roofline share (%) of the step PROGRAM, compute-bound reading: the
FLOPs of one step as its shapes give them (``flops.step_program_flops``:
what the program does, scoring pass included, zero-padding taps counted as
the MXU executes them) over the device time of one step (as
``device_ms_per_step`` takes it), over the chip's peak bf16 FLOP/s. The
step is bound by the MXU at these shapes (a ResNet convolution at batch >=
256 has hundreds of FLOPs per byte), so the compute roof is the one that
binds."""


def reduce(ctx):
    us = ctx["capture"].step_device_us()
    if not (us and ctx["steps"] and ctx["step_flops"] and ctx["peak_flops"]):
        return None
    seconds_per_step = us / 1e6 / ctx["capture"].steps_held(ctx["steps"])
    return 100.0 * ctx["step_flops"] / seconds_per_step / ctx["peak_flops"]
