"""Idle share (%) of the device between the first step program's start and
the last one's end: 1 - busy / span, mean over chips. The closing
``evaluate()`` runs after the last step and so lies outside. Where the
capture lost step programs (``Capture.steps_held``) they ran inside that
span and left no op in it: their time (``Capture.lost_step_us``) is taken
out of the span."""


def reduce(ctx):
    capture = ctx["capture"]
    idle = capture.step_idle()
    if idle is None:
        return None
    lost_us = capture.lost_step_us(ctx["steps"])
    if not lost_us:
        return 100.0 * idle["idle_frac"]
    span = idle["span_us"] - lost_us
    if span <= 0:
        return None
    return 100.0 * max(span - idle["busy_us"], 0.0) / span
