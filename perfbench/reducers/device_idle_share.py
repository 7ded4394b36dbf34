"""Idle share (%) of the device between the first step program's start and
the last one's end: 1 - busy / span, mean over chips. The closing
``evaluate()`` runs after the last step and so lies outside."""


def reduce(ctx):
    idle = ctx["capture"].step_idle()
    return None if idle is None else 100.0 * idle["idle_frac"]
