"""Device time (ms) of one train step: self times of the ops that ran
inside the step program's executions, mean over chips, over the steps: the
steps the traced calls took, or the executions the capture holds where it
lost some (``Capture.steps_held``)."""


def reduce(ctx):
    us = ctx["capture"].step_device_us()
    if not us or not ctx["steps"]:
        return None
    return us / 1e3 / ctx["capture"].steps_held(ctx["steps"])
