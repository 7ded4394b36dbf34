"""Device time (ms) of one train step: self times of the ops that ran
inside the step program's executions, mean over chips, over the steps."""


def reduce(ctx):
    us = ctx["capture"].step_device_us()
    if not us or not ctx["steps"]:
        return None
    return us / 1e3 / ctx["steps"]
