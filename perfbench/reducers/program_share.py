"""Share (%) of the device's busy time, over the whole traced ``fit()``
call, that lies inside the executions of one of the program's compiled
modules (``evaluate``: the eval epoch that closes each ``fit()``). Read
from the device's module lane, not from the host's clock: on a device-bound
cell the host runs ahead of the device, and the wall time inside
``evaluate()`` is mostly the wait for the steps queued before it."""


def reduce(ctx, program):
    module = ctx["programs"].get(program)
    if not module:
        return None
    share = ctx["capture"].module_share(module)
    return None if share is None else 100.0 * share
