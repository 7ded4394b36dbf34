"""The jax API surface this codebase shares across modules, in one place.

The training code uses ``jax.shard_map`` (keyword ``check_vma``, manual
axes named via ``axis_names``) through :func:`shard_map`, whose one
convenience is ``axis_names=None`` meaning "manual over every mesh axis".

This module must import nothing from the rest of the package (it is the
first thing ``parallel/__init__`` pulls in).
"""

from __future__ import annotations

from jax import shard_map as _shard_map
from jax.lax import axis_size as axis_size  # noqa: F401
from jax.lax import pcast as pcast  # noqa: F401


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` names the axes the body is manual
    over (None = all of them), ``check_vma`` toggles the varying-manual-
    axes check."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_vma, **kw)
