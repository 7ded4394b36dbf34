"""A partition of the train step's device time (the ops and the per-plane
mean of ``path_scope_share``) over the leaves of a causal decoder's step:
every op goes to ONE of ``LEAVES`` or to ``UNSCOPED``, so the shares sum to
100 and what no scope names is a number of its own. In order of precedence:

1. a kernel, told by its op's name wherever it lies (``KERNELS``, as
   ``kernel_roofline_share`` tells them): splash-attention's calls are
   ``attention_kernel``, the head's kernel over vocabulary blocks belongs to
   ``mercury_lm_head``, and the grouped products of the routed experts to
   ``mercury_moe`` (XLA makes ``lax.ragged_dot`` a kernel of its own,
   ``ragged-dot-none.N``, whose path is that name and holds no scope);
2. the INNERMOST of ``LEAVES`` on the op's path (a norm inside
   ``mercury_moe`` is ``mercury_norm``'s; ``mercury_attention`` keeps what
   lies under it and under no inner leaf and is no kernel: the glue);
3. ``UNSCOPED``.

A fusion is one op and is booked whole to the one path the compiler gives
it (its root's, or its product's where it holds one).
``reduce(ctx, leaf)`` is a leaf's share (%); ``None`` where the leaf holds
no op (a program that does not emit the scope) and, for every leaf and
``UNSCOPED`` alike, where no op of the step lies under any of
``MODEL_LEAVES`` (a program without a decoder, whose step the seven scopes
of ``leaf_scope_share`` split already). ``reduce(ctx,
holds=[...])`` is the share of the ops whose path holds every one of
``holds`` (the train pass's recomputed forward: ``mercury_train`` and jax's
``rematted_computation``), ``None`` where none does."""

import re

from perfbench import trace_reduce

UNSCOPED = "unscoped"
#: (what an op's name or path holds, the leaf it then belongs to).
KERNELS = (("splash_mqa", "attention_kernel"),
           ("mercury_head_nll", "mercury_lm_head"),
           ("ragged-dot", "mercury_moe"))
#: The step's own scopes that hold no model code.
STEP_LEAVES = ("mercury_pool_ingest", "mercury_draw", "mercury_optimizer",
               "mercury_grad_sync")
#: The model's (``models/decoder.py``, ``models/moe.py``, the loss seam);
#: ``mercury_attention`` and ``mercury_moe`` are what is left of them.
MODEL_LEAVES = ("mercury_rows", "mercury_embed", "mercury_norm",
                "mercury_attention_proj", "attention_kernel",
                "mercury_attention", "mercury_moe_route", "mercury_moe_shared",
                "mercury_moe", "mercury_dense_mlp", "mercury_lm_head")
LEAVES = STEP_LEAVES + MODEL_LEAVES

_SCOPE = re.compile(r"mercury_[a-z0-9_]+")


def leaf_of(text):
    """The leaf an op's searchable text puts it under."""
    for held, leaf in KERNELS:
        if held in text:
            return leaf
    named = [scope for scope in _SCOPE.findall(text) if scope in LEAVES]
    return named[-1] if named else UNSCOPED


def _per_plane(capture, key):
    """Per device plane that ran the step: ``{key(an op's searchable text):
    self microseconds of the step's ops}``."""
    planes = []
    for plane in capture.planes:
        us_of = {}
        for event, us in capture._step_ops(plane):
            k = key(trace_reduce._searchable_text(event))
            us_of[k] = us_of.get(k, 0.0) + us
        if sum(us_of.values()):
            planes.append(us_of)
    return planes


def _share(planes, key):
    """``key``'s share (%) of the step, mean over the planes; ``None``
    where it holds nothing."""
    if not any(plane.get(key) for plane in planes):
        return None
    return 100.0 * sum(plane.get(key, 0.0) / sum(plane.values())
                       for plane in planes) / len(planes)


def partition(ctx):
    """``{leaf: microseconds}`` per plane; read once a traced run and kept
    in ``ctx``, as ``timeline.py`` keeps the host lane."""
    if "_model_leaves" not in ctx:
        ctx["_model_leaves"] = _per_plane(ctx["capture"], leaf_of)
    return ctx["_model_leaves"]


def reduce(ctx, leaf=None, holds=()):
    if holds:
        return _share(_per_plane(
            ctx["capture"], lambda text: all(h in text for h in holds)), True)
    planes = partition(ctx)
    if not any(plane.get(m) for plane in planes for m in MODEL_LEAVES):
        return None
    return _share(planes, leaf)
