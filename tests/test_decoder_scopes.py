"""The decoder's scopes, read off the traced program on the CPU at the tiny
widths (``chip_smoke.register_tiny_lm``: grouped-query attention under the
softmax rule; ``register_tiny_latent_lm``: latent attention under the
sigmoid rule, a shared expert, a leading dense layer): every heavy equation
of one scored forward and of one ``value_and_grad`` through the loss seam
lies under one of the model's leaves (the ones
``perfbench/reducers/model_leaf_share.py`` partitions the step's device time
by), each leaf shows in the forward and in the backward pass, and what the
backward pass runs again of a checkpointed forward carries jax's
``rematted_computation`` (``train_recompute_share`` reads that mark: a jax
that renames it fails here and does not read 0 there). Scopes are metadata:
nothing here is a measurement."""

import functools

import jax
import jax.numpy as jnp
import pytest

from chip_smoke import register_tiny_latent_lm, register_tiny_lm
from mercury_tpu.data.tokens import zipf_tokens
from mercury_tpu.models import create_model, decoder
from mercury_tpu.sampling.importance import sequence_rows
from perfbench.reducers import model_leaf_share

VOCAB, T = 96, 32
#: name -> (registered model, cut): both mixers, both routing rules.
MODELS = {"grouped-softmax": (register_tiny_lm(), (4, 0, 4)),
          "latent-sigmoid": (register_tiny_latent_lm(), (3, 0, 4, 0, 2))}
#: The model's leaves a decoder of each kind emits off the chip (there the
#: attention itself is the blockwise XLA form under ``mercury_attention``;
#: on the chip it is the kernels, which the reducer tells by their name).
LEAVES = {
    "grouped-softmax": (
        "mercury_embed", "mercury_norm", "mercury_attention_proj",
        "mercury_attention", "mercury_moe_route", "mercury_moe",
        "mercury_lm_head"),
    "latent-sigmoid": (
        "mercury_embed", "mercury_norm", "mercury_attention_proj",
        "mercury_attention", "mercury_moe_route", "mercury_moe_shared",
        "mercury_moe", "mercury_dense_mlp", "mercury_lm_head"),
}
#: What runs under a ``jax.checkpoint`` (a layer, a row's head): all but
#: the embedding.
CHECKPOINTED = {kind: leaves[1:] for kind, leaves in LEAVES.items()}
#: Equations that are work on the device whatever they fuse with.
HEAVY = ("dot_general", "ragged_dot", "ragged_dot_general", "gather",
         "scatter-add", "scatter_add", "sort", "top_k", "cumsum", "argmax",
         "argmin")
REMAT = "rematted_computation"


def _is_heavy(eqn) -> bool:
    name = eqn.primitive.name
    return name in HEAVY or name.startswith("reduce_")


def _equations(jaxpr, outer="", in_rows=False):
    """``(equation, its whole name stack, whether it lies in a row's body)``
    of every equation, sub-jaxprs included: an equation's own name stack is
    relative to the equation that holds its jaxpr, as lowering joins them.
    A row's body is that of ``lax.map`` (a ``scan``): ``row`` in the
    decoder, a row's loss in ``sequence_rows``."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        stack = "/".join(part for part in (outer, own) if part)
        yield eqn, stack, in_rows
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(
                sub, stack, in_rows or eqn.primitive.name == "scan")


@functools.lru_cache(maxsize=None)
def _traced(kind):
    """``(the scored forward's equations, the differentiated pass's)`` of
    one decoder kind: two rows of ``T`` tokens through the model and the
    loss seam, traced once and never run."""
    name, cut = MODELS[kind]
    model = create_model(name, num_classes=VOCAB, cut=cut)
    (x, y), _ = zipf_tokens(VOCAB, T, train_size=2, test_size=0, seed=1)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), x[:1], train=False))["params"]

    def scored(params):
        outputs, _ = model.apply({"params": params}, x, train=True,
                                 mutable=[decoder.MOE_LOAD])
        return sequence_rows(outputs, jnp.asarray(y))

    def trained(params):
        return jax.value_and_grad(
            lambda p: jnp.mean(scored(p)[:, 0]))(params)

    return tuple(list(_equations(jax.make_jaxpr(f)(params).jaxpr))
                 for f in (scored, trained))


def _leaf(stack: str, leaves):
    """The innermost of ``leaves`` on a name stack, or None."""
    leaf = model_leaf_share.leaf_of(stack.lower())
    return leaf if leaf in leaves else None


@pytest.mark.parametrize("which", [0, 1], ids=["scored", "trained"])
@pytest.mark.parametrize("kind", sorted(MODELS))
def test_every_heavy_equation_of_a_row_lies_under_a_leaf(kind, which):
    """Products, gathers, scatter-adds, reductions, sorts and top-k inside
    ``row`` and ``sequence_rows``, forward and backward: none is left to
    the partition's remainder."""
    heavy = [(eqn.primitive.name, stack)
             for eqn, stack, in_rows in _traced(kind)[which]
             if in_rows and _is_heavy(eqn)]
    assert len(heavy) > 40
    bare = [(name, stack) for name, stack in heavy
            if _leaf(stack, LEAVES[kind]) is None]
    assert not bare, bare


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_the_recomputed_forward_carries_jaxs_mark(kind):
    """Every checkpointed leaf has equations under ``rematted_computation``
    in the differentiated pass, all of them in the backward pass
    (``transpose(``), and a pass that nothing differentiates has none."""
    scored, trained = _traced(kind)
    assert not [stack for _, stack, _ in scored if REMAT in stack]
    marked = [stack for _, stack, _ in trained if REMAT in stack]
    assert marked and all("transpose(" in stack for stack in marked)
    assert ({_leaf(stack, LEAVES[kind]) for stack in marked} - {None}
            == set(CHECKPOINTED[kind]))
    # the products are run again, not only their cheap neighbours
    assert sum(REMAT in stack for eqn, stack, _ in trained
               if eqn.primitive.name == "dot_general") >= 8


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_the_scope_around_the_rows(kind):
    """``mercury_rows`` is on the stack of every equation of a row's body
    and of the ``scan`` itself (what ``lax.map`` does a row is the scope's
    own), forward and backward."""
    scored, trained = _traced(kind)
    for equations in (scored, trained):
        scans = [eqn for eqn, _, in_rows in equations
                 if eqn.primitive.name == "scan" and not in_rows]
        assert len(scans) >= 2          # the decoder's and the loss seam's
        assert all("mercury_rows" in stack
                   for eqn, stack, in_rows in equations
                   if in_rows or eqn.primitive.name == "scan")


def _cases():
    return [(kind, leaf) for kind in sorted(LEAVES) for leaf in LEAVES[kind]]


@pytest.mark.parametrize("kind, leaf", _cases())
def test_each_leaf_shows_forward_and_backward(kind, leaf):
    """A leaf's scope is on the name stack of equations of the scored
    forward, of the differentiated pass's forward, and of its ``transpose(``
    paths: the scope is where the work is traced, whichever pass."""
    scored, trained = _traced(kind)

    def under(equations, backward):
        return [stack for eqn, stack, in_rows in equations
                if in_rows and ("transpose(" in stack) == backward
                and _leaf(stack, LEAVES[kind]) == leaf]

    assert under(scored, False)
    assert under(trained, False)
    assert under(trained, True)
