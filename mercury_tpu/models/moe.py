"""Mixture-of-Experts MLP with expert parallelism.

The reference has no MoE or expert parallelism (SURVEY.md §2.5); this is a
beyond-parity extension completing the parallelism matrix
(dp/tp/pp/sp/**ep**). The layer is a Switch-style top-1-routed expert MLP:

- a gating projection scores ``num_experts`` experts per token; each token
  goes to its argmax expert, output scaled by the gate probability;
- every expert is a 2-layer GELU MLP whose weights live in stacked arrays
  ``[E, ...]`` — shard that leading axis over a mesh axis (``ep_axis``) and
  each device holds ``E/W`` experts;
- under expert parallelism the dispatch is the TPU-native all-to-all: each
  device buckets its local tokens by target expert into a fixed-capacity
  tensor (static shapes — XLA-friendly), ``lax.all_to_all`` exchanges
  expert-major slabs so every device receives exactly the tokens routed to
  *its* experts, applies them, and a second all-to-all returns the outputs
  to the tokens' home devices;
- tokens beyond an expert's capacity are dropped (output 0 for that token,
  the standard Switch overflow semantics); with enough capacity the EP
  layer is numerically identical to the dense reference path, which the
  tests pin.

A load-balancing auxiliary loss (Switch eq. 4: ``E · Σ_e f_e · p̄_e``) is
returned alongside the output.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from mercury_tpu.compat import axis_size
from jax import lax


class MoEMLP(nn.Module):
    """Top-1 (Switch) mixture-of-experts MLP over token features.

    Call with ``x: [B, T, D]`` (or ``[N, D]``); returns ``(y, aux_loss)``
    with ``y`` the same shape as ``x``.

    ``ep_axis``: mesh axis for expert parallelism — requires being inside
    ``shard_map`` with tokens sharded over the same axis and the stacked
    expert params sharded ``P(ep_axis)`` on their leading axis;
    ``num_experts`` must be divisible by the axis size. ``None`` = single
    device: same fixed-capacity bucketing (identical drop semantics, and
    O(N·capacity_factor) compute), minus the all-to-alls. The O(E·N)
    one-hot oracle is :meth:`reference`.
    """

    num_experts: int
    d_model: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    ep_axis: Optional[str] = None
    compute_dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    def setup(self):
        e, d, h = self.num_experts, self.d_model, self.mlp_ratio * self.d_model
        if self.ep_axis is not None:
            # Inside shard_map each device holds its expert shard, so the
            # declared param shapes are per-device. Initialize params with
            # a dense twin (ep_axis=None) and shard their leading axis.
            w = axis_size(self.ep_axis)
            if e % w:
                raise ValueError(
                    f"num_experts {e} not divisible by axis size {w}"
                )
            e = e // w
        init = nn.initializers.lecun_normal()
        self.gate = nn.Dense(self.num_experts, dtype=self.compute_dtype,
                             param_dtype=self.param_dtype, name="gate")
        self.w_up = self.param("w_up", init, (e, d, h), self.param_dtype)
        self.b_up = self.param("b_up", nn.initializers.zeros, (e, h),
                               self.param_dtype)
        self.w_down = self.param("w_down", init, (e, h, d), self.param_dtype)
        self.b_down = self.param("b_down", nn.initializers.zeros, (e, d),
                                 self.param_dtype)

    def _expert_mlp(self, w_up, b_up, w_down, b_down, tokens):
        # tokens: [..., D] with a leading expert axis matching w_up's.
        h = jnp.einsum("e...d,edh->e...h", tokens,
                       w_up.astype(self.compute_dtype))
        h = nn.gelu(h + b_up.astype(self.compute_dtype)[(slice(None),)
                    + (None,) * (h.ndim - 2)])
        y = jnp.einsum("e...h,ehd->e...d", h,
                       w_down.astype(self.compute_dtype))
        return y + b_down.astype(self.compute_dtype)[(slice(None),)
                   + (None,) * (y.ndim - 2)]

    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        orig_shape = x.shape
        d = orig_shape[-1]
        tokens = x.reshape(-1, d).astype(self.compute_dtype)   # [N, D]
        n = tokens.shape[0]
        e = self.num_experts

        logits = self.gate(tokens)                              # [N, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)                 # [N]
        gate_val = jnp.max(probs, axis=-1)                      # [N]
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)

        # Switch load-balancing loss: E · Σ_e (fraction routed)·(mean prob).
        frac = jnp.mean(onehot, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        if self.ep_axis is not None:
            frac = lax.pmean(frac, self.ep_axis)
            mean_prob = lax.pmean(mean_prob, self.ep_axis)
        aux = e * jnp.sum(frac * mean_prob)

        capacity = int(math.ceil(self.capacity_factor * n / e))

        # Position of each token within its expert's bucket; overflow
        # drops. Integer cumsum: a float32 count would stop incrementing
        # exactly past 2^24 tokens.
        onehot_i = onehot.astype(jnp.int32)
        pos = jnp.sum(jnp.cumsum(onehot_i, axis=0) * onehot_i, axis=-1) - 1
        keep = (pos < capacity).astype(self.compute_dtype)      # [N]
        slot = jnp.clip(pos, 0, capacity - 1)

        # Scatter local tokens into [E, C, D] buckets.
        dispatch = jnp.zeros((e, capacity, d), self.compute_dtype)
        dispatch = dispatch.at[expert_idx, slot].add(
            tokens * keep[:, None]
        )

        if self.ep_axis is None:
            # Single-device path: same bucketing (so capacity semantics
            # match EP exactly), no exchange — each expert's MLP runs on
            # its C bucketed tokens, O(N·capacity_factor) compute. The
            # O(E·N) one-hot oracle lives in :meth:`reference`.
            out = self._expert_mlp(
                self.w_up, self.b_up, self.w_down, self.b_down, dispatch
            )                                                   # [E, C, D]
            y = out[expert_idx, slot] * (keep * gate_val)[:, None]
            return y.reshape(orig_shape).astype(x.dtype), aux

        # ---------------- expert-parallel dispatch ----------------
        w = axis_size(self.ep_axis)
        e_loc = e // w
        # Exchange expert-major slabs: [W, E_loc, C, D] — after all_to_all
        # the leading axis indexes the SOURCE device and E_loc are my
        # experts.
        dispatch = dispatch.reshape(w, e_loc, capacity, d)
        received = lax.all_to_all(dispatch, self.ep_axis, 0, 0, tiled=False)

        out = self._expert_mlp(
            self.w_up, self.b_up, self.w_down, self.b_down,
            received.transpose(1, 0, 2, 3).reshape(e_loc, w * capacity, d),
        )                                                       # [E_loc, W·C, D]
        out = out.reshape(e_loc, w, capacity, d).transpose(1, 0, 2, 3)

        # Route outputs back to the tokens' home devices.
        returned = lax.all_to_all(out, self.ep_axis, 0, 0, tiled=False)
        returned = returned.reshape(e, capacity, d)             # my tokens'
        y = returned[expert_idx, slot] * (keep * gate_val)[:, None]
        return y.reshape(orig_shape).astype(x.dtype), aux

    def reference(self, x) -> Tuple[jax.Array, jax.Array]:
        """O(E·N) one-hot oracle: every expert processes every token, the
        routed output is selected by one-hot combine. No capacity, no
        drops — the definitional top-1 semantics the bucketed paths are
        tested against (``ep_axis`` must be None)."""
        orig_shape = x.shape
        tokens = x.reshape(-1, orig_shape[-1]).astype(self.compute_dtype)
        e = self.num_experts
        probs = jax.nn.softmax(
            self.gate(tokens).astype(jnp.float32), axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)
        gate_val = jnp.max(probs, axis=-1)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        aux = e * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
        all_out = self._expert_mlp(
            self.w_up, self.b_up, self.w_down, self.b_down,
            jnp.broadcast_to(tokens, (e,) + tokens.shape),
        )                                                       # [E, N, D]
        y = jnp.einsum("ne,end->nd", onehot.astype(all_out.dtype), all_out)
        y = y * gate_val[:, None].astype(y.dtype)
        return y.reshape(orig_shape).astype(x.dtype), aux


# ----------------------------------------------- top-k over a share, no drops
#: The flax collection a model sows its routed layers' ``load`` into
#: (:func:`routed_experts`): ``held_pair_share``, ``load_max_over_mean``,
#: ``bounded_share``, and ``bias_moved_share`` where the router has a bias.
MOE_LOAD = "moe_load"

#: The sorted pairs are cut to this many times the rows that uniform routing
#: gives the holder of a share (``k * T * held / E``). A holder of an eighth
#: read 0.4 to 1.5 times its uniform share over seeds and steps (PERF.md
#: section 6), so twice leaves room and still cuts the rows to a quarter;
#: past it the uncut rows run: the factor decides a speed, never a result.
ROWS_OVER_UNIFORM = 2
#: And to no fewer than one in this many of the pairs. A thinner holder's
#: share swings by more of itself (8 of 128 experts under the sigmoid rule
#: read 0.05 to 1.9 times the uniform sixteenth at the log gates, and on one
#: seed of six a layer passed twice it in most rows: 5 % of the step, PERF.md
#: section 6: PR 41's chip runs), and what the bound cuts is by then the
#: lesser part of the routing: ``top_k``, the sorts and the router's product
#: run over all ``k * T`` pairs whatever the bound.
ROWS_AT_LEAST_ONE_IN = 4
#: The cut is a whole number of these: a row tile of the grouped products.
_ROW_TILE = 128


def pair_bound(pairs: int, held: int, experts: int) -> int:
    """The rows kept of ``pairs`` sorted (choice, token) pairs by a holder
    of ``held`` of ``experts`` experts: ``ROWS_OVER_UNIFORM`` times its
    uniform share and no fewer than one in ``ROWS_AT_LEAST_ONE_IN``, rounded
    up to a row tile; all of them where that is no fewer (a layer held
    whole). A function of shapes alone."""
    rows = max(-(-ROWS_OVER_UNIFORM * pairs * held // experts),
               -(-pairs // ROWS_AT_LEAST_ONE_IN))
    return min(pairs, -(-rows // _ROW_TILE) * _ROW_TILE)


def _by_slot(x, slot):
    """A row of ``x [R, D]`` for each pair, by its sorted slot; the pairs
    whose slot lies past the ``R`` rows kept read zero. With every row kept
    (``R = k * T``) it is the permutation ``x[slot]``."""
    rows = x.shape[0]
    if rows == slot.shape[0]:
        return x[slot]
    return jnp.where((slot < rows)[:, None],
                     x[jnp.minimum(slot, rows - 1)], 0)


@jax.custom_vjp
def _rows_back(x, slot, order):
    """:func:`_by_slot` of ``x [R, D]``, whose row ``s`` is pair
    ``order[s]``'s: its transpose is the gather of ``R`` rows by ``order``,
    not a scatter."""
    return _by_slot(x, slot)


def _rows_back_fwd(x, slot, order):
    return _by_slot(x, slot), order


def _rows_back_bwd(order, g):
    return g[order], None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


@jax.custom_vjp
def _rows_of_pairs(h, order, slot):
    """A row of ``h [T, D]`` for each of the first ``R`` of the ``k * T``
    (choice, token) pairs in sorted order (``order [R]``; pair ``p`` is
    token ``p % T``); its transpose sums each token's ``k`` rows, gathered
    by ``slot``."""
    return h[order % h.shape[0]]


def _rows_of_pairs_fwd(h, order, slot):
    return h[order % h.shape[0]], (slot, h.shape[0])


def _rows_of_pairs_bwd(res, g):
    slot, t = res
    return (jnp.sum(_by_slot(g, slot).reshape(-1, t, g.shape[-1]), axis=0),
            None, None)


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


def route_top_k(router_logits, top_k: int, first_expert: int, held: int,
                bias=None, scale: float = 1.0):
    """Top-k routing of ``[T, E]`` float32 router logits over ALL ``E``
    experts, for a layer that holds experts ``first_expert ..
    first_expert + held - 1``: ``(weights [T, k], slot [k * T], order
    [k * T], group_sizes [held], is_held [T, k])``, and with a ``bias`` a
    sixth, ``bias_moved``.

    **Two rules** of choice and weight, by ``bias``. None: the ``k``
    largest logits, weighted by the softmax over those ``k`` (ties to the
    lower index). A ``bias [E]`` (DeepSeek-V3's ``noaux_tc``): scores
    ``s = sigmoid(logits)``; the ``k`` largest of ``s + bias`` are chosen,
    and weighted by the UNBIASED scores, ``scale * s_i / (sum of the
    chosen s_j + 1e-20)``: the bias enters the choice alone, so its
    gradient is zero. ``bias_moved`` is the share of the (token, choice)
    pairs that ``s`` alone would not have chosen.

    The ``k * T`` (choice, token) pairs, choice-major (pair ``c * T + t`` is
    token ``t``'s ``c``-th choice: a ``[k, T, D]`` array of their rows has
    whole tiles, which ``[T, k, D]`` at ``k = 6`` has not), are sorted by
    expert with the pairs of experts held elsewhere last: ``order[s]`` is
    the pair in sorted slot ``s``, ``slot`` its inverse, ``group_sizes``
    the pairs of each held expert. Nothing has a capacity: every pair has
    a slot, and the held pairs have the first ``sum(group_sizes)`` of them,
    which is what lets :func:`routed_experts` cut the rows that follow the
    sort to a bound and lose none."""
    with jax.named_scope("mercury_moe_route"):
        moved = ()
        if bias is None:
            logits, chosen = lax.top_k(router_logits, top_k)
            weights = jax.nn.softmax(logits, axis=-1)
        else:
            scores = jax.nn.sigmoid(router_logits)
            _, chosen = lax.top_k(scores + bias.astype(scores.dtype), top_k)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = scale * picked / (
                jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
            # a chosen expert that k or more unbiased scores lie above is
            # one the bias brought in
            above = jnp.sum(scores[:, None, :] > picked[:, :, None], axis=-1)
            moved = (jnp.mean((above >= top_k).astype(jnp.float32)),)
        local = chosen - first_expert
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held).T.reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        # the inverse of a permutation by a second sort: a scatter of
        # single elements is the slow way on the TPU
        slot = jnp.argsort(order).astype(jnp.int32)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        return (weights, slot, order, group_sizes, is_held, *moved)


def _experts_of_rows(activation, h, weights, gate, up, down, slot, order,
                     group_sizes, is_held):
    """The held experts' weighted outputs ``y [T, D]`` float32 from the
    first ``R = order.shape[0]`` sorted pairs, which must hold every held
    pair (``sum(group_sizes) <= R``): every array between the sort and the
    return to the tokens has ``R`` rows. ``activation`` is the gate's."""
    t, d = h.shape
    rows, top_k = order.shape[0], weights.shape[1]
    with jax.named_scope("mercury_moe_route"):
        # A row of h for every pair kept, in sorted order: [R, D]. The rows
        # past the last group are no product's business, forward or
        # backward: the select keeps what a kernel leaves there out of h's
        # gradient.
        in_a_group = (jnp.arange(rows, dtype=jnp.int32)
                      < jnp.sum(group_sizes))[:, None]
        pairs = jnp.where(in_a_group, _rows_of_pairs(h, order, slot), 0)

    def grouped(x, w):
        return lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)

    hidden = (activation(grouped(pairs, gate))
              * grouped(pairs, up)).astype(h.dtype)
    out = grouped(hidden, down).astype(h.dtype)
    with jax.named_scope("mercury_moe_route"):
        # The return gathers a row for every pair out of the R (timed in
        # the step against a scatter-add of the R weighted rows, which cost
        # ten times the gather: PERF.md section 6, PR 40).
        back = _rows_back(out, slot, order).reshape(top_k, t, d)
        # rows past the last group were never written: select, not scale
        back = jnp.where(is_held.T[..., None], back, 0)
        return jnp.sum(back * weights.T[..., None], axis=0)


def _cut(bound: int, routing):
    """The routing's arrays ``(slot, order, group_sizes, is_held)`` with
    the sorted pairs cut to the first ``bound``."""
    slot, order, *rest = routing
    return (slot, order[:bound], *rest)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bounded_or_whole(bound: int, activation, fits, floats, routing):
    """:func:`_experts_of_rows` of ``(*floats, *routing)`` over ``bound``
    rows where the held pairs fit in them (``fits``, a traced bool), over
    all ``k * T`` where not: one ``lax.cond`` forward and one backward.
    ``lax.cond``'s own derivative would keep both arms' residuals, zeros
    for the arm not taken, and so write the ``k * T``-row arrays on the
    bounded path after all; here the residuals are the operands, and the
    arm taken is differentiated inside the backward ``cond`` (the caller's
    ``jax.checkpoint`` recomputes the layer there anyway)."""
    return lax.cond(
        fits, lambda f, r: _experts_of_rows(activation, *f, *_cut(bound, r)),
        lambda f, r: _experts_of_rows(activation, *f, *r), floats, routing)


def _bounded_or_whole_fwd(bound, activation, fits, floats, routing):
    return (_bounded_or_whole(bound, activation, fits, floats, routing),
            (fits, floats, routing))


def _bounded_or_whole_bwd(bound, activation, res, g):
    fits, floats, routing = res

    def pull(cut):
        def arm(g, floats, routing):
            return jax.vjp(
                lambda *f: _experts_of_rows(activation, *f, *cut(routing)),
                *floats)[1](g)
        return arm

    return (None, lax.cond(fits, pull(functools.partial(_cut, bound)),
                           pull(tuple), g, floats, routing), None)


_bounded_or_whole.defvjp(_bounded_or_whole_fwd, _bounded_or_whole_bwd)


def gated_mlp(h, gate, up, down, activation):
    """``(activation(h W_gate) * (h W_up)) W_down`` of every token: a dense
    layer's MLP, and the shared experts beside the routed ones (as one MLP
    of their widths together). ``h [T, D]`` in the compute precision ->
    ``[T, D]`` float32."""
    def dot(x, w):
        return jnp.dot(x, w, preferred_element_type=jnp.float32)

    hidden = (activation(dot(h, gate)) * dot(h, up)).astype(h.dtype)
    return dot(hidden, down)


def routed_experts(h, router_logits, gate, up, down, top_k: int,
                   first_expert: int = 0, *, bias=None, scale: float = 1.0,
                   activation=jax.nn.relu, shared=None):
    """Top-k routed gated experts (ReGLU, or ``activation``'s) over the
    share of them held here, with no capacity and no dropped token,
    whatever the imbalance: ``h [T, D]``, ``router_logits [T, E]`` float32
    over all ``E`` experts, ``gate`` / ``up`` ``[held, D, F]`` and ``down``
    ``[held, F, D]`` the held experts' weights. Every token is routed over
    all ``E`` by one of :func:`route_top_k`'s two rules (``bias`` and
    ``scale`` are the second's); the (token, expert) pairs whose expert is
    held are grouped by expert and go through three grouped matrix
    products (``lax.ragged_dot``; pairs of experts held elsewhere lie past
    the last group and are not computed); the outputs return to their
    tokens weighted. On one chip there is no exchange. ``shared`` (``(gate
    [D, S], up [D, S], down [S, D])``) is the MLP every token goes through
    beside its routed experts, added unweighted: every holder of a share
    computes it alike.

    **The bound.** The sort puts the held pairs first, so the gathers, the
    selects, the casts and the products' operands that follow it have
    ``C = pair_bound(k * T, held, E)`` rows, not ``k * T``: twice what
    uniform routing gives this holder (``ROWS_OVER_UNIFORM``) and no fewer
    than a quarter of the pairs (``ROWS_AT_LEAST_ONE_IN``), from the shapes
    alone. Where the held pairs of a row of tokens outnumber ``C``,
    the same function runs over all ``k * T`` rows under the other arm of
    one ``lax.cond``: the bound is no capacity, and the two arms compute
    the same sums. A layer held whole (``held == E``) has ``C = k * T``
    and traces no ``cond``.

    Returns ``(y [T, D] float32, load)`` with ``load = (held_pair_share,
    load_max_over_mean, bounded)``: the share of pairs that fell on held
    experts (``held / E`` at uniform routing), the pairs of the busiest
    held expert over the mean, and 1.0 where the bounded rows ran (0.0
    where all ``k * T`` did; 1.0 where no ``cond`` was traced); under the
    rule with a bias a fourth, ``bias_moved_share`` (the share of the
    pairs that the unbiased scores would not have chosen)."""
    pairs, held = h.shape[0] * top_k, gate.shape[0]
    weights, slot, order, group_sizes, is_held, *moved = route_top_k(
        router_logits, top_k, first_expert, held, bias, scale)
    floats = (h, weights, gate, up, down)
    routing = (slot, order, group_sizes, is_held)
    bound = pair_bound(pairs, held, router_logits.shape[-1])
    if bound == pairs:
        y = _experts_of_rows(activation, *floats, *routing)
        fits = jnp.ones((), bool)
    else:
        fits = jnp.sum(group_sizes) <= bound
        y = _bounded_or_whole(bound, activation, fits, floats, routing)
    if shared is not None:
        with jax.named_scope("mercury_moe_shared"):
            y = y + gated_mlp(h, *shared, activation)
    with jax.named_scope("mercury_moe_route"):
        sizes = group_sizes.astype(jnp.float32)
        load = (jnp.sum(sizes) / pairs,
                jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9),
                fits.astype(jnp.float32), *moved)
    return y, load
