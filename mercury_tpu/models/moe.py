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
#: The flax collection a model sows its last routed layer's ``load`` into
#: (:func:`routed_experts`): ``held_pair_share``, ``load_max_over_mean``.
MOE_LOAD = "moe_load"


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation of the rows: its transpose is the
    gather by the inverse, not a scatter."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _rows_of_pairs(h, order, inverse):
    """A row of ``h [T, D]`` for each of the ``k * T`` (choice, token)
    pairs in sorted order (pair ``p`` is token ``p % T``); its transpose
    sums each token's ``k`` rows, gathered by the inverse."""
    return h[order % h.shape[0]]


def _rows_of_pairs_fwd(h, order, inverse):
    return h[order % h.shape[0]], (inverse, h.shape[0])


def _rows_of_pairs_bwd(res, g):
    inverse, t = res
    return jnp.sum(g[inverse].reshape(-1, t, g.shape[-1]), axis=0), None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


def route_top_k(router_logits, top_k: int, first_expert: int, held: int):
    """Top-k routing of ``[T, E]`` float32 router logits over ALL ``E``
    experts, for a layer that holds experts ``first_expert ..
    first_expert + held - 1``: ``(weights [T, k], slot [k * T], order
    [k * T], group_sizes [held], is_held [T, k])``. The weights are the
    softmax over the ``k`` chosen logits (ties to the lower index). The
    ``k * T`` (choice, token) pairs, choice-major (pair ``c * T + t`` is
    token ``t``'s ``c``-th choice: a ``[k, T, D]`` array of their rows has
    whole tiles, which ``[T, k, D]`` at ``k = 6`` has not), are sorted by
    expert with the pairs of experts held elsewhere last: ``order[s]`` is
    the pair in sorted slot ``s``, ``slot`` its inverse, ``group_sizes``
    the pairs of each held expert. Nothing has a capacity: every pair has
    a slot."""
    with jax.named_scope("mercury_moe_route"):
        logits, chosen = lax.top_k(router_logits, top_k)
        weights = jax.nn.softmax(logits, axis=-1)
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
        return weights, slot, order, group_sizes, is_held


def routed_experts(h, router_logits, gate, up, down, top_k: int,
                   first_expert: int = 0):
    """Top-k routed ReGLU experts over the share of them held here, with no
    capacity and no dropped token, whatever the imbalance: ``h [T, D]``,
    ``router_logits [T, E]`` float32 over all ``E`` experts, ``gate`` /
    ``up`` ``[held, D, F]`` and ``down`` ``[held, F, D]`` the held experts'
    weights. Every token is routed over all ``E``; the (token, expert)
    pairs whose expert is held are grouped by expert and go through three
    grouped matrix products (``lax.ragged_dot``; pairs of experts held
    elsewhere lie past the last group and are not computed); the outputs
    return to their tokens weighted. On one chip there is no exchange.
    Returns ``(y [T, D] float32, load)`` with ``load = (held_pair_share,
    load_max_over_mean)``: the share of pairs that fell on held experts
    (``held / E`` at uniform routing) and the pairs of the busiest held
    expert over the mean."""
    t, d = h.shape
    weights, slot, order, group_sizes, is_held = route_top_k(
        router_logits, top_k, first_expert, gate.shape[0])
    with jax.named_scope("mercury_moe_route"):
        # A row of h for every pair, in sorted order: [k * T, D]. The rows
        # past the last group are no product's business, forward or
        # backward: the select keeps what a kernel leaves there out of h's
        # gradient.
        in_a_group = (jnp.arange(t * top_k, dtype=jnp.int32)
                      < jnp.sum(group_sizes))[:, None]
        pairs = jnp.where(in_a_group, _rows_of_pairs(h, order, slot), 0)

    def grouped(x, w):
        return lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32)

    hidden = (jax.nn.relu(grouped(pairs, gate))
              * grouped(pairs, up)).astype(h.dtype)
    out = grouped(hidden, down).astype(h.dtype)
    with jax.named_scope("mercury_moe_route"):
        back = _permute(out, slot, order).reshape(top_k, t, d)
        # rows past the last group were never written: select, not scale
        back = jnp.where(is_held.T[..., None], back, 0)
        y = jnp.sum(back * weights.T[..., None], axis=0)
        sizes = group_sizes.astype(jnp.float32)
        load = (jnp.sum(sizes) / (t * top_k),
                jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1e-9))
    return y, load
