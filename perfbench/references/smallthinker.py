"""Plain reference of the family ``smallthinker``: a causal decoder over
integer tokens with window and full grouped-query attention mixed and
top-k routed ReGLU experts, of which this worker holds a share
(SmallThinker, PowerInfer 2025; the configuration's ``source`` is the
published ``config.json``), trained on the next token with one loss per
sequence.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
no flax module and no import from the program. It reads a parameter tree by
the names flax gives the program's modules (``embed`` ``[V, D]``;
``layer<i>`` holding ``input_norm`` and ``post_norm`` ``[D]``, ``router``
``[D, experts]``, ``q`` / ``k`` / ``v`` ``[D, heads x head_dim]``, ``o``,
``gate`` / ``up`` ``[E, D, F]`` and ``down`` ``[E, F, D]`` of the ``E``
experts held; ``final_norm``; ``head`` ``[D, V]``): names and shapes only.
The depth and every width but the head size are the tree's.

**The equations.** For layer ``l`` of the kept ones, ``x`` in
``R^{T x D}``: ``h = rms_norm(x, g1)``; ``r = h W_r`` (the router reads the
layer's input after ``input_norm``, the tensor attention also reads: an
assumption the configuration states); ``q = h W_q``, ``k = h W_k``,
``v = h W_v`` (no bias, no q/k norm); where ``rope_layout[l]`` is 1, ``q``
and ``k`` are rotated (rotate-half over the whole head, positions 0..T-1),
where 0 they are left as they are; query ``i`` sees key ``j`` iff
``j <= i``, and where ``sliding_window_layout[l]`` is 1 also
``j > i - sliding_window_size``; scores over ``sqrt(head_dim)``, softmax,
query head ``g`` reads key/value head ``g // (heads / kv_heads)``;
``x1 = x + attn W_o``. ``h2 = rms_norm(x1, g2)``; the ``top_k`` largest of
``r`` (ties to the lower index); weights = softmax over those logits;
expert ``e``: ``(relu(h2 W_gate_e) * (h2 W_up_e)) W_down_e``;
``x2 = x1 + sum over selected AND held e of w_e expert_e(h2)``: the held
experts are ``first_expert_held .. first_expert_held + E - 1`` of the
router's columns, the others lie on other chips and add nothing here.
After the last layer: ``rms_norm``, ``logits = h W_head``, untied from the
embedding. The loss of a sequence is the mean over its positions of the
token negative log-likelihood.

**Blocking, not a kernel.** Attention is computed a block of
``query_block`` queries at a time against all keys under an explicit mask
of position comparisons, each block and each layer under
``jax.checkpoint``, so that a gradient over one row of 8,192 tokens holds
one block's probabilities at a time (28 x 1,024 x 8,192 x 4 B, about
1 GB) and not a layer's 7.5 GB. The numbers are those of the unblocked
equations. Routing is ``top_k`` and a dense loop (a ``lax.scan``) over the
held experts: every token through every held expert, weighted by 0 where
it was not selected; no grouping, no sort. Consecutive layers of one kind
(a period's three windowed layers) run as a ``lax.scan`` over their
stacked parameters, so that the compiler sees their body once.

The configuration's ``reference`` group (``arch``) gives ``head_dim``,
``num_key_value_heads``, ``rope_theta``, ``rope_layout``,
``sliding_window_layout``, ``sliding_window_size``, ``top_k``,
``first_expert_held``, ``rms_norm_eps`` and ``query_block``.

**Where the fp8 control rounds** (``quantize="fp8"``): the inputs and the
weights of every matrix product with parameters: the q, k, v and o
projections, the three products of every held expert, the head (e4m3, one
scale per tensor). The router's product is not rounded (its top-k would
pick other experts, and the reading would be of routing flips, not of
precision); the embedding lookup, RMSNorm, the rotation, both attention
products, softmax, ReLU and the residual sums stay in float32.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference import round_to

#: RMSNorm within a position, attention and routing within a row.
ROWS_INDEPENDENT = True


def _mm(x, w, quantize):
    return jnp.matmul(round_to(x, quantize),
                      round_to(w.astype(jnp.float32), quantize),
                      precision=lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rotate(x, theta: float):
    """Rotate-half RoPE over the whole head of ``x [N, T, H, hd]``."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def _attention(q, k, v, window: Optional[int], query_block: int):
    """``q [N, T, H, hd]``, ``k`` / ``v`` ``[N, T, KV, hd]`` ->
    ``[N, T, H * hd]``, a block of queries at a time."""
    n, t, h, hd = q.shape
    kv = k.shape[2]
    block = min(query_block, t)
    assert t % block == 0, (t, block)
    q = q.reshape(n, t // block, block, kv, h // kv, hd)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        qb, start = args                       # [N, block, KV, G, hd]
        query_pos = start + jnp.arange(block)
        seen = key_pos[None, :] <= query_pos[:, None]
        if window is not None:
            seen &= key_pos[None, :] > query_pos[:, None] - window
        s = jnp.einsum("nqcgd,nkcd->ncgqk", qb, k,
                       precision=lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("ncgqk,nkcd->nqcgd", p, v,
                          precision=lax.Precision.HIGHEST)

    out = lax.map(one, (jnp.moveaxis(q, 1, 0),
                        jnp.arange(0, t, block)))
    return jnp.moveaxis(out, 0, 1).reshape(n, t, h * hd)


def _experts(h, r, p, arch, quantize):
    """Sum over the held experts of weight x expert, every token through
    every held expert (a scan over them: one expert's program, not eight
    copies of it)."""
    top, first = int(arch["top_k"]), int(arch["first_expert_held"])
    logits, chosen = lax.top_k(r, top)             # ties: the lower index
    weights = jax.nn.softmax(logits, axis=-1)

    def add(out, expert):
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        hidden = jax.nn.relu(_mm(h, gate, quantize)) * _mm(h, up, quantize)
        return out + w[..., None] * _mm(hidden, down, quantize), None

    held = p["gate"].shape[0]
    out, _ = lax.scan(add, jnp.zeros_like(h),
                      (jnp.arange(held), p["gate"], p["up"], p["down"]))
    return out


def _layer(x, p, arch, index: int, quantize):
    eps, hd = float(arch["rms_norm_eps"]), int(arch["head_dim"])
    n, t, _ = x.shape
    h = _rms_norm(x, p["input_norm"], eps)
    r = jnp.matmul(h, p["router"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    q, k, v = (_mm(h, p[name], quantize).reshape(n, t, -1, hd)
               for name in ("q", "k", "v"))
    if arch["rope_layout"][index]:
        q, k = (_rotate(a, float(arch["rope_theta"])) for a in (q, k))
    window = (int(arch["sliding_window_size"])
              if arch["sliding_window_layout"][index] else None)
    attn = _attention(q, k, v, window, int(arch["query_block"]))
    x = x + _mm(attn, p["o"], quantize)
    h2 = _rms_norm(x, p["post_norm"], eps)
    return x + _experts(h2, r, p, arch, quantize)


# ------------------------------------------------------------ the interface
def prepare(raw_rows, arch: Mapping[str, Any]):
    """Token rows are the model's inputs."""
    return raw_rows


def augment(key, inputs, arch: Mapping[str, Any]):
    """None: token rows are trained on as they are."""
    return inputs


def forward(params, model_state, inputs, arch: Mapping[str, Any],
            quantize: Optional[str] = None):
    """Logits ``[N, T, V]`` (float32) for tokens ``[N, T]``; training and
    inference mode are one and ``model_state`` is ignored."""
    layers = sum(1 for name in params if name.startswith("layer"))
    kinds = [(arch["rope_layout"][i], arch["sliding_window_layout"][i])
             for i in range(layers)]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[inputs]
        first = 0
        while first < layers:
            # consecutive layers of one kind are one program's body, run
            # over their stacked parameters: the same numbers, a third of
            # the compiler's work for a period's three windowed layers
            last = first
            while last + 1 < layers and kinds[last + 1] == kinds[first]:
                last += 1
            layer = jax.checkpoint(
                lambda x, p, i=first: _layer(x, p, arch, i, quantize))
            run = [params[f"layer{i}"] for i in range(first, last + 1)]
            x, _ = lax.scan(lambda x, p: (layer(x, p), None), x,
                            jax.tree.map(lambda *a: jnp.stack(a), *run))
            first = last + 1
        h = _rms_norm(x, params["final_norm"], float(arch["rms_norm_eps"]))
        return _mm(h, params["head"], quantize)


def example_loss(outputs, labels):
    """``[N]``: the mean over a sequence's positions of the token negative
    log-likelihood."""
    logp = jax.nn.log_softmax(outputs.astype(jnp.float32), axis=-1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(token, axis=-1)


def eval_example_loss(outputs, labels):
    """On the device: a block's logits never come to the host."""
    return np.asarray(jax.jit(example_loss)(outputs, jnp.asarray(labels)),
                      np.float64)


def mean_keys_seen(seq_len: int, window: Optional[int]) -> float:
    """Keys a query sees, mean over the positions of a sequence."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2.0
    return (window * (window + 1) / 2.0
            + (seq_len - window) * window) / seq_len


def attention_pairs(config: Mapping[str, Any]) -> float:
    """Query-key pairs one sequence's attention requires, all query heads
    and kept layers together: causal, and windowed where the layout says."""
    t = int(config["seq_len"])
    return float(config["num_attention_heads"]) * t * sum(
        mean_keys_seen(t, int(config["sliding_window_size"])
                       if config["sliding_window_layout"][i] else None)
        for i in range(int(config["num_hidden_layers"])))


def fwd_flops_per_example(config: Mapping[str, Any]) -> float:
    """2 x MACs one sequence's forward pass requires on this chip: the four
    projections and the router of every kept layer, the held share of the
    activated experts at uniform routing (``top_k x held / router width``
    experts a token), both attention products over the keys a query sees,
    and the head over the vocabulary rows held. Norms, the rotation,
    softmax and the embedding lookup left out, as is the convention."""
    t, d = int(config["seq_len"]), int(config["hidden_size"])
    hd = int(config["head_dim"])
    q_width = int(config["num_attention_heads"]) * hd
    kv_width = int(config["num_key_value_heads"]) * hd
    width = int(config["moe_router_width"])
    layer = 2 * d * (q_width + kv_width) + d * width      # q, o, k, v, router
    expert = 3 * d * int(config["moe_ffn_hidden_size"])
    experts = (int(config["moe_num_active_primary_experts"])
               * int(config["moe_num_primary_experts"]) / width * expert)
    macs = t * (int(config["num_hidden_layers"]) * (layer + experts)
                + d * int(config["vocab_size"]))
    return 2.0 * macs + 4.0 * hd * attention_pairs(config)


def attention_kernel_work(config: Mapping[str, Any],
                          rows_forward: float, rows_trained: float):
    """``(flops, bytes)`` the attention itself (scores and values, no
    projection) requires for ``rows_forward`` sequences scored and
    ``rows_trained`` trained on: 4 x head_dim FLOPs a query-key pair
    forward and twice that backward (four products, no recomputation);
    q, k, v read and the output written once forward, and backward q, k,
    v, the output and its cotangent read and three cotangents written, in
    the two bytes of the compute precision."""
    hd, t = int(config["head_dim"]), int(config["seq_len"])
    forward_flops = 4.0 * hd * attention_pairs(config)
    q = int(config["num_attention_heads"]) * hd * t
    kv = int(config["num_key_value_heads"]) * hd * t
    layers = int(config["num_hidden_layers"])
    forward_bytes = 2.0 * layers * (2 * q + 2 * kv)
    backward_bytes = 2.0 * layers * (4 * q + 4 * kv)
    return ((rows_forward + 3.0 * rows_trained) * forward_flops,
            (rows_forward + rows_trained) * forward_bytes
            + rows_trained * backward_bytes)
