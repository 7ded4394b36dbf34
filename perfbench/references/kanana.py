"""Plain reference of the family ``kanana``: a causal decoder over integer
tokens with multi-head latent attention (no query latent), a leading dense
SwiGLU layer, and in every other layer top-k routed SwiGLU experts under a
sigmoid router with a selection bias, of which this worker holds a share,
beside shared experts (Kanana-2-30B-A3B, Kakao 2026, ``model_type``
``deepseek_v3``; the configuration's ``source`` is the published
``config.json``), trained on the next token with one loss per sequence.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
no flax module and no import from the program. It reads a parameter tree by
the names flax gives the program's modules (``embed`` ``[V, D]``;
``layer<i>`` holding ``input_norm`` and ``post_norm`` ``[D]``, ``q`` ``[D,
H x (nope + rope)]``, ``kv_a`` ``[D, rank + rope]``, ``kv_norm`` ``[rank]``,
``kv_b`` ``[rank, H x (nope + v)]``, ``o`` ``[H x v, D]`` of the ``H`` heads
held, and either ``dense_gate`` / ``dense_up`` ``[D, I]`` and ``dense_down``
``[I, D]`` or ``router`` ``[D, experts]``, ``router_bias`` ``[experts]``,
``gate`` / ``up`` ``[E, D, F]`` and ``down`` ``[E, F, D]`` of the ``E``
experts held, ``shared_gate`` / ``shared_up`` ``[D, S]`` and ``shared_down``
``[S, D]``; ``final_norm``; ``head`` ``[D, V]``): names and shapes only.
The depth, which layers are dense, and every width but the four sizes of a
head and the latent are the tree's.

**The equations.** For layer ``l`` of the kept ones, ``x`` in
``R^{T x D}``, heads ``h`` of the ``H`` held: ``n = rms_norm(x, g1)``;
``q_h = n W_q[h]`` of ``qk_nope_head_dim + qk_rope_head_dim``, split
``(q_nope, q_rope)``; ``(c, k_rope) = n W_kva`` of ``kv_lora_rank`` and
``qk_rope_head_dim``; ``c = rms_norm(c, g_kv)``; ``(k_nope_h, v_h) =
c W_kvb[h]`` of ``qk_nope_head_dim`` and ``v_head_dim``; ``q_rope`` and
``k_rope`` are rotated at positions 0..T-1 on interleaved pairs ``(2i,
2i + 1)`` (``rope_interleave``; ``theta^(-2i / rope)``, no scaling), and
``k_rope`` is ONE head, the same for every ``h``; scores ``(q_nope .
k_nope + q_rope . k_rope) / sqrt(nope + rope)``, query ``i`` sees key ``j``
iff ``j <= i``, softmax, times ``v_h``; the heads joined ``[T, H x v]``
times ``W_o``; ``x1 = x + that`` (no window, no bias, no query latent, no
q/k norm beyond the latent's). ``m = rms_norm(x1, g2)``. A layer with
``dense_gate``: ``x2 = x1 + (silu(m W_gate) * (m W_up)) W_down``. Every
other: ``s = sigmoid(m W_r)``; the ``top_k`` largest of ``s + b`` are chosen
(ties to the lower index; ``n_group = topk_group = 1``: the group limit is
the identity); weights ``w_e = routed_scaling_factor x s_e / (sum of the
chosen s + 1e-20)`` from the UNBIASED scores; expert ``e``: ``(silu(m
W_gate_e) * (m W_up_e)) W_down_e``; ``x2 = x1 + sum over chosen AND held e
of w_e expert_e(m) + shared(m)``, the shared experts one SwiGLU of their
widths together, unweighted: the held experts are ``first_expert_held ..
first_expert_held + E - 1`` of the router's columns, the others lie on
other chips and add nothing here; likewise what the heads held elsewhere
would add to ``x1`` is left out. After the last layer: ``rms_norm``,
``logits = h W_head``, untied from the embedding. The loss of a sequence is
the mean over its positions of the token negative log-likelihood.

**Departures from the published description.** The selection bias ``b`` is
a parameter that nothing moves (its gradient is zero: it enters the choice
alone); the balancing rule that moves it in pre-training is no key of
``config.json`` and is not run, as when such a model is fine-tuned with the
router's bias frozen. The two shared experts are one MLP of twice the
width (the same sums). Dropout, the query latent (``q_lora_rank`` null),
group-limited choice (``n_group`` 1) and RoPE scaling (null) do not occur
in this configuration and are not written.

**Blocking, not a kernel.** Attention is computed a block of
``query_block`` queries at a time against all keys under an explicit mask
of position comparisons, per-head ``einsum``s, each block and each layer
under ``jax.checkpoint``, so that a gradient over one row of 8,192 tokens
holds one block's probabilities at a time (8 heads x 1,024 x 8,192 x 4 B,
0.27 GB) and not a layer's. The numbers are those of the unblocked equations.
Routing is ``top_k`` and a dense loop (a ``lax.scan``) over the held
experts: every token through every held expert, weighted by 0 where it was
not chosen; no grouping, no sort. Consecutive layers of one kind run as a
``lax.scan`` over their stacked parameters.

The configuration's ``reference`` group (``arch``) gives ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``top_k``, ``routed_scaling_factor``, ``first_expert_held``,
``rms_norm_eps`` and ``query_block``.

**Where the fp8 control rounds** (``quantize="fp8"``): the inputs and the
weights of every matrix product with parameters but the router's: the q,
kv_a, kv_b and o projections, the three products of the dense MLP, of every
held expert and of the shared experts, the head (e4m3, one scale per
tensor). The router's product is not rounded (its top-k would pick other
experts, and the reading would be of routing flips, not of precision); the
embedding lookup, RMSNorm (the latent's too), the rotation, both attention
products, softmax, the sigmoid, SiLU and the residual sums stay in float32.
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


def _rotate_pairs(x, theta: float):
    """RoPE on interleaved pairs ``(2i, 2i + 1)`` of the last axis of
    ``x [N, T, H, rd]``, positions 0..T-1."""
    t, rd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down, quantize):
    return _mm(jax.nn.silu(_mm(x, gate, quantize)) * _mm(x, up, quantize),
               down, quantize)


def _attention(q_nope, q_rope, k_nope, k_rope, v, query_block: int):
    """``q_nope`` / ``k_nope [N, T, H, nope]``, ``q_rope [N, T, H, rd]``,
    ``k_rope [N, T, rd]`` (one head), ``v [N, T, H, vd]`` -> ``[N, T, H x
    vd]``, causal, a block of queries at a time."""
    n, t, h, nope = q_nope.shape
    block = min(query_block, t)
    assert t % block == 0, (t, block)
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + q_rope.shape[-1]))
    key_pos = jnp.arange(t)

    def blocks(a):
        return jnp.moveaxis(a.reshape(n, t // block, block, h, -1), 1, 0)

    @jax.checkpoint
    def one(args):
        qn, qr, start = args                        # [N, block, H, .]
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        s = (jnp.einsum("nqhd,nkhd->nhqk", qn, k_nope,
                        precision=lax.Precision.HIGHEST)
             + jnp.einsum("nqhd,nkd->nhqk", qr, k_rope,
                          precision=lax.Precision.HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v,
                          precision=lax.Precision.HIGHEST)

    out = lax.map(one, (blocks(q_nope), blocks(q_rope),
                        jnp.arange(0, t, block)))
    return jnp.moveaxis(out, 0, 1).reshape(n, t, -1)


def _experts(m, p, arch, quantize):
    """Sum over the held experts of weight x expert, every token through
    every held expert (a scan over them), plus the shared experts."""
    top, first = int(arch["top_k"]), int(arch["first_expert_held"])
    scores = jax.nn.sigmoid(jnp.matmul(
        m, p["router"].astype(jnp.float32), precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(scores + p["router_bias"], top)  # ties: lower index
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = float(arch["routed_scaling_factor"]) * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    def add(out, expert):
        e, gate, up, down = expert
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return out + w[..., None] * _swiglu(m, gate, up, down, quantize), None

    held = p["gate"].shape[0]
    out, _ = lax.scan(add, jnp.zeros_like(m),
                      (jnp.arange(held), p["gate"], p["up"], p["down"]))
    return out + _swiglu(m, p["shared_gate"], p["shared_up"],
                         p["shared_down"], quantize)


def _mixer(x, p, arch, quantize):
    """What latent attention adds to the stream ``x [N, T, D]``."""
    eps = float(arch["rms_norm_eps"])
    rank, nope = int(arch["kv_lora_rank"]), int(arch["qk_nope_head_dim"])
    rd, theta = int(arch["qk_rope_head_dim"]), float(arch["rope_theta"])
    n, t, _ = x.shape
    h = _rms_norm(x, p["input_norm"], eps)
    q = _mm(h, p["q"], quantize).reshape(n, t, -1, nope + rd)
    down = _mm(h, p["kv_a"], quantize)
    latent = _rms_norm(down[..., :rank], p["kv_norm"], eps)
    up = _mm(latent, p["kv_b"], quantize).reshape(n, t, q.shape[2], -1)
    q_rope = _rotate_pairs(q[..., nope:], theta)
    k_rope = _rotate_pairs(down[..., None, rank:], theta)[:, :, 0]
    attn = _attention(q[..., :nope], q_rope, up[..., :nope], k_rope,
                      up[..., nope:], int(arch["query_block"]))
    return _mm(attn, p["o"], quantize)


def _mlp(x, p, arch, quantize):
    """What the layer's MLP adds to the stream: the dense one where the
    layer has it, else the routed and the shared experts."""
    m = _rms_norm(x, p["post_norm"], float(arch["rms_norm_eps"]))
    if "dense_gate" in p:
        return _swiglu(m, p["dense_gate"], p["dense_up"], p["dense_down"],
                       quantize)
    return _experts(m, p, arch, quantize)


def _layer(x, p, arch, quantize):
    x = x + _mixer(x, p, arch, quantize)
    return x + _mlp(x, p, arch, quantize)


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
    kinds = ["dense_gate" in params[f"layer{i}"] for i in range(layers)]
    layer = jax.checkpoint(lambda x, p: _layer(x, p, arch, quantize))
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[inputs]
        first = 0
        while first < layers:
            # consecutive layers of one kind are one program's body, run
            # over their stacked parameters: the same numbers, a quarter of
            # the compiler's work for the four expert layers
            last = first
            while last + 1 < layers and kinds[last + 1] == kinds[first]:
                last += 1
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


def attention_pairs(config: Mapping[str, Any]) -> float:
    """Query-key pairs one sequence's attention requires, the query heads
    held and the kept layers together: causal, no window."""
    t = int(config["seq_len"])
    return (float(config["num_attention_heads"]) * t * (t + 1) / 2.0
            * int(config["num_hidden_layers"]))


def fwd_flops_per_example(config: Mapping[str, Any]) -> float:
    """2 x MACs one sequence's forward pass requires on this chip: the
    latent attention's four projections over the heads held, both attention
    products over the keys a query sees (``qk_head_dim + v_head_dim`` MACs
    a pair a head), the dense MLP of the leading layers, and in every other
    layer the router, the shared experts and the held share of the
    activated experts at uniform routing (``top_k x held / router width``
    experts a token), and the head over the vocabulary rows held. Norms,
    the rotation, softmax, the sigmoid and the embedding lookup left out,
    as is the convention."""
    t, d = int(config["seq_len"]), int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    qk, vd = int(config["qk_head_dim"]), int(config["v_head_dim"])
    rank, rd = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    attention = (d * heads * qk + d * (rank + rd)
                 + rank * heads * (int(config["qk_nope_head_dim"]) + vd)
                 + heads * vd * d)
    layers = int(config["num_hidden_layers"])
    dense = min(layers, int(config["first_k_dense_replace"]))
    width = int(config["moe_router_width"])
    expert = 3 * d * int(config["moe_intermediate_size"])
    routed = (d * width + int(config["n_shared_experts"]) * expert
              + int(config["num_experts_per_tok"])
              * int(config["n_routed_experts"]) / width * expert)
    macs = t * (layers * attention
                + dense * 3 * d * int(config["intermediate_size"])
                + (layers - dense) * routed
                + d * int(config["vocab_size"]))
    return 2.0 * macs + 2.0 * (qk + vd) * attention_pairs(config)


def attention_kernel_work(config: Mapping[str, Any],
                          rows_forward: float, rows_trained: float):
    """``(flops, bytes)`` the attention itself (scores and values, no
    projection) requires for ``rows_forward`` sequences scored and
    ``rows_trained`` trained on, whatever route computes it: 2 x
    (``qk_head_dim + v_head_dim``) FLOPs a causal query-key pair a head
    forward and twice that backward (four products, no recomputation);
    q, k, v read and the output written once forward, and backward q, k,
    v, the output and its cotangent read and three cotangents written, in
    the two bytes of the compute precision, a key of ``qk_head_dim`` a
    head (the rotated part as each head reads it)."""
    qk, vd = int(config["qk_head_dim"]), int(config["v_head_dim"])
    t, heads = int(config["seq_len"]), int(config["num_attention_heads"])
    forward_flops = 2.0 * (qk + vd) * attention_pairs(config)
    layers = int(config["num_hidden_layers"])
    qk_bytes, v_bytes = heads * qk * t, heads * vd * t
    forward_bytes = 2.0 * layers * (2 * qk_bytes + 2 * v_bytes)
    backward_bytes = 2.0 * layers * (4 * qk_bytes + 4 * v_bytes)
    return ((rows_forward + 3.0 * rows_trained) * forward_flops,
            (rows_forward + rows_trained) * forward_bytes
            + rows_trained * backward_bytes)
