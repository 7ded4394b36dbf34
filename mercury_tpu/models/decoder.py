"""A causal decoder over integer tokens, trained on the next token: RMSNorm,
grouped-query softmax attention under a causal or a windowed mask with RoPE
by layer, top-k routed ReGLU experts of which this worker may hold a share,
an untied head. The widths are a published model's, chosen by name
(``LM_WIDTHS``); what a chip holds of it is the ``cut``.

**A row at a time.** A sequence is the unit the step scores and draws, and
nothing here mixes two rows, so the whole forward runs under ``lax.map`` over
the rows of a pool or a batch: the projections still see ``T`` tokens at a
time, attention never holds more than one row's blocks, the pairs of the
routed experts are sorted a row at a time. The model stops before its head's
product: it returns the final hidden states and the head, ``(hidden [N, T, D],
head [D, V])`` in the compute precision, and the step's loss seam
(``train/stages.py::row_loss_and_score``, ``sampling.importance``) takes the
product and the token loss a row at a time, so that no more than one row's
``[T, V]`` logits ever exist; ``Trainer.predict`` alone forms whole logits, of
the few rows it is given.

**Attention** never forms ``[T, T]`` scores for a whole sequence. On the TPU
(``use_pallas``) it is jax's splash-attention kernel, once per key/value head
with that head's query heads as one multi-query call (no key is copied per
query head), key blocks outside the causal or windowed mask not visited, the
backward pass recomputing probabilities. Elsewhere — and where the shapes are
not the kernel's (a head size or a length that is no multiple of 128) — a
blockwise XLA form: a block of queries against the slice of keys its mask can
reach, each block under ``jax.checkpoint``.

**Routing.** Every token is routed over all the layer's experts; the (choice,
token) pairs are sorted with those of the experts held here first, and every
array that follows the sort (the gather of the tokens' rows, the grouped
products' operands, the rows that return) has a static bound of rows, not
``k * T``: twice what uniform routing gives a holder of ``held`` of ``E``
experts (``models/moe.py::pair_bound``, from the shapes alone; a quarter of
the pairs for 8 of 64). A row of tokens whose held pairs outnumber the bound
runs over all ``k * T`` rows under the other arm of one ``lax.cond``: no pair
is dropped and nothing has a capacity. A layer held whole has no bound and
no ``cond``.

**Precision.** Parameters in ``param_dtype``; matrix products in
``compute_dtype`` with float32 accumulation; the residual stream, RMSNorm,
the rotation, softmax and the token loss in float32; the router's product
and its top-k in float32 from the normalised float32 stream, so that as few
of its near-ties as the products' rounding allows fall the other way than
in a float32 forward.

Scopes (metadata only): ``mercury_attention`` (projections, rotation, the
attention), ``mercury_moe`` (router, grouping, expert products, return) with
``mercury_moe_route`` nested in it (``mercury_lm_head`` is the seam's). The
last layer's load and the share of all the routed layers that ran over the
bounded rows are sowed into the ``MOE_LOAD`` collection.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mercury_tpu.models.moe import MOE_LOAD, routed_experts
from mercury_tpu.ops import mercury_kernels


class LMWidths(NamedTuple):
    """A published decoder's sizes. Layer ``l`` is a full-attention layer
    without RoPE where ``l % period == 0`` and a windowed one with RoPE
    elsewhere."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    expert_width: int
    window: int
    rope_theta: float
    period: int = 4
    norm_eps: float = 1e-6


LM_WIDTHS: Dict[str, LMWidths] = {
    # https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json; 151,936 vocabulary rows.
    "smallthinker-21b-a3b": LMWidths(
        num_layers=52, d_model=2560, num_heads=28, num_kv_heads=4,
        head_dim=128, num_experts=64, top_k=6, expert_width=768,
        window=4096, rope_theta=1_500_000.0),
}

#: Queries a block of the XLA form takes at a time.
QUERY_BLOCK = 512
#: Splash-attention block sizes on the TPU (q, kv, kv_compute).
SPLASH_BLOCKS = (1024, 1024, 512)


# ------------------------------------------------------------------ pieces
def windowed_and_rotated(widths: LMWidths, index: int) -> Tuple[bool, bool]:
    """Whether layer ``index`` attends under the window, and whether it
    rotates its queries and keys: the first layer of each period does
    neither (full attention, no position), the others do both."""
    kind = index % widths.period != 0
    return kind, kind


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * scale.astype(jnp.float32)


def rotate_half(x, theta: float, offset: int = 0):
    """RoPE (rotate-half over the whole head) of ``x [T, H, hd]`` float32
    at positions ``offset .. offset + T - 1``."""
    t, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def _masked_attention(q, k, v, window: Optional[int], first_query=0,
                      first_key=0):
    """Softmax attention of queries at positions ``first_query ..`` over
    keys at ``first_key ..``: query ``i`` sees key ``j`` iff ``j <= i``
    and, under a window, ``j > i - window``. ``q [KV, G, Tq, hd]``
    (scaled), ``k`` / ``v`` ``[KV, Tk, hd]`` -> ``[KV, G, Tq, hd]``."""
    i = first_query + jnp.arange(q.shape[2])[:, None]
    j = first_key + jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    s = jnp.einsum("cgqd,ckd->cgqk", q, k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("cgqk,ckd->cgqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def dense_attention(q, k, v, window: Optional[int]):
    """The equations as written, ``[T, T]`` scores and all: what the
    blockwise forms are tested against."""
    return _masked_attention(q, k, v, window)


def blockwise_attention(q, k, v, window: Optional[int],
                        block: int = QUERY_BLOCK):
    """A block of queries at a time against the slice of keys its mask can
    reach (a windowed layer's block never reads a key more than ``window``
    behind its first query), each block under ``jax.checkpoint``: the
    backward pass recomputes the block's probabilities and stores none.
    Shapes as :func:`_masked_attention`."""
    t = q.shape[2]
    block = min(block, t)
    if t % block:
        raise ValueError(f"sequence length {t} is no multiple of the query "
                         f"block {block}")
    one = jax.checkpoint(_masked_attention, static_argnums=(3,))
    out = []
    for start in range(0, t, block):
        lo = 0 if window is None else max(0, start - window + 1)
        out.append(one(q[:, :, start:start + block], k[:, lo:start + block],
                       v[:, lo:start + block], window, start, lo))
    return jnp.concatenate(out, axis=2)


@functools.lru_cache(maxsize=None)
def _splash_kernel(t: int, groups: int, window: Optional[int],
                   interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask as mask_lib,
    )

    if window is None:
        mask = mask_lib.CausalMask((t, t))
    else:       # query i sees keys i - window + 1 .. i
        mask = mask_lib.LocalMask((t, t), (window - 1, 0), 0)
    bq, bkv, bc = (min(b, t) for b in SPLASH_BLOCKS)
    sizes = kernel.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bc,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bc,
        block_q_dq=bq, block_kv_dq=bkv)
    return kernel.make_splash_mqa(
        mask_lib.MultiHeadMask([mask] * groups), block_sizes=sizes,
        head_shards=1, q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, window: Optional[int]):
    """jax's splash-attention kernel: one multi-query call per key/value
    head (``vmap`` over them), blocks outside the mask skipped; interpreted
    off the chip, as the repo's own kernels are. Shapes as
    :func:`_masked_attention`."""
    # built outside any trace: the kernel object holds the mask's block
    # tables as arrays, and is cached across traces
    with jax.ensure_compile_time_eval():
        kernel = _splash_kernel(q.shape[2], q.shape[1], window,
                                mercury_kernels._interpret())
    return jax.vmap(kernel)(q, k, v)


def splash_takes(t: int, head_dim: int) -> bool:
    """Whether the kernel takes these shapes (lanes of 128)."""
    return head_dim % 128 == 0 and t % 128 == 0


# ------------------------------------------------------------------- model
class _LayerParams(nn.Module):
    """One layer's parameters, by the names the plain reference reads."""

    widths: LMWidths
    held: int
    param_dtype: Any

    @nn.compact
    def __call__(self) -> Dict[str, jax.Array]:
        w, pd = self.widths, self.param_dtype
        d, f = w.d_model, w.expert_width
        dense = nn.initializers.lecun_normal()
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        shapes = dict(
            router=(d, w.num_experts), q=(d, w.num_heads * w.head_dim),
            k=(d, w.num_kv_heads * w.head_dim),
            v=(d, w.num_kv_heads * w.head_dim),
            o=(w.num_heads * w.head_dim, d))
        out = {name: self.param(name, dense, shape, pd)
               for name, shape in shapes.items()}
        for name in ("input_norm", "post_norm"):
            out[name] = self.param(name, nn.initializers.ones, (d,), pd)
        for name, shape in (("gate", (self.held, d, f)),
                            ("up", (self.held, d, f)),
                            ("down", (self.held, f, d))):
            out[name] = self.param(name, stacked, shape, pd)
        return out


class CausalDecoder(nn.Module):
    """``tokens [N, T]`` int -> ``(hidden [N, T, D], head [D, V])``: the
    final hidden states (normalised) and the untied head, both in
    ``compute_dtype``; the logits are their product, which the caller
    takes a row at a time. ``num_classes`` is the vocabulary rows held
    (embedding and head alike); ``cut`` is ``(layers kept, first expert
    held, experts held)``, None the whole model. Training and inference
    mode are one (no dropout, no running statistic)."""

    num_classes: int
    widths: LMWidths
    cut: Optional[Tuple[int, int, int]] = None
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_pallas: bool = False

    def _sizes(self) -> Tuple[int, int, int]:
        w = self.widths
        layers, first, held = self.cut or (w.num_layers, 0, w.num_experts)
        if not (1 <= layers <= w.num_layers and 0 <= first
                and held >= 1 and first + held <= w.num_experts):
            raise ValueError(f"cut {self.cut} does not lie inside "
                             f"{w.num_layers} layers and {w.num_experts} "
                             "experts")
        return int(layers), int(first), int(held)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        w, pd, cd = self.widths, self.param_dtype, self.compute_dtype
        layers, first, held = self._sizes()
        # Rows of unit scale: a seeded stand-in for a trained model, whose
        # residual stream carries the token. At the customary 0.02 the
        # branches' outputs (unit scale under these initialisers) are fifty
        # times the embedding, every position's hidden state is the
        # sequence's mean but for a fiftieth, and the router sends all of a
        # sequence's tokens to the same ``top_k`` experts.
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.num_classes, w.d_model), pd)
        blocks = [_LayerParams(w, held, pd, name=f"layer{i}")()
                  for i in range(layers)]
        final_norm = self.param("final_norm", nn.initializers.ones,
                                (w.d_model,), pd)
        head = self.param("head", nn.initializers.lecun_normal(),
                          (w.d_model, self.num_classes), pd)
        if self.is_initializing():
            # the parameters are all there is to initialise: no forward,
            # whose programs and kernels would be compiled for this once
            return jnp.zeros(tokens.shape + (w.d_model,), cd), head.astype(cd)
        # the matrices in the compute precision, once for all rows; the
        # norms' gains and the router stay as they are (float32 products)
        keep = ("input_norm", "post_norm", "router")
        blocks = [{name: a if name in keep else a.astype(cd)
                   for name, a in block.items()} for block in blocks]

        def row(ids):
            # the residual stream stays float32 (each layer adds its two
            # float32-accumulated products to it unrounded): what the
            # router reads then differs from the plain float32 forward by
            # the products' input rounding alone, not by eight roundings
            # of x itself, and fewer near-ties of its top-k fall the
            # other way
            x = embed[ids].astype(jnp.float32)
            bounded = 0.0
            for i, block in enumerate(blocks):
                x, (*load, fits) = jax.checkpoint(functools.partial(
                    self._layer, index=i, first_expert=first))(x, block)
                bounded += fits / layers
            return (rms_norm(x, final_norm, w.norm_eps).astype(cd),
                    (*load, bounded))

        hidden, load = lax.map(row, tokens)
        for name, value in zip(("held_pair_share", "load_max_over_mean",
                                "bounded_share"), load):
            self.sow(MOE_LOAD, name, jnp.mean(value))
        return hidden, head.astype(cd)

    # ------------------------------------------------------------ a layer
    def _layer(self, x, p, index: int, first_expert: int):
        w, cd = self.widths, self.compute_dtype
        t = x.shape[0]
        windowed, rotated = windowed_and_rotated(w, index)
        h = rms_norm(x, p["input_norm"], w.norm_eps)
        with jax.named_scope("mercury_moe"):
            with jax.named_scope("mercury_moe_route"):
                router_logits = jnp.dot(
                    h, p["router"].astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
        h = h.astype(cd)
        with jax.named_scope("mercury_attention"):
            def heads(name, n):
                return jnp.dot(h, p[name],
                               preferred_element_type=jnp.float32
                               ).reshape(t, n, w.head_dim)

            q, k, v = (heads("q", w.num_heads), heads("k", w.num_kv_heads),
                       heads("v", w.num_kv_heads))
            if rotated:
                q, k = (rotate_half(a, w.rope_theta) for a in (q, k))
            q = q * (w.head_dim ** -0.5)
            groups = w.num_heads // w.num_kv_heads
            q = q.astype(cd).reshape(t, w.num_kv_heads, groups, w.head_dim)
            q = q.transpose(1, 2, 0, 3)                     # [KV, G, T, hd]
            k, v = (a.astype(cd).transpose(1, 0, 2) for a in (k, v))
            window = w.window if windowed else None
            if self.use_pallas and splash_takes(t, w.head_dim):
                attn = splash_attention(q, k, v, window)
            else:
                attn = blockwise_attention(q, k, v, window)
            attn = attn.transpose(2, 0, 1, 3).reshape(t, -1)
            x = x + jnp.dot(attn, p["o"], preferred_element_type=jnp.float32)
        with jax.named_scope("mercury_moe"):
            h2 = rms_norm(x, p["post_norm"], w.norm_eps).astype(cd)
            y, load = routed_experts(h2, router_logits, p["gate"], p["up"],
                                     p["down"], w.top_k, first_expert)
        return x + y, load
