"""A causal decoder over integer tokens, trained on the next token: RMSNorm,
softmax attention under a causal or a windowed mask, a gated MLP that is
dense in the leading layers and top-k routed experts (of which this worker
may hold a share, beside shared experts) in the others, an untied head. The
widths are a published model's, chosen by name (``LM_WIDTHS``), and say
which of two mixers and which of two routing rules its layers have; what a
chip holds of it is the ``cut``.

**Two mixers**, by ``LMWidths.latent``. Without it grouped-query attention:
window and full layers mixed by ``period``, RoPE (rotate-half over the whole
head) on the windowed ones, none on the full ones. With it multi-head latent
attention: keys and values are up-projections of one normalised latent of
``kv_rank`` a token (``kv_a`` down, ``kv_norm``, ``kv_b`` up); a query and a
key have a position-free part of ``head_dim`` and a rotated part of
``rope_dim``, the key's rotated part one head that every query head reads;
values have ``v_head_dim``; no window, every layer rotated, on interleaved
pairs ``(2i, 2i + 1)``. A share of the heads (``cut``) holds those heads'
columns of ``q`` and ``kv_b`` and rows of ``o``; the latent projection and
its norm are whole on every holder.

**Two routing rules**, by ``LMWidths.router``. ``softmax``: the router reads
what attention reads (the layer's input after ``input_norm``), the ``top_k``
largest logits are chosen and weighted by the softmax over them. ``sigmoid``
(DeepSeek-V3's ``noaux_tc``): the router reads the MLP's input; scores are
sigmoids, the choice is by score plus a selection bias (a parameter whose
gradient is zero: it enters the choice alone; the balancing rule that moves
it in pre-training is not run), the weights are the unbiased scores
normalised over the chosen and scaled by ``routed_scale``.

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
query head; latent attention: one query head to each key/value head, queries
and keys of ``head_dim + rope_dim`` against values of ``v_head_dim``), key
blocks outside the causal or windowed mask not visited, the backward pass
recomputing probabilities. Elsewhere — and where the shapes are not the
kernel's (:func:`splash_takes`) — a blockwise XLA form: a block of queries
against the slice of keys its mask can reach, each block under
``jax.checkpoint``.

**Routing.** Every token is routed over all the layer's experts; the (choice,
token) pairs are sorted with those of the experts held here first, and every
array that follows the sort (the gather of the tokens' rows, the grouped
products' operands, the rows that return) has a static bound of rows, not
``k * T``: twice what uniform routing gives a holder of ``held`` of ``E``
experts and no fewer than a quarter of the pairs
(``models/moe.py::pair_bound``, from the shapes alone; a quarter of the
pairs for 8 of 64 and for 8 of 128). A row of tokens whose held
pairs outnumber the bound runs over all ``k * T`` rows under the other arm of
one ``lax.cond``: no pair is dropped and nothing has a capacity. A layer held
whole has no bound and no ``cond``.

**Precision.** Parameters in ``param_dtype``; matrix products in
``compute_dtype`` with float32 accumulation; the residual stream, RMSNorm
(the latent's too), the rotation, softmax and the token loss in float32; the
router's product, its sigmoid and its top-k in float32 from the normalised
float32 stream, so that as few of its near-ties as the products' rounding
allows fall the other way than in a float32 forward.

Scopes (metadata only; ``docs/OBSERVABILITY.md`` has the table): around the
rows ``mercury_rows`` (``lax.map``; the once-a-pass casts before it hold a
third of a percent of a step and have no scope); in a row ``mercury_embed``, ``mercury_norm`` (every
``rms_norm`` and the cast behind it), ``mercury_attention`` (rotation, the
attention, the transposes around it; the products into and out of the heads
under ``mercury_attention_proj`` inside it; latent attention whole under
``mercury_mla``, with what it adds around the kernel under
``mercury_mla_latent``), ``mercury_moe`` (router, grouping, expert products,
return, the shared experts) with ``mercury_moe_route`` and
``mercury_moe_shared`` nested in it, ``mercury_dense_mlp`` (a leading dense
layer's MLP); ``mercury_lm_head`` is the seam's. A branch's closing sum lies
in the branch's scope: a fusion is booked to its root. The last layer's load
and the share of all the routed layers that ran over the bounded rows are
sowed into the ``MOE_LOAD`` collection.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mercury_tpu.models.moe import MOE_LOAD, gated_mlp, routed_experts
from mercury_tpu.ops import mercury_kernels


class Latent(NamedTuple):
    """Multi-head latent attention's sizes: the latent a token's keys and
    values are projected up from, the rotated part of a query and a key,
    a value head (``LMWidths.head_dim`` is the position-free part)."""

    kv_rank: int
    rope_dim: int
    v_head_dim: int


class LMWidths(NamedTuple):
    """A published decoder's sizes, and which mixer, routing rule and
    expert activation its layers have. Without ``latent``, layer ``l`` is a
    full-attention layer without RoPE where ``l % period == 0`` and a
    windowed one with RoPE elsewhere (``window`` and ``period`` mean
    nothing to latent attention, whose layers are all full and rotated).
    The first ``dense_layers`` layers have a dense MLP of ``dense_width``
    in the experts' place; ``shared_width`` is the shared experts' widths
    together (0: none)."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    expert_width: int
    window: Optional[int]
    rope_theta: float
    period: int = 4
    norm_eps: float = 1e-6
    latent: Optional[Latent] = None
    router: str = "softmax"
    routed_scale: float = 1.0
    activation: str = "relu"
    shared_width: int = 0
    dense_layers: int = 0
    dense_width: int = 0


LM_WIDTHS: Dict[str, LMWidths] = {
    # https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json; 151,936 vocabulary rows.
    "smallthinker-21b-a3b": LMWidths(
        num_layers=52, d_model=2560, num_heads=28, num_kv_heads=4,
        head_dim=128, num_experts=64, top_k=6, expert_width=768,
        window=4096, rope_theta=1_500_000.0),
    # https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601
    # config.json (``deepseek_v3``); 128,256 vocabulary rows.
    "kanana-2-30b-a3b": LMWidths(
        num_layers=48, d_model=2048, num_heads=32, num_kv_heads=32,
        head_dim=128, num_experts=128, top_k=6, expert_width=768,
        window=None, rope_theta=1_000_000.0,
        latent=Latent(kv_rank=512, rope_dim=64, v_head_dim=128),
        router="sigmoid", routed_scale=2.448, activation="silu",
        shared_width=2 * 768, dense_layers=1, dense_width=6144),
}

_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}
_ROUTERS = ("softmax", "sigmoid")

#: Queries a block of the XLA form takes at a time.
QUERY_BLOCK = 512
#: Splash-attention block sizes on the TPU (q, kv, kv_compute).
SPLASH_BLOCKS = (1024, 1024, 512)


# ------------------------------------------------------------------ pieces
def windowed_and_rotated(widths: LMWidths, index: int) -> Tuple[bool, bool]:
    """Whether layer ``index`` attends under the window, and whether it
    rotates its queries and keys. Latent attention has one kind of layer:
    no window, always rotated. Grouped-query attention has two: the first
    layer of each period does neither (full attention, no position), the
    others do both."""
    if widths.latent is not None:
        return False, True
    kind = index % widths.period != 0
    return kind, kind


def rms_norm(x, scale, eps: float):
    with jax.named_scope("mercury_norm"):
        x = x.astype(jnp.float32)
        return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def normed_as(h, dtype):
    """A norm's float32 output ``h`` in ``dtype``. The compiler fuses the
    norm's last two products into the cast and books the fusion to the
    cast's path: so the cast lies in the norm's scope."""
    with jax.named_scope("mercury_norm"):
        return h.astype(dtype)


def _rope_angles(t: int, hd: int, theta: float, offset: int = 0):
    """The angles of rotate-half, ``[T, hd]`` float32: column ``i`` and
    column ``i + hd/2`` turn by ``position / theta ** (2i / hd)``."""
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv[None]
    return jnp.concatenate([angle, angle], -1)


def rotate_half(x, theta: float, offset: int = 0):
    """RoPE (rotate-half over the whole head) of ``x [T, H, hd]`` float32
    at positions ``offset .. offset + T - 1``."""
    t, hd = x.shape[0], x.shape[-1]
    angle = _rope_angles(t, hd, theta, offset)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def rope_tables(t: int, hd: int, theta: float):
    """``(cos, signed sin)`` of :func:`rotate_half`'s angles at positions
    ``0 .. t - 1``, each ``[T, hd]`` float32, the sine's first half negated:
    ``rotate_half(x) == x * cos + roll(x, hd/2) * signed sin`` to the bit
    (``(-a) * b == a * (-b)``). Made once a row, for every layer
    (``ops.rope_heads_pallas`` reads them)."""
    angle = _rope_angles(t, hd, theta)
    sign = jnp.where(jnp.arange(hd) < hd // 2, -1.0, 1.0)
    return jnp.cos(angle), jnp.sin(angle) * sign


def side_by_side(x):
    """``x`` with the interleaved pairs ``(2i, 2i + 1)`` of its last axis
    brought side by side as rotate-half pairs them, ``(i, i + half)``:
    ``x[..., [0, 2, 4, ..., 1, 3, 5, ...]]``, as a transpose (whose
    gradient is a transpose, no gather and no scatter).
    :func:`rotate_half` of it is the interleaved rotation of ``x``, permuted
    alike; a query and a key permuted alike have the dot product they
    had."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    return jnp.swapaxes(pairs, -1, -2).reshape(x.shape)


def _masked_attention(q, k, v, window: Optional[int], first_query=0,
                      first_key=0):
    """Softmax attention of queries at positions ``first_query ..`` over
    keys at ``first_key ..``: query ``i`` sees key ``j`` iff ``j <= i``
    and, under a window, ``j > i - window``. ``q [KV, G, Tq, hd]``
    (scaled), ``k [KV, Tk, hd]``, ``v [KV, Tk, vd]`` -> ``[KV, G, Tq,
    vd]``."""
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


def splash_takes(t: int, head_dim: int, v_head_dim: Optional[int] = None
                 ) -> bool:
    """Whether the kernel takes a sequence of ``t`` with queries and keys
    of ``head_dim`` and values of ``v_head_dim`` (``head_dim`` where not
    given): lanes of 128 for the sequence and for the values, whose size is
    the minor dimension of the output; half-lanes of 64 for queries and
    keys (192 = 128 + 64 under latent attention)."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    return t % 128 == 0 and v_head_dim % 128 == 0 and head_dim % 64 == 0


def grouped_query_attention(w: LMWidths, h, p, windowed: bool, rotated: bool,
                            attend, tables=None):
    """Grouped-query attention of ``h [T, D]`` (the compute precision) by
    the matrices ``p["q"]``, ``p["k"]``, ``p["v"]`` of the heads held ->
    the heads' outputs joined, ``[T, heads x head_dim]``. ``attend(q, k, v,
    window)`` is the attention itself (shapes as
    :func:`_masked_attention`). With ``tables`` (:func:`rope_tables`) each
    operand is made from its float32 product in one pass
    (``ops.rope_heads_pallas``: rotation, scale, cast and the head-major
    layout as one read and one write); without, by the plain forms."""
    cd, t, hd = h.dtype, h.shape[0], w.head_dim

    def product(name):
        with jax.named_scope("mercury_attention_proj"):
            return jnp.dot(h, p[name], preferred_element_type=jnp.float32)

    if tables is not None:
        def operand(name, rope, scale=1.0):
            return mercury_kernels.rope_heads_pallas(product(name), rope, hd,
                                                     scale, cd)

        rope = tables if rotated else None
        q = operand("q", rope, hd ** -0.5)
        k, v = operand("k", rope), operand("v", None)
        q = q.reshape(k.shape[0], -1, t, hd)                # [KV, G, T, hd]
    else:
        q, k, v = (product(name).reshape(t, -1, hd) for name in "qkv")
        if rotated:
            q, k = (rotate_half(a, w.rope_theta) for a in (q, k))
        q = q * (hd ** -0.5)
        kv_heads = k.shape[1]
        q = q.astype(cd).reshape(t, kv_heads, q.shape[1] // kv_heads, hd)
        q = q.transpose(1, 2, 0, 3)                         # [KV, G, T, hd]
        k, v = (a.astype(cd).transpose(1, 0, 2) for a in (k, v))
    attn = attend(q, k, v, w.window if windowed else None)
    return attn.transpose(2, 0, 1, 3).reshape(t, -1)


def pairs_side_by_side(w: LMWidths, p):
    """A latent-attention layer's matrices with the rotated columns of
    ``q`` (each head's) and of ``kv_a`` permuted so that the interleaved
    pairs ``(2i, 2i + 1)`` lie as rotate-half pairs them
    (:func:`side_by_side`): queries and keys permuted alike have the scores
    they had, and the rotation needs no strided lane. Once for all rows,
    like the cast."""
    lat, d = w.latent, w.d_model
    q = p["q"].reshape(d, -1, w.head_dim + lat.rope_dim)
    q = jnp.concatenate([q[..., :w.head_dim],
                         side_by_side(q[..., w.head_dim:])], -1)
    kv_a = jnp.concatenate([p["kv_a"][:, :lat.kv_rank],
                            side_by_side(p["kv_a"][:, lat.kv_rank:])], -1)
    return dict(p, q=q.reshape(d, -1), kv_a=kv_a)


def latent_attention(w: LMWidths, h, p, attend):
    """Multi-head latent attention of ``h [T, D]`` (the compute precision)
    by the matrices of the heads held, as :func:`pairs_side_by_side` left
    them -> the heads' outputs joined, ``[T, heads x v_head_dim]``.
    ``attend`` as in :func:`grouped_query_attention`."""
    lat, cd = w.latent, h.dtype
    t, hd, rd = h.shape[0], w.head_dim, lat.rope_dim

    def dot(x, name):
        with jax.named_scope("mercury_attention_proj"):
            return jnp.dot(x, p[name], preferred_element_type=jnp.float32)

    q = dot(h, "q").reshape(t, -1, hd + rd)
    n = q.shape[1]
    with jax.named_scope("mercury_mla_latent"):
        down = dot(h, "kv_a")                               # [T, rank + rd]
        latent = normed_as(rms_norm(down[:, :lat.kv_rank], p["kv_norm"],
                                    w.norm_eps), cd)
        up = dot(latent, "kv_b").reshape(t, n, hd + lat.v_head_dim)
        k_rope = down[:, None, lat.kv_rank:]                # one head
    q_rope, k_rope = (rotate_half(a, w.rope_theta)
                      for a in (q[..., hd:], k_rope))
    q = jnp.concatenate([q[..., :hd], q_rope], -1) * ((hd + rd) ** -0.5)
    with jax.named_scope("mercury_mla_latent"):
        k = jnp.concatenate(
            [up[..., :hd], jnp.broadcast_to(k_rope, (t, n, rd))], -1)
        v = up[..., hd:]
    # one query head to each key/value head: [H, 1, T, .], [H, T, .]
    q = q.astype(cd).transpose(1, 0, 2)[:, None]
    k, v = (a.astype(cd).transpose(1, 0, 2) for a in (k, v))
    attn = attend(q, k, v, None)                        # [H, 1, T, vd]
    return attn[:, 0].transpose(1, 0, 2).reshape(t, -1)


# ------------------------------------------------------------------- model
class _LayerParams(nn.Module):
    """One layer's parameters, by the names the plain reference reads:
    those of its mixer over the ``heads`` query heads held, and of a dense
    MLP (``dense``) or of the router with the ``held`` experts."""

    widths: LMWidths
    held: int
    heads: int
    dense: bool
    param_dtype: Any

    @nn.compact
    def __call__(self) -> Dict[str, jax.Array]:
        w, pd = self.widths, self.param_dtype
        d, f, hd = w.d_model, w.expert_width, w.head_dim
        normal = nn.initializers.lecun_normal()
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        ones = nn.initializers.ones
        shapes, lat = {}, w.latent
        if not self.dense:
            shapes["router"] = (d, w.num_experts)
        if lat is None:
            kv = self.heads * w.num_kv_heads // w.num_heads * hd
            shapes.update(q=(d, self.heads * hd), k=(d, kv), v=(d, kv),
                          o=(self.heads * hd, d))
        else:
            shapes.update(
                q=(d, self.heads * (hd + lat.rope_dim)),
                kv_a=(d, lat.kv_rank + lat.rope_dim),
                kv_b=(lat.kv_rank, self.heads * (hd + lat.v_head_dim)),
                o=(self.heads * lat.v_head_dim, d))
        if self.dense:
            shapes.update(dense_gate=(d, w.dense_width),
                          dense_up=(d, w.dense_width),
                          dense_down=(w.dense_width, d))
        elif w.shared_width:
            shapes.update(shared_gate=(d, w.shared_width),
                          shared_up=(d, w.shared_width),
                          shared_down=(w.shared_width, d))
        out = {name: self.param(name, normal, shape, pd)
               for name, shape in shapes.items()}
        for name in ("input_norm", "post_norm"):
            out[name] = self.param(name, ones, (d,), pd)
        if lat is not None:
            out["kv_norm"] = self.param("kv_norm", ones, (lat.kv_rank,), pd)
        if self.dense:
            return out
        if w.router == "sigmoid":
            # Seeded and then left where it is (its gradient is zero): a
            # stand-in for the bias a trained model's balancing left. At
            # 0.02, about the spacing of the sixth and seventh of 128
            # sigmoid scores, it moves some choices and not all.
            out["router_bias"] = self.param(
                "router_bias", nn.initializers.normal(0.02),
                (w.num_experts,), pd)
        for name, shape in (("gate", (self.held, d, f)),
                            ("up", (self.held, d, f)),
                            ("down", (self.held, f, d))):
            out[name] = self.param(name, stacked, shape, pd)
        return out


#: What stays in ``param_dtype`` when a layer's matrices are cast to the
#: compute precision: the norms' gains, the router and its bias.
_KEPT = ("input_norm", "post_norm", "kv_norm", "router", "router_bias")


class CausalDecoder(nn.Module):
    """``tokens [N, T]`` int -> ``(hidden [N, T, D], head [D, V])``: the
    final hidden states (normalised) and the untied head, both in
    ``compute_dtype``; the logits are their product, which the caller
    takes a row at a time. ``num_classes`` is the vocabulary rows held
    (embedding and head alike); ``cut`` is ``(layers kept, first expert
    held, experts held)`` or, with a share of the query heads too,
    ``(layers, first expert, experts, first head, heads)``; None the whole
    model. Which heads and which experts a share is decides nothing the
    heads compute (``first head`` names the columns of the published
    matrices that these are); ``first expert`` places the held experts
    among the router's outputs. Training and inference mode are one (no
    dropout, no running statistic)."""

    num_classes: int
    widths: LMWidths
    cut: Optional[Tuple[int, ...]] = None
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_pallas: bool = False

    def _sizes(self) -> Tuple[int, int, int, int]:
        """``(layers, first expert, experts held, heads held)``."""
        w = self.widths
        cut = tuple(self.cut or (w.num_layers, 0, w.num_experts))
        if len(cut) == 3:
            cut += (0, w.num_heads)
        if len(cut) != 5:
            raise ValueError(f"cut {self.cut} is neither (layers, first "
                             "expert, experts) nor (layers, first expert, "
                             "experts, first head, heads)")
        layers, first, held, first_head, heads = (int(v) for v in cut)
        if not (1 <= layers <= w.num_layers and 0 <= first
                and held >= 1 and first + held <= w.num_experts):
            raise ValueError(f"cut {self.cut} does not lie inside "
                             f"{w.num_layers} layers and {w.num_experts} "
                             "experts")
        group = w.num_heads // w.num_kv_heads
        if not (0 <= first_head and heads >= 1
                and first_head + heads <= w.num_heads
                and first_head % group == 0 and heads % group == 0):
            raise ValueError(
                f"cut {self.cut} does not hold whole key/value groups "
                f"({group} query heads each) of {w.num_heads} heads")
        if w.router not in _ROUTERS or w.activation not in _ACTIVATIONS:
            raise ValueError(f"widths name router {w.router!r} (one of "
                             f"{_ROUTERS}) and activation {w.activation!r} "
                             f"(one of {sorted(_ACTIVATIONS)})")
        return layers, first, held, heads

    def _one_pass(self) -> bool:
        """Whether a layer's three operands of the attention are made from
        their products in one pass: on the TPU, at widths the kernel takes
        (grouped-query heads of whole lanes; latent attention's 128 + 64,
        rotated in part, are not)."""
        w = self.widths
        width, rope = (
            (w.head_dim, w.head_dim) if w.latent is None else
            (w.head_dim + w.latent.rope_dim, w.latent.rope_dim))
        return self.use_pallas and mercury_kernels.rope_heads_takes(width,
                                                                    rope)

    def operand_sites(self) -> Tuple[int, int]:
        """``(one-pass, plain)``: how many operands of the attention (three
        a layer) one forward makes in the one-pass form, and how many by
        the plain forms."""
        sites = 3 * self._sizes()[0]
        return (sites, 0) if self._one_pass() else (0, sites)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        w, pd, cd = self.widths, self.param_dtype, self.compute_dtype
        layers, first, held, heads = self._sizes()
        routed = layers - min(layers, w.dense_layers)
        # Rows of unit scale: a seeded stand-in for a trained model, whose
        # residual stream carries the token. At the customary 0.02 the
        # branches' outputs (unit scale under these initialisers) are fifty
        # times the embedding, every position's hidden state is the
        # sequence's mean but for a fiftieth, and the router sends all of a
        # sequence's tokens to the same ``top_k`` experts.
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (self.num_classes, w.d_model), pd)
        blocks = [_LayerParams(w, held, heads, i < w.dense_layers, pd,
                               name=f"layer{i}")()
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
        blocks = [{name: a if name in _KEPT else a.astype(cd)
                   for name, a in block.items()} for block in blocks]
        if w.latent is not None:
            blocks = [pairs_side_by_side(w, block) for block in blocks]
        one_pass = self._one_pass()

        def row(ids):
            # the rotation's cosines and signed sines, once for all the
            # row's layers, where the operands are made in one pass (made
            # outside the map, once a pass, they cost the v5e's compiler
            # 600 MiB of peak: it then runs the pool's rows after the
            # train pass and holds their stack across it; PERF.md §6)
            tables = (rope_tables(tokens.shape[1], w.head_dim, w.rope_theta)
                      if one_pass else None)
            # the residual stream stays float32 (each layer adds its two
            # float32-accumulated products to it unrounded): what the
            # router reads then differs from the plain float32 forward by
            # the products' input rounding alone, not by eight roundings
            # of x itself, and fewer near-ties of its top-k fall the
            # other way
            with jax.named_scope("mercury_embed"):
                x = embed[ids].astype(jnp.float32)
            bounded, load = 0.0, ()
            for i, block in enumerate(blocks):
                x, routing = jax.checkpoint(functools.partial(
                    self._layer, index=i, first_expert=first))(x, block,
                                                               tables)
                if routing:     # a dense layer routes nothing
                    bounded += routing[2] / routed
                    load = routing
            # the last routed layer's load, with all of them's bounded share
            return (normed_as(rms_norm(x, final_norm, w.norm_eps), cd),
                    load and (*load[:2], bounded, *load[3:]))

        # what lax.map itself does (a row sliced out, its outputs written
        # into the stack) and whatever else of a row's body bears no leaf's
        # name is the scope's own
        with jax.named_scope("mercury_rows"):
            hidden, load = lax.map(row, tokens)
        for name, value in zip(("held_pair_share", "load_max_over_mean",
                                "bounded_share", "bias_moved_share"), load):
            self.sow(MOE_LOAD, name, jnp.mean(value))
        return hidden, head.astype(cd)

    # ------------------------------------------------------------ a layer
    def _layer(self, x, p, tables, index: int, first_expert: int):
        w, cd = self.widths, self.compute_dtype
        windowed, rotated = windowed_and_rotated(w, index)
        dense = index < w.dense_layers
        h = rms_norm(x, p["input_norm"], w.norm_eps)
        if not dense and w.router == "softmax":
            # this rule's router reads what attention reads
            with jax.named_scope("mercury_moe"):
                router_logits = self._router_logits(h, p)
        h = normed_as(h, cd)
        with jax.named_scope("mercury_attention"):
            if w.latent is None:
                attn = grouped_query_attention(w, h, p, windowed, rotated,
                                               self._attend, tables)
            else:
                with jax.named_scope("mercury_mla"):
                    attn = latent_attention(w, h, p, self._attend)
            with jax.named_scope("mercury_attention_proj"):
                x = x + jnp.dot(attn, p["o"],
                                preferred_element_type=jnp.float32)
        activation = _ACTIVATIONS[w.activation]
        # the MLP's norm lies in the MLP's scope, as it always has
        with jax.named_scope("mercury_dense_mlp" if dense else "mercury_moe"):
            h2 = rms_norm(x, p["post_norm"], w.norm_eps)
            if dense:
                return x + gated_mlp(normed_as(h2, cd), p["dense_gate"],
                                     p["dense_up"], p["dense_down"],
                                     activation), ()
            rule = {}
            if w.router == "sigmoid":
                router_logits = self._router_logits(h2, p)
                rule = dict(bias=p["router_bias"], scale=w.routed_scale)
            if w.shared_width:
                rule["shared"] = (p["shared_gate"], p["shared_up"],
                                  p["shared_down"])
            y, load = routed_experts(
                normed_as(h2, cd), router_logits, p["gate"], p["up"],
                p["down"], w.top_k, first_expert, activation=activation,
                **rule)
            return x + y, load

    @staticmethod
    def _router_logits(h, p):
        """The router's product in float32, from the float32 stream."""
        with jax.named_scope("mercury_moe_route"):
            return jnp.dot(h, p["router"].astype(jnp.float32),
                           precision=lax.Precision.HIGHEST)

    def _attend(self, q, k, v, window: Optional[int]):
        """The attention itself, shapes as :func:`_masked_attention`: the
        kernel where it runs and takes these shapes, else blockwise."""
        if self.use_pallas and splash_takes(q.shape[2], q.shape[-1],
                                            v.shape[-1]):
            return splash_attention(q, k, v, window)
        return blockwise_attention(q, k, v, window)
