"""Plain reference of the family ``transformer_classifier``: the pre-LN
Transformer encoder over feature sequences ``[N, T, F]`` that the program
trains as ``model="transformer"`` (Vaswani et al. 2017 with the layer norm
before each sublayer, Xiong et al. 2020): a dense input projection plus
learned positions, blocks of multi-head softmax attention (separate
``query``/``key``/``value``/``proj`` projections, scores over sqrt of the
head size) and a GELU MLP (tanh approximation), each added to its input,
a final LayerNorm, the mean over positions, a dense head. LayerNorm has
scale and bias and eps 1e-6. No dropout and no running statistic: training
and inference mode are one, ``model_state`` is ignored, and a row's logits
depend on that row alone (``ROWS_INDEPENDENT``: the training side of the
reference may go by row blocks, ``check.train_block_rows``).

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``;
it reads a parameter tree by the names flax gives the program's modules
(``embed``, ``pos_embed``, ``block<i>`` holding ``LayerNorm_0``, ``query``,
``key``, ``value``, ``proj``, ``LayerNorm_1``, ``Dense_0``, ``Dense_1``;
``LayerNorm_0``, ``head``) — names and shapes only. The depth is the
tree's; the configuration's ``reference`` group (``arch``) gives
``num_heads``, which no shape shows. The dataset's rows are the inputs, and
the job runs with ``augmentation="none"``.

It stands where ``smallcnn`` stands: a rehearsal family of the CPU tests,
in no cell on the chip.

**Where the fp8 control rounds** (``quantize="fp8"``): the inputs and the
weights of every dense layer (input projection, the four attention
projections, both MLP layers, the head) and both operands of the two
attention products (queries and keys; probabilities and values), e4m3 with
one scale per tensor. LayerNorm, softmax, GELU, the residual sums and the
pool stay in float32.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference import round_to
from perfbench.references.resnet import (  # noqa: F401  (class NLL)
    _dense, eval_example_loss, example_loss)

LN_EPS = 1e-6
#: LayerNorm normalises within a position, attention mixes within a row.
ROWS_INDEPENDENT = True


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _attention(h, p, num_heads: int, quantize):
    n, t, d = h.shape
    heads = (n, t, num_heads, d // num_heads)
    q, k, v = (_dense(h, p[name], quantize).reshape(heads)
               for name in ("query", "key", "value"))
    scores = jnp.einsum("nqhd,nkhd->nhqk", round_to(q, quantize),
                        round_to(k, quantize),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(scores / jnp.sqrt(jnp.float32(heads[-1])), axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", round_to(probs, quantize),
                     round_to(v, quantize), precision=lax.Precision.HIGHEST)
    return _dense(out.reshape(n, t, d), p["proj"], quantize)


def _block(x, p, num_heads: int, quantize):
    x = x + _attention(_layer_norm(x, p["LayerNorm_0"]), p, num_heads,
                       quantize)
    h = _dense(_layer_norm(x, p["LayerNorm_1"]), p["Dense_0"], quantize)
    return x + _dense(jax.nn.gelu(h, approximate=True), p["Dense_1"],
                      quantize)


# ------------------------------------------------------------ the interface
def prepare(raw_rows, arch: Mapping[str, Any]):
    """The dataset's rows are the model's inputs."""
    return raw_rows.astype(jnp.float32)


def augment(key, inputs, arch: Mapping[str, Any]):
    """None: the job runs with ``augmentation="none"``."""
    return inputs


def forward(params, model_state, inputs, arch: Mapping[str, Any],
            quantize: Optional[str] = None):
    """Logits ``[N, classes]`` (float32) for sequences ``[N, T, F]``."""
    num_heads = int(arch["num_heads"])
    with jax.default_matmul_precision("highest"):
        x = _dense(inputs.astype(jnp.float32), params["embed"], quantize)
        x = x + params["pos_embed"][None, :x.shape[1]]
        for i in range(sum(1 for name in params if name.startswith("block"))):
            x = _block(x, params[f"block{i}"], num_heads, quantize)
        x = jnp.mean(_layer_norm(x, params["LayerNorm_0"]), axis=1)
        return _dense(x, params["head"], quantize)


def fwd_flops_per_example(config: Mapping[str, Any]) -> float:
    """2 x MACs of every dense layer and of both attention products for one
    sequence (LayerNorm, softmax, GELU and the pool left out, as is the
    convention for model FLOPs). ``config``: ``seq_len``, ``feature_dim``,
    ``num_classes`` and, in ``reference``, ``d_model``, ``num_layers``,
    ``mlp_ratio``."""
    arch = config["reference"]
    t, d = int(config["seq_len"]), int(arch["d_model"])
    block = (4 * t * d * d                              # q, k, v, proj
             + 2 * t * t * d                            # q.k and p.v
             + 2 * t * d * int(arch["mlp_ratio"]) * d)  # the MLP
    macs = (t * int(config["feature_dim"]) * d
            + int(arch["num_layers"]) * block
            + d * int(config["num_classes"]))
    return 2.0 * macs
