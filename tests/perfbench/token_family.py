"""A fixture family, reference side only: integer tokens in, a loss per
sequence out. The inputs are ``int32 [N, T]`` tokens, the labels ``int32
[N, T]`` (the inputs shifted by one), the forward an embedding and a tied
head (``[N, T, V]`` float32 logits), the per-example loss the mean over
``T`` of the token cross-entropy. No program stands behind it: it shows
that the shared functions of ``perfbench/reference.py``, ``replay.py`` and
``check.py`` take such a family as they are. A row's logits depend on that
row alone (``ROWS_INDEPENDENT``), so the training side may go by row
blocks. The fp8 control rounds both operands of the head's product (one
scale per tensor: over a block, where the training side goes by blocks).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference import round_to

ROWS_INDEPENDENT = True


def prepare(raw_rows, arch):
    """Token rows are the model's inputs."""
    return raw_rows


def augment(key, inputs, arch):
    return inputs


def forward(params, model_state, inputs, arch, quantize=None):
    table = params["embed"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("ntd,vd->ntv", round_to(table[inputs], quantize),
                          round_to(table, quantize),
                          precision=lax.Precision.HIGHEST)


def example_loss(outputs, labels):
    logp = jax.nn.log_softmax(outputs.astype(jnp.float32), axis=-1)
    token = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(token, axis=-1)


def eval_example_loss(outputs, labels):
    """On the device, as a language model's would be: a block's logits
    never come to the host."""
    return np.asarray(example_loss(outputs, jnp.asarray(labels)), np.float64)


def fwd_flops_per_example(config):
    return 2.0 * config["seq_len"] * config["reference"]["d_model"] \
        * config["vocab"]
