"""The ``kanana`` family against the program, at a small size on the CPU
(``chip_smoke.register_tiny_latent_lm``: d_model 64, 4 heads of 128 + 64
against 128 over a latent of 32, of which 2 are held; 16 SwiGLU experts
top-3 under the sigmoid rule with a selection bias, of which 4 are held; a
shared expert of 64; one leading dense layer of 96; vocabulary 96; seeded
random weights with the norms' gains and the bias moved off their seeds),
and the cell's whole command rehearsed (``run.run_cell(...,
rehearsal=...)``). On the chip the same code runs at the published widths;
nothing here is a device measurement."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import register_tiny_latent_lm
from mercury_tpu.sampling.importance import sequence_rows, token_logits
from perfbench import reference, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kn2-is-8k"
VOCAB, T = 96, 32
ARCH = {"file": "perfbench/references/kanana.py", "kv_lora_rank": 32,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_theta": 10000.0, "top_k": 3, "routed_scaling_factor": 2.448,
        "first_expert_held": 0, "rms_norm_eps": 1e-6, "query_block": 8,
        "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9},
        "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}}
FAMILY = reference.family(ARCH)
#: The cell's whole command at the small size. float32 on both sides: only
#: the order of sums differs (blocks, the grouped products against the dense
#: loop), and a routing tie would show as a gap of a whole expert.
TINY = {
    "train_config": {"model": register_tiny_latent_lm(),
                     "model_cut": [3, 0, 4, 0, 2], "num_classes": VOCAB,
                     "seq_len": T, "batch_size": 2, "presample_batches": 3,
                     "compute_dtype": "float32", "base_lr": 1e-3,
                     "log_every": 10},
    "steps_per_call": 10, "trace_calls": 2,
    "reference": ARCH,
    "check": {"sample_rows": 2, "block_rows": 1, "train_block_rows": 1,
              "logit_gap_limit": 1e-4, "eval_loss_gap_limit": 1e-3,
              "loss_gap_limit": 1e-4, "grad_norm_gap_limit": 1e-3,
              "update_norm_gap_limit": 0.05, "weight_gap_limit": 1e-4,
              "window_update_rms_floor": 1e-5},
}


def _model(compute_dtype="float32", first=0, first_head=0):
    from mercury_tpu.models import create_model

    return create_model(register_tiny_latent_lm(), num_classes=VOCAB,
                        compute_dtype=compute_dtype,
                        cut=(3, first, 4, first_head, 2))


def _data(first=0, seed=0):
    """Parameters (gains and bias off their seeds), tokens and labels."""
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (3, T)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, VOCAB, (3, T)), jnp.int32)
    params = _model(first=first).init(jax.random.key(seed), tokens,
                                      train=False)["params"]
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape),
                                        a.dtype) if a.ndim == 1 else a,
        params)
    return params, tokens, labels


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-12))


@pytest.mark.parametrize("first, first_head", [(0, 0), (8, 2)])
def test_the_program_in_float32_is_the_reference(first, first_head):
    """Logits, per-sequence loss and the gradient of every leaf, with
    experts 0-3 and 8-11 held (heads 0-1, 2-3: which heads a share is
    decides nothing they compute): float32 against float32 at ``highest``,
    so what differs is the order of sums (1e-5 of the largest entry). The
    selection bias's gradient is exactly zero on both sides."""
    arch = dict(ARCH, first_expert_held=first)
    params, tokens, labels = _data(first)
    model = _model(first=first, first_head=first_head)

    def program(p):
        return sequence_rows(
            model.apply({"params": p}, tokens, train=True), labels)[:, 0]

    def plain(p):
        return FAMILY.example_loss(
            FAMILY.forward(p, None, tokens, arch), labels)

    with jax.default_matmul_precision("highest"):
        logits = token_logits(
            model.apply({"params": params}, tokens, train=False))
        want = FAMILY.forward(params, None, FAMILY.prepare(tokens, arch),
                              arch)
        assert _rel(logits, want) < 1e-5
        np.testing.assert_allclose(program(params), plain(params), rtol=1e-6)
        got = jax.grad(lambda p: program(p).sum())(params)
        ref = jax.grad(lambda p: plain(p).sum())(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(a).any() and not np.asarray(b).any(), name
            continue
        assert _rel(a, b) < 1e-5, name
        assert float(jnp.abs(b).max()) > 0, name


def test_bfloat16_lies_inside_the_band_and_fp8_outside():
    """The program in bfloat16 against the float32 reference: logits within
    6 % (rms over rms; it reads 0.02-0.03: bfloat16 keeps 8 bits, three
    layers deep, and a near-tie of the biased top-k that falls the other
    way moves a token's whole expert output), and the reference in fp8
    (reads 0.1-0.2), put in the program's place, outside it."""
    from perfbench import check

    params, tokens, _ = _data()
    with jax.default_matmul_precision("highest"):
        want = FAMILY.forward(params, None, tokens, ARCH)
        lower = FAMILY.forward(params, None, tokens, ARCH, "fp8")
    got = token_logits(
        _model("bfloat16").apply({"params": params}, tokens, train=False))
    sound, control = (check.logit_gap(a, want) for a in (got, lower))
    assert sound < 0.06 < control, (sound, control)


def test_the_shares_add_up_to_the_uncut_reference():
    """One layer's two sub-layers, the program's shares against the UNCUT
    plain reference: what the four holders of one head each add to the
    stream sums to the reference's four-head attention, and what the sixteen
    holders of one expert each add, with the shared expert counted once,
    sums to the reference's sixteen-expert layer."""
    from mercury_tpu.models import LM_WIDTHS, create_model, decoder, moe

    w = LM_WIDTHS[register_tiny_latent_lm()]
    rng = np.random.default_rng(2)
    p = create_model(register_tiny_latent_lm(), num_classes=VOCAB,
                     compute_dtype="float32").init(
        jax.random.key(2), jnp.zeros((1, T), jnp.int32),
        train=False)["params"]["layer1"]
    p = {name: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype)
         if a.ndim == 1 else a for name, a in p.items()}
    x = jnp.asarray(rng.standard_normal((1, T, 64)), jnp.float32)
    lat = w.latent
    qk, kv = w.head_dim + lat.rope_dim, w.head_dim + lat.v_head_dim
    with jax.default_matmul_precision("highest"):
        want = FAMILY._mixer(x, p, ARCH, None)[0]
        h = decoder.rms_norm(x[0], p["input_norm"], w.norm_eps)
        parts = 0.0
        for first in range(w.num_heads):
            share = decoder.pairs_side_by_side(w, dict(
                p, q=p["q"][:, first * qk:(first + 1) * qk],
                kv_b=p["kv_b"][:, first * kv:(first + 1) * kv],
                o=p["o"][first * lat.v_head_dim:
                         (first + 1) * lat.v_head_dim]))
            parts = parts + decoder.latent_attention(
                w, h, share, decoder.blockwise_attention) @ share["o"]
        assert _rel(parts, want) < 1e-5

        want = FAMILY._mlp(x, p, ARCH, None)[0]
        m = decoder.rms_norm(x[0], p["post_norm"], w.norm_eps)
        logits = m @ p["router"]
        parts = moe.gated_mlp(m, p["shared_gate"], p["shared_up"],
                              p["shared_down"], jax.nn.silu)
        for first in range(16):
            held = slice(first, first + 1)
            y, (share, *_) = moe.routed_experts(
                m, logits, p["gate"][held], p["up"][held], p["down"][held],
                w.top_k, first, bias=p["router_bias"], scale=w.routed_scale,
                activation=jax.nn.silu)
            parts = parts + y
            assert 0.0 <= float(share) < 1.0
        assert _rel(parts, want) < 1e-5


def test_the_config_counts_what_the_issue_counts():
    """The constants of the configuration file against the arithmetic they
    were sized by: 412.5 MFLOP a token, 3.379 TFLOP a sequence; 330,589,184
    parameters held (heads 0-7 of the 32); every catalog key copied or
    listed as reduced."""
    cfg = json.load(open(os.path.join(
        REPO, "perfbench", "configs", "kanana-2-30b-a3b.json")))
    fam = reference.family(cfg["reference"])
    assert fam.fwd_flops_per_example(cfg) == cfg["fwd_flops_per_example"]
    assert cfg["fwd_flops_per_example"] / cfg["seq_len"] == 412_496_384
    share = (2.0 * (cfg["qk_head_dim"] + cfg["v_head_dim"])
             * fam.attention_pairs(cfg) / cfg["fwd_flops_per_example"])
    assert 0.25 < share < 0.26           # scores and values alone: 25.4 %
    fields = cfg["train_config"]
    from mercury_tpu.models import LM_WIDTHS, create_model

    w = LM_WIDTHS[fields["model"]]
    for key, value in (
            ("hidden_size", w.d_model), ("qk_nope_head_dim", w.head_dim),
            ("qk_rope_head_dim", w.latent.rope_dim),
            ("qk_head_dim", w.head_dim + w.latent.rope_dim),
            ("v_head_dim", w.latent.v_head_dim),
            ("kv_lora_rank", w.latent.kv_rank),
            ("moe_intermediate_size", w.expert_width),
            ("moe_router_width", w.num_experts),
            ("num_experts_per_tok", w.top_k),
            ("routed_scaling_factor", w.routed_scale),
            ("first_k_dense_replace", w.dense_layers),
            ("intermediate_size", w.dense_width),
            ("rope_theta", w.rope_theta), ("rms_norm_eps", w.norm_eps)):
        assert cfg[key] == value, key
    assert cfg["n_shared_experts"] * cfg["moe_intermediate_size"] \
        == w.shared_width
    assert (cfg["scoring_func"], cfg["hidden_act"]) == (w.router,
                                                        w.activation)
    assert cfg["q_lora_rank"] is None and cfg["n_group"] == 1
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["num_attention_heads"]) \
        == (w.num_layers, w.num_experts, w.num_heads)
    layers, first, held, first_head, heads = fields["model_cut"]
    assert (layers, held, heads) == (cfg["num_hidden_layers"],
                                     cfg["n_routed_experts"],
                                     cfg["num_attention_heads"])
    assert first == cfg["moe_first_expert_held"] \
        == cfg["reference"]["first_expert_held"]
    assert first_head == cfg["first_head_held"]
    for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "routed_scaling_factor",
                "rms_norm_eps"):
        assert cfg["reference"][key] == cfg[key], key
    assert cfg["reference"]["top_k"] == cfg["num_experts_per_tok"]
    model = create_model(fields["model"], num_classes=fields["num_classes"],
                         cut=tuple(fields["model_cut"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 330_589_184
    assert "router" not in shapes["layer0"]
    assert shapes["layer1"]["router_bias"].shape == (128,)


# ---------------------------------------------------------- the rehearsal
def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearsed_is_correct(capsys):
    """The whole command at the small size, blocks of one row on every side
    of the reference: ``correct``."""
    result = run.run_cell(CELL, seed=2 ** 31 + 11, seconds=0.3, trace=False,
                          rehearsal=TINY)
    assert result["correct"] and result["attempted"] >= 10
    assert _last_line(capsys) == result


def _route(monkeypatch, change):
    """``route_top_k`` with its arguments changed by ``change(logits, bias,
    scale) -> (bias, scale)``."""
    from mercury_tpu.models import moe

    real = moe.route_top_k

    def routed(logits, top_k, first, held, bias, scale):
        return real(logits, top_k, first, held, *change(logits, bias, scale))

    monkeypatch.setattr(moe, "route_top_k", routed)


def _the_bias_dropped_from_the_choice(monkeypatch):
    _route(monkeypatch, lambda r, bias, scale: (jnp.zeros_like(bias), scale))


def _the_scaling_factor_left_out(monkeypatch):
    _route(monkeypatch, lambda r, bias, scale: (bias, 1.0))


def _weights_taken_from_the_biased_scores(monkeypatch):
    from mercury_tpu.models import moe

    real = moe.route_top_k

    def biased(logits, top_k, first, held, bias, scale):
        weights, *rest = real(logits, top_k, first, held, bias, scale)
        picked = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)[0]
        return (scale * picked / jnp.sum(picked, -1, keepdims=True), *rest)

    monkeypatch.setattr(moe, "route_top_k", biased)


def _k_rope_rotated_at_each_heads_own_positions(monkeypatch):
    from mercury_tpu.models import decoder

    real = decoder.rotate_half

    def per_head(x, theta, offset=0):
        if x.shape[1] == 1:     # the one shared key head: as it should be
            return real(x, theta, offset)
        return jnp.concatenate(
            [real(x[:, h:h + 1], theta, offset + h)
             for h in range(x.shape[1])], 1)

    monkeypatch.setattr(decoder, "rotate_half", per_head)


def _the_shared_expert_left_out(monkeypatch):
    from mercury_tpu.models import decoder

    real = decoder.routed_experts
    monkeypatch.setattr(
        decoder, "routed_experts",
        lambda *a, shared=None, **kw: real(*a, **kw))


def _the_latents_norm_left_out(monkeypatch):
    from mercury_tpu.models import decoder

    real = decoder.rms_norm
    monkeypatch.setattr(
        decoder, "rms_norm",
        lambda x, scale, eps: (x.astype(jnp.float32) if scale.shape == (32,)
                               else real(x, scale, eps)))


@pytest.mark.parametrize("fault", [
    _the_bias_dropped_from_the_choice,
    _weights_taken_from_the_biased_scores,
    _k_rope_rotated_at_each_heads_own_positions,
    _the_shared_expert_left_out,
    _the_scaling_factor_left_out,
    _the_latents_norm_left_out,
], ids=["bias", "biased_weights", "k_rope", "shared", "scale", "kv_norm"])
def test_this_models_own_faults_are_not_correct(capsys, monkeypatch, fault):
    """The program broken underneath in ways that are this model's own:
    ``correct`` comes out false, by the inference check and by the replayed
    loss at least."""
    fault(monkeypatch)
    result = run.run_cell(CELL, seed=5, seconds=0.3, trace=False,
                          rehearsal=TINY)
    assert result["correct"] is False
    out = capsys.readouterr().out
    failed = {line.split()[2].rstrip(":") for line in out.splitlines()
              if line.startswith("[perfbench] check") and "FAIL" in line}
    assert {"logit_gap", "loss_gap"} <= failed, failed


# ------------------------------------------------------------- new metrics
def test_the_roofline_metrics_counts_are_the_familys():
    """``mla_roofline_share``'s FLOPs and bytes a step are what the family
    file's count function gives for this cell's pool and batch: the
    required work (2 x (192 + 128) FLOPs a causal pair a head, each operand
    once), whatever route computes it."""
    from perfbench import cell as cell_mod

    c = cell_mod.Cell(CELL)
    spec = cell_mod.layer_metric("mla_roofline_share")
    fields = c.train_config_fields(seed=1, trace=False)
    pool = fields["batch_size"] * fields["presample_batches"]
    counted = spec["counted_for"]
    assert (counted["rows_forward"], counted["rows_trained"]) \
        == (pool, fields["batch_size"])
    fam = reference.family(c.config["reference"])
    flops, moved = fam.attention_kernel_work(c.config, pool,
                                             fields["batch_size"])
    assert spec["args"]["flops_per_step"] == flops
    assert spec["args"]["bytes_per_step"] == moved
    pairs = 8 * 8192 * 8193 / 2 * 5
    assert fam.attention_pairs(c.config) == pairs
    assert flops == 13 * 2 * 320 * pairs


def test_the_new_metrics_are_files_over_the_reducers_that_exist():
    """The five entries this configuration's PR appended list only its cell,
    and each file names a reducer and arguments that the reducer reads off a
    made capture: the scopes by ``path_scope_share``, the counter by
    ``instant_arg``."""
    from perfbench import cell as cell_mod
    from tests.perfbench.test_smallthinker import _fake_capture

    names = ("mla_share", "mla_latent_share", "moe_shared_share",
             "mla_roofline_share", "moe_bias_moved_share")
    entries = {m["name"]: m for m in cell_mod.manifest()["per_layer"]}
    for name in names:
        assert entries[name]["workloads"] == [CELL], name
        spec = cell_mod.layer_metric(name)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == entries[name][key], (name, key)
    assert {m["name"] for m in cell_mod.Cell(CELL).per_layer()} >= set(names)
    capture = _fake_capture([
        ("fusion.1", "jit(step)/mercury_scoring/mercury_attention/"
                     "mercury_mla/dot_general", 100.0),
        ("fusion.2", "jit(step)/mercury_train/mercury_attention/mercury_mla/"
                     "mercury_mla_latent/dot_general", 50.0),
        ("custom-call.3", "jit(step)/mercury_train/transpose(jvp("
                          "mercury_mla))/splash_mqa_dkv_no_residuals", 50.0),
        ("fusion.4", "jit(step)/mercury_train/mercury_moe/"
                     "mercury_moe_shared/dot_general", 100.0),
        ("fusion.5", "jit(step)/mercury_optimizer/add", 200.0),
    ])
    ctx = dict(capture=capture, steps=2, peak_flops=None, spans=[
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"held_pair_share": 0.06, "bias_moved_share": 0.25}}])
    got = {name: cell_mod.reducer(spec["reducer"])(ctx, **spec["args"])
           for name in names for spec in [cell_mod.layer_metric(name)]}
    assert got["mla_share"] == pytest.approx(40.0)
    assert got["mla_latent_share"] == pytest.approx(10.0)
    assert got["moe_shared_share"] == pytest.approx(20.0)
    assert got["moe_bias_moved_share"] == pytest.approx(0.25)
    assert got["mla_roofline_share"] is None     # no peak off the chip
