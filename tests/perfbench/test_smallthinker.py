"""The ``smallthinker`` family against the program, at a small size on the
CPU (d_model 64, 4 query heads on 1 key/value head of 16, window 8 at T 32,
16 experts top-3 of which 4 are held, vocabulary 96; seeded random weights
with the norms' gains moved off 1), and the cell's whole command rehearsed
(``run.run_cell(..., rehearsal=...)``). On the chip the same code runs at
the published widths; nothing here is a device measurement."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import register_tiny_lm
from mercury_tpu.sampling.importance import sequence_rows, token_logits
from perfbench import reference, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "st21b-is-8k"
VOCAB, T = 96, 32
ARCH = {"file": "perfbench/references/smallthinker.py", "head_dim": 16,
        "num_key_value_heads": 1, "rope_theta": 10000.0,
        "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
        "sliding_window_size": 8, "top_k": 3, "first_expert_held": 0,
        "rms_norm_eps": 1e-6, "query_block": 8,
        "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9},
        "adam": {"b1": 0.9, "b2": 0.999, "eps": 1e-8}}
FAMILY = reference.family(ARCH)
#: The cell's whole command at the small size. float32 on both sides: only
#: the order of sums differs (blocks, the grouped products against the dense
#: loop), and a routing tie would show as a gap of a whole expert.
TINY = {
    "train_config": {"model": register_tiny_lm(), "model_cut": [4, 0, 4],
                     "num_classes": VOCAB, "seq_len": T, "batch_size": 2,
                     "presample_batches": 3, "compute_dtype": "float32",
                     "base_lr": 1e-3, "log_every": 10},
    "steps_per_call": 10, "trace_calls": 2,
    "reference": ARCH,
    "check": {"sample_rows": 2, "block_rows": 1, "train_block_rows": 1,
              "logit_gap_limit": 1e-4, "eval_loss_gap_limit": 1e-3,
              "loss_gap_limit": 1e-4, "grad_norm_gap_limit": 1e-3,
              "update_norm_gap_limit": 0.05, "weight_gap_limit": 1e-4,
              "window_update_rms_floor": 1e-5},
}


def _model(compute_dtype="float32", first=0):
    from mercury_tpu.models import create_model

    return create_model(register_tiny_lm(), num_classes=VOCAB,
                        compute_dtype=compute_dtype, cut=(4, first, 4))


def _data(first=0, seed=0):
    """Parameters (gains off 1), tokens and labels."""
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


@pytest.mark.parametrize("first", [0, 8])
def test_the_program_in_float32_is_the_reference(first):
    """(i) Logits, per-sequence loss and the gradient of every leaf, with
    experts 0-3 and 8-11 held: float32 against float32 at ``highest``, so
    what differs is the order of sums (1e-5 of the largest entry)."""
    arch = dict(ARCH, first_expert_held=first)
    params, tokens, labels = _data(first)
    model = _model(first=first)

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
        assert _rel(a, b) < 1e-5, jax.tree_util.keystr(path)
        assert float(jnp.abs(b).max()) > 0, jax.tree_util.keystr(path)


def test_bfloat16_lies_inside_the_band_and_fp8_outside():
    """(ii) The program in bfloat16 against the float32 reference: logits
    within 6 % (rms over rms: bfloat16 keeps 8 bits, four layers deep, and
    at this size a router's near-tie that falls the other way moves a
    token's whole expert output; it reads 0.032), and the reference in fp8
    (three bits of mantissa; reads 0.17), put in the program's place,
    outside it: the nearest precision below is told apart."""
    from perfbench import check

    params, tokens, _ = _data()
    with jax.default_matmul_precision("highest"):
        want = FAMILY.forward(params, None, tokens, ARCH)
        lower = FAMILY.forward(params, None, tokens, ARCH, "fp8")
    got = token_logits(
        _model("bfloat16").apply({"params": params}, tokens, train=False))
    sound, control = (check.logit_gap(a, want) for a in (got, lower))
    assert sound < 0.06 < control, (sound, control)


def test_the_config_counts_what_the_issue_counts():
    """The constants of the configuration file against the arithmetic they
    were sized by: 492.6 MFLOP a token, 4.035 TFLOP a sequence; 370.5 M
    parameters held."""
    cfg = json.load(open(os.path.join(
        REPO, "perfbench", "configs", "smallthinker-21b-a3b.json")))
    fam = reference.family(cfg["reference"])
    assert fam.mean_keys_seen(8192, None) == 4096.5
    assert fam.mean_keys_seen(8192, 4096) == 3072.25
    assert fam.mean_keys_seen(32, 4096) == 16.5
    per_token = fam.fwd_flops_per_example(cfg) / cfg["seq_len"]
    assert per_token == 492_570_112
    fields = cfg["train_config"]
    from mercury_tpu.models import LM_WIDTHS, create_model

    w = LM_WIDTHS[fields["model"]]
    for key, value in (("hidden_size", w.d_model), ("head_dim", w.head_dim),
                       ("num_attention_heads", w.num_heads),
                       ("num_key_value_heads", w.num_kv_heads),
                       ("moe_ffn_hidden_size", w.expert_width),
                       ("moe_router_width", w.num_experts),
                       ("moe_num_active_primary_experts", w.top_k),
                       ("sliding_window_size", w.window),
                       ("rope_theta", w.rope_theta)):
        assert cfg[key] == value, key
    layers, first, held = fields["model_cut"]
    assert (layers, held) == (cfg["num_hidden_layers"],
                              cfg["moe_num_primary_experts"])
    assert first == cfg["moe_first_expert_held"] \
        == cfg["reference"]["first_expert_held"]
    assert [int(i % w.period != 0) for i in range(layers)] \
        == cfg["reference"]["rope_layout"] \
        == cfg["rope_layout"][:layers] \
        == cfg["sliding_window_layout"][:layers]
    model = create_model(fields["model"], num_classes=fields["num_classes"],
                         cut=tuple(fields["model_cut"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 370_547_200


# ---------------------------------------------------------- the rehearsal
def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_rehearsed_is_correct(capsys):
    """(viii) The whole command at the small size, blocks of one row on
    every side of the reference: ``correct``."""
    result = run.run_cell(CELL, seed=2 ** 31 + 11, seconds=0.3, trace=False,
                          rehearsal=TINY)
    assert result["correct"] and result["attempted"] >= 10
    assert _last_line(capsys) == result


def _a_window_one_key_too_wide(monkeypatch):
    from mercury_tpu.models import decoder

    real = decoder.blockwise_attention
    monkeypatch.setattr(
        decoder, "blockwise_attention",
        lambda q, k, v, window, *a: real(
            q, k, v, None if window is None else window + 1, *a))


def _rope_on_the_nope_layer(monkeypatch):
    from mercury_tpu.models import decoder

    monkeypatch.setattr(decoder, "windowed_and_rotated",
                        lambda widths, index: (index % widths.period != 0,
                                               True))


def _an_experts_weight_left_unnormalised(monkeypatch):
    from mercury_tpu.models import moe

    real = moe.route_top_k

    def unnormalised(router_logits, top_k, *a):
        weights, *rest = real(router_logits, top_k, *a)
        top = jax.lax.top_k(router_logits, top_k)[0]
        return (jnp.exp(top - top[:, :1]), *rest)

    monkeypatch.setattr(moe, "route_top_k", unnormalised)


@pytest.mark.parametrize("fault, failing", [
    (_a_window_one_key_too_wide, {"logit_gap", "loss_gap"}),
    (_rope_on_the_nope_layer, {"logit_gap", "loss_gap"}),
    (_an_experts_weight_left_unnormalised, {"logit_gap", "loss_gap"}),
], ids=["window", "rope", "weights"])
def test_this_models_own_faults_are_not_correct(capsys, monkeypatch, fault,
                                                failing):
    """(viii) The program broken underneath in ways that are this model's
    own: ``correct`` comes out false, by the inference check and by the
    replayed loss at least."""
    fault(monkeypatch)
    result = run.run_cell(CELL, seed=5, seconds=0.3, trace=False,
                          rehearsal=TINY)
    assert result["correct"] is False
    out = capsys.readouterr().out
    failed = {line.split()[2].rstrip(":") for line in out.splitlines()
              if line.startswith("[perfbench] check") and "FAIL" in line}
    assert failing <= failed, failed


# ------------------------------------------------------------- new metrics
def test_the_roofline_metrics_counts_are_the_familys():
    """``attention_roofline_share``'s FLOPs and bytes a step are what the
    family file's count function gives for this cell's pool and batch: the
    work (causal and windowed pairs, each operand once), as
    ``fwd_flops_per_example`` is held."""
    from perfbench import cell as cell_mod

    c = cell_mod.Cell(CELL)
    spec = cell_mod.layer_metric("attention_roofline_share")
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
    # the attention's share of the forward's required FLOPs: 38.7 %
    share = 4.0 * c.config["head_dim"] * fam.attention_pairs(c.config) \
        / c.config["fwd_flops_per_example"]
    assert 0.38 < share < 0.39


def _fake_capture(ops, steps=2):
    """A capture of ``steps`` step programs on one chip whose ops are
    ``(name, path, self microseconds)``, each run once a step."""
    from perfbench import trace_reduce

    events = []
    for s in range(steps):
        t0 = 1000.0 * s
        events.append({"ph": "X", "name": "jit_step", "ts": t0,
                       "dur": 900.0, "pid": 1, "tid": 1,
                       "_pname": "/device:TPU:0", "_tname": "XLA Modules"})
        at = t0
        for name, path, us in ops:
            events.append({"ph": "X", "name": name, "ts": at, "dur": us,
                           "pid": 1, "tid": 2, "_pname": "/device:TPU:0",
                           "_tname": "XLA Ops", "args": {"long_name": path}})
            at += us
    return trace_reduce.Capture(events, "jit_step")


def test_the_new_reducers_on_a_made_capture():
    from perfbench import cell as cell_mod

    capture = _fake_capture([
        ("fusion.1", "jit(step)/mercury_scoring/mercury_score_forward/"
                     "mercury_attention/dot_general", 100.0),
        ("custom-call.2", "jit(step)/mercury_train/transpose(jvp("
                          "mercury_attention))/splash_mqa_dkv_no_residuals",
         200.0),
        ("sort.3", "jit(step)/mercury_train/mercury_moe/mercury_moe_route/"
                   "sort", 50.0),
        ("fusion.4", "jit(step)/mercury_train/mercury_moe/ragged_dot", 50.0),
        ("fusion.5", "jit(step)/mercury_optimizer/add", 100.0),
    ])
    assert capture.step_count() == 2
    ctx = dict(capture=capture, steps=2, peak_flops=1e12, spans=[
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"held_pair_share": 0.125, "load_max_over_mean": 1.5}},
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"held_pair_share": 0.125, "load_max_over_mean": 2.5}},
        {"name": "trainer/fit", "ph": "X", "dur": 5.0, "args": {}}])
    share = cell_mod.reducer("path_scope_share")
    assert share(ctx, scope="mercury_attention") == pytest.approx(60.0)
    assert share(ctx, scope="mercury_moe") == pytest.approx(20.0)
    assert share(ctx, scope="mercury_moe_route") == pytest.approx(10.0)
    assert share(ctx, scope="mercury_lm_head") is None
    instant = cell_mod.reducer("instant_arg")
    assert instant(ctx, instant="trainer/moe_load",
                   arg="load_max_over_mean") == pytest.approx(2.0)
    assert instant(ctx, instant="trainer/absent", arg="x") is None
    assert instant(dict(ctx, spans=[]), instant="trainer/moe_load",
                   arg="load_max_over_mean") is None
    roofline = cell_mod.reducer("kernel_roofline_share")
    # off the chip no bandwidth is tabulated for the device: the flops
    # side alone decides where it binds (1e8 FLOPs at 1e12/s = 100 us of
    # the kernel's 200 us a step)
    import perfbench.reducers.kernel_roofline_share as module

    real = module.peak
    module.peak = lambda kind, what: 1e12
    try:
        assert roofline(ctx, pattern="splash_mqa", flops_per_step=1e8,
                        bytes_per_step=1e6) == pytest.approx(50.0)
        assert roofline(ctx, pattern="absent_kernel", flops_per_step=1e8,
                        bytes_per_step=1e6) is None
        assert roofline(dict(ctx, peak_flops=None), pattern="splash_mqa",
                        flops_per_step=1e8, bytes_per_step=1e6) is None
    finally:
        module.peak = real
