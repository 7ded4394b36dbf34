"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — ``TrainConfig`` → ``Trainer`` → ``fit()`` /
``evaluate()`` / ``save()`` / ``restore()``, the objects ``python -m
mercury_tpu`` drives — at the full width of the reference's live model
(CIFAR-stem ResNet-18, batch 32, 320-candidate pool, bf16 compute) on
seeded synthetic data, and checks what comes out:

- one chip: 60 importance-sampled steps (loss finite throughout and well
  under ln 10 at the end), the four eval keys, a save → restore round trip,
  20 steps of the uniform arm, three ``scan_steps=25`` chunks, ten
  pipelined steps of the token path (``smallthinker-tiny`` on
  ``tokens_zipf``: rows of ids, a per-sequence loss) and ten more through
  the decoder's other mixer and routing rule (``latent-tiny``), zero
  compiles after each trainer's first call; beside each token phase the
  head's kernel over vocabulary blocks against the plain form, one
  sequence at its benchmark cell's shape, and beside the first the
  attention's query operand in one pass against the plain forms;
- the Pallas kernels really compiled (Mosaic custom call in the compiled
  step, nothing in interpret mode) and each matches its jax-native twin
  standalone on the chip, at the shapes ``Trainer`` produces;
- a three-step profiler capture reduces (``obs.profile_parse``) to non-zero
  device time with a non-zero ``mercury_scoring`` share;
- with >= 4 devices visible: the same config at ``world_size=4`` — every
  step input and state leaf committed on the 4-device mesh before step 1,
  memory in use on all four after, loss falling, zero compiles after the
  first call.

One process, which is the only one that touches JAX. It refuses to run off
the chip (non-zero exit, no result line) and any failed check raises — no
phase is skipped or tolerated. The last stdout line is one JSON object.
``run(tiny=True)`` is the same body at ``smallcnn`` size, which tier-1
drives on the virtual CPU mesh (``tests/test_chip_smoke.py``).

    python chip_smoke.py            # on a machine with a TPU
"""

from __future__ import annotations

import json
import math
import sys
import tempfile
import time
from typing import Any, Dict, List


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def _say(phase: str, **fields: Any) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] {phase}: {body}", flush=True)


# --------------------------------------------------------------- trainers
def _config(tiny: bool, total_steps: int, **kw):
    """The verify skill's config (resnet18 / synthetic / batch 32 / pool
    320 / bf16), or its ``smallcnn`` miniature. ``steps_per_epoch=1`` so
    ``fit(num_epochs=n)`` advances exactly ``n`` steps (``n`` chunks under
    ``scan_steps``) while the LR schedule still spans ``total_steps``."""
    from mercury_tpu import TrainConfig

    scan = int(kw.get("scan_steps", 1))
    base: Dict[str, Any] = dict(
        model="resnet18", dataset="synthetic", batch_size=32,
        presample_batches=10, steps_per_epoch=scan,
        num_epochs=total_steps // scan, log_every=scan, eval_every=0,
        checkpoint_every=0, heartbeat_every=0, seed=0,
    )
    if tiny:
        base.update(model="smallcnn", batch_size=8, presample_batches=2,
                    compute_dtype="float32")
    base.update(kw)
    return TrainConfig(**base)


def _committed_on_mesh(trainer) -> List[str]:
    """Paths of step inputs / state leaves NOT committed on the whole
    mesh (empty = placed)."""
    import jax
    from jax.sharding import NamedSharding

    n = trainer.mesh.devices.size
    ds = trainer.dataset
    tree = {"state": trainer.state, "x_train": trainer._step_x,
            "y_train": ds.y_train, "shard_indices": ds.shard_indices}
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sh = getattr(leaf, "sharding", None)
        if not (isinstance(sh, NamedSharding) and len(sh.device_set) == n
                and leaf.committed):
            bad.append(f"{jax.tree_util.keystr(path)}: {sh}")
    return bad


def _train_phase(name: str, tiny: bool, steps: int, loss_below: float,
                 deep: bool = False, **kw) -> Dict[str, Any]:
    """Construct a ``Trainer``, take ``steps`` steps through ``fit()`` —
    the first call apart, as set-up — and check losses, eval keys and the
    compile count; ``deep`` adds the compiled-step, checkpoint and
    profiler checks. Returns the phase's facts (trainer closed)."""
    import jax

    from mercury_tpu.lint.tracecheck import CompileMonitor
    from mercury_tpu.train import Trainer

    config = _config(tiny, steps, **kw)
    calls = config.num_epochs
    t0 = time.perf_counter()
    with CompileMonitor() as setup, Trainer(config) as trainer:
        unplaced = _committed_on_mesh(trainer)
        _require(not unplaced, f"{name}: not committed on the mesh before "
                               f"step 1: {unplaced[:4]}")
        losses: List[float] = []
        trainer.logger.add_observer(
            lambda rec: losses.append(float(rec["train/loss"])))
        closing = trainer.fit(num_epochs=1)
        setup.stop()
        setup_s = time.perf_counter() - t0
        with CompileMonitor() as monitor:
            t1 = time.perf_counter()
            trainer.fit(num_epochs=calls - 1)
            fit_s = time.perf_counter() - t1
            # the splits fit()'s closing evaluation took (the token
            # phase's leaves the train split out: a row is a sequence)
            evals = trainer.evaluate(
                include_train="train/eval_loss" in closing)
            compiles = monitor.snapshot()[1]
        _require(len(losses) == calls,
                 f"{name}: {len(losses)} loss records for {calls} calls")
        _require(all(math.isfinite(v) for v in losses),
                 f"{name}: non-finite loss in {losses}")
        _require(losses[-1] < loss_below,
                 f"{name}: final loss {losses[-1]:.4f} not under "
                 f"{loss_below}")
        _require({"test/eval_loss", "test/eval_acc"} <= set(evals)
                 and set(evals) == set(closing)
                 and all(math.isfinite(v) for v in evals.values()),
                 f"{name}: evaluate() returned {evals}")
        _require(int(trainer.state.step) == steps,
                 f"{name}: state.step {int(trainer.state.step)} != {steps}")
        _require(compiles == 0,
                 f"{name}: {compiles} compile(s) after the first call")
        facts = dict(
            steps=steps, setup_s=round(setup_s, 2),
            setup_compile_s=round(setup.compile_secs, 2),
            cache_hits=setup.cache_hits, cache_misses=setup.cache_misses,
            # Wall seconds of the fit() that took the remaining steps:
            # host loop, per-call log gate and fit()'s closing evaluate()
            # included — a reading, not a benchmark. The device's own
            # time per step is the profile's.
            rest_steps=steps - steps // calls, rest_fit_s=round(fit_s, 3),
            loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
            compiles_after_first=compiles,
        )
        if deep:
            facts.update(_deep_checks(name, trainer, tiny))
        if jax.devices()[0].platform != "cpu":  # the CPU reports none
            in_use = [d.memory_stats()["bytes_in_use"]
                      for d in trainer.mesh.devices.flat]
            _require(all(b > 0 for b in in_use),
                     f"{name}: bytes_in_use per mesh device = {in_use}")
            facts["mib_in_use"] = [b >> 20 for b in in_use]
    _say(name, **facts)
    return facts


def _deep_checks(name: str, trainer, tiny: bool) -> Dict[str, Any]:
    """The one-chip IS trainer's extra checks: kernels in the compiled
    step, a checkpoint round trip, and (on the chip) the profiler
    capture."""
    from mercury_tpu.ops import mercury_kernels, on_tpu

    ds = trainer.dataset
    # use_pallas=None resolves to on_tpu(): on the chip the compiled step
    # must carry the Mosaic custom call and nothing may run under the
    # interpreter; off it, neither.
    text = trainer.train_step.lower(
        trainer.state, trainer._step_x, ds.y_train, ds.shard_indices
    ).compile().as_text()
    facts: Dict[str, Any] = {"mosaic_in_step": "tpu_custom_call" in text}
    _require(facts["mosaic_in_step"] == on_tpu()
             and mercury_kernels._interpret() != on_tpu(),
             f"{name}: use_pallas resolved to {on_tpu()} but "
             f"tpu_custom_call in step = {facts['mosaic_in_step']}, "
             f"interpret = {mercury_kernels._interpret()}")
    step = int(trainer.state.step)
    with tempfile.TemporaryDirectory() as tmp:
        trainer.save(tmp + "/ckpt")
        # Three more steps, so that restore() has something to undo — on
        # the chip under the profiler (the CPU has no device lanes to
        # reduce).
        if tiny:
            trainer.fit(num_epochs=3)
        else:
            facts.update(_profile_three_steps(name, trainer, tmp))
        restored = trainer.restore(tmp + "/ckpt")
    _require(restored == step and int(trainer.state.step) == step,
             f"{name}: saved at step {step}, restore() returned "
             f"{restored}, state.step {int(trainer.state.step)}")
    facts["restored_step"] = restored
    return facts


def _profile_three_steps(name: str, trainer, tmp: str) -> Dict[str, Any]:
    """Three steady steps under ``jax.profiler``, reduced by the repo's
    own trace reader: the per-layer metrics of the benchmark all come
    from this reduction, so it must see the device and the scoring scope
    in a real trace. The steps are dispatched directly (``fit()`` would
    close the window with an ``evaluate()`` pass and dilute the shares)."""
    import jax

    from mercury_tpu.obs.profile_parse import parse_profile

    ds = trainer.dataset
    jax.profiler.start_trace(tmp + "/profile")
    try:
        for _ in range(3):
            trainer.state, metrics = trainer.train_step(
                trainer.state, trainer._step_x, ds.y_train, ds.shard_indices)
        jax.block_until_ready(metrics)
    finally:
        jax.profiler.stop_trace()
    breakdown = parse_profile(tmp + "/profile")
    device_us = breakdown["total_device_time_us"]
    scoring = breakdown["scopes"]["mercury_scoring"]["frac"]
    _require(device_us > 0 and scoring > 0,
             f"{name}: profile reduction saw device_time_us={device_us}, "
             f"mercury_scoring share={scoring} "
             f"({breakdown['counts']})")
    return {"profile_device_ms_per_step": round(device_us / 3e3, 3),
            "profile_scoring_share": round(scoring, 3),
            "profile_idle_share": round(breakdown["idle"]["idle_frac"], 3)}


# ---------------------------------------------------------------- kernels
def _kernel_phase(tiny: bool) -> Dict[str, Any]:
    """Each Pallas kernel ``TrainConfig`` can reach, standalone against
    its jax-native twin under one key, at the shapes ``Trainer`` produces
    (pool 320 / 2,560; scoretable shards 5,000 / 12,500 / 50,000)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mercury_tpu.ops import per_sample_nll_pallas, score_and_draw_pallas
    from mercury_tpu.sampling.importance import (
        importance_probs,
        per_sample_loss,
    )
    from mercury_tpu.sampling.scoretable import table_draw_inverse_cdf

    nll_shapes = [(320, 10, jnp.float32), (32, 10, jnp.float32),
                  (320, 100, jnp.float32), (320, 10, jnp.bfloat16)]
    draw_shapes = [(320, 32), (2560, 256), (5000, 32), (12500, 32),
                   (50000, 32)]
    if tiny:
        nll_shapes, draw_shapes = nll_shapes[1:2], draw_shapes[:1]

    nll_err = vjp_err = 0.0
    for i, (n, c, dtype) in enumerate(nll_shapes):
        k1, k2, k3 = jax.random.split(jax.random.key(i), 3)
        logits = (3.0 * jax.random.normal(k1, (n, c))).astype(dtype)
        labels = jax.random.randint(k2, (n,), 0, c)
        w = jax.random.uniform(k3, (n,))

        def vjp(fn):
            return jax.jit(jax.grad(
                lambda z: jnp.sum(w * fn(z, labels))))(logits)

        nll_p = jax.jit(per_sample_nll_pallas)(logits, labels)
        nll_n = jax.jit(per_sample_loss)(logits, labels)
        g_p, g_n = vjp(per_sample_nll_pallas), vjp(per_sample_loss)
        nll_err = max(nll_err, float(jnp.max(jnp.abs(nll_p - nll_n))))
        vjp_err = max(vjp_err, float(jnp.max(jnp.abs(
            g_p.astype(jnp.float32) - g_n.astype(jnp.float32)))))
    # Same f32 math on both sides (the kernel upcasts bf16 logits as the
    # twin does), exp/log from two compilers: 1.9e-6 / 1.8e-7 on the v5e
    # (PR 21). The bf16 case's gradient is rounded to bf16 once on each
    # side, so one bf16 ulp of a <=1 softmax entry bounds it.
    _require(nll_err <= 2e-5, f"kernel 1 NLL max |diff| {nll_err}")
    _require(vjp_err <= 2.0 ** -8, f"kernel 1 vjp max |diff| {vjp_err}")

    probs_err = scaled_err = 0.0
    draws = moved = 0
    for n, b in draw_shapes:
        key = jax.random.key(n)
        losses = jax.random.exponential(jax.random.fold_in(key, 1), (n,))
        ema = jnp.mean(losses)
        probs, sel, scaled = jax.jit(
            score_and_draw_pallas, static_argnums=3)(key, losses, ema, b)
        probs_n = jax.jit(importance_probs)(losses, ema)
        sel_n = jax.jit(table_draw_inverse_cdf, static_argnums=2)(
            key, probs_n, b)
        probs, sel, scaled, probs_n, sel_n = map(
            np.asarray, (probs, sel, scaled, probs_n, sel_n))
        _require(((sel >= 0) & (sel < n)).all(),
                 f"kernel 2 drew outside the pool at N={n}")
        probs_err = max(probs_err, float(np.max(np.abs(probs - probs_n) * n)))
        same = sel == sel_n
        scaled_err = max(scaled_err, float(np.max(np.abs(
            scaled[same] / (probs_n[sel_n[same]] * n) - 1.0))))
        # The twin is the same inverse-CDF on the same uniforms through
        # XLA's cumsum; the kernel's CDF is an f32 matmul (HIGHEST
        # precision, exact products of 0/1 masks) with another summation
        # order, so a u within rounding of a slot boundary may land on
        # the neighbouring slot — never further. On the v5e all 384
        # draws matched (PR 21).
        _require(np.abs(sel - sel_n).max() <= 1,
                 f"kernel 2 draw off by more than one slot at N={n}: "
                 f"{sel[:8]} vs {sel_n[:8]}")
        draws += b
        moved += int((sel != sel_n).sum())
    _require(probs_err <= 1e-4, f"kernel 2 probs max N*|diff| {probs_err}")
    _require(scaled_err <= 1e-4, f"kernel 2 scaled max rel diff {scaled_err}")
    _require(moved <= max(1, draws // 50),
             f"kernel 2: {moved} of {draws} draws moved a slot")
    facts = dict(nll_max_abs=f"{nll_err:.2e}", vjp_max_abs=f"{vjp_err:.2e}",
                 draw_probs_max_nabs=f"{probs_err:.2e}",
                 draws_moved_a_slot=f"{moved}/{draws}")
    _say("kernels", **facts)
    return facts


def _head_check(phase: str, tiny: bool, d: int, v: int) -> Dict[str, Any]:
    """The head of one sequence of 8,192 tokens at a token cell's shape
    (hidden ``d``, vocabulary ``v``, bfloat16 operands): the kernel over
    vocabulary blocks (``ops.head_nll_pallas``) against the plain form that
    writes the ``[T, V]`` logits. Half the labels are the plain logits'
    argmax, so the hits say something. ``tiny``: 32 x 64 x 96 in blocks the
    interpreter walks quickly."""
    import jax
    import jax.numpy as jnp

    from mercury_tpu.ops import head_nll_pallas
    from mercury_tpu.sampling.importance import _token_rows_plain

    t, blocks = 8192, None
    if tiny:
        t, d, v, blocks = 32, 64, 96, (16, 128)
    k1, k2, k3, k4 = jax.random.split(jax.random.key(v), 4)
    hidden = jax.random.normal(k1, (t, d)).astype(jnp.bfloat16)
    head = (jax.random.normal(k2, (d, v)) * d ** -0.5).astype(jnp.bfloat16)
    best = jnp.argmax(jnp.dot(hidden, head,
                              preferred_element_type=jnp.float32), -1)
    labels = jnp.where(jax.random.bernoulli(k3, 0.5, (t,)), best,
                       jax.random.randint(k4, (t,), 0, v))
    nll_k, hit_k = jax.jit(lambda *a: head_nll_pallas(*a, blocks))(
        hidden, head, labels)
    nll_p, hit_p = jax.jit(_token_rows_plain)(hidden, head, labels)
    facts = dict(shape=f"{t}x{d}x{v}",
                 loss_kernel=float(nll_k.mean()), loss_plain=float(nll_p.mean()),
                 hit_kernel=float(hit_k.mean()), hit_plain=float(hit_p.mean()),
                 token_loss_max_abs=float(jnp.max(jnp.abs(nll_k - nll_p))),
                 hits_differ=int(jnp.sum(hit_k != hit_p)))
    _say(f"{phase}/head", **facts)
    # float32 sums of the same bfloat16 products in another order, exp/log
    # from two compilers: 1e-6 on the mean at both shapes on the v5e (PR
    # 44); a hit may differ where two logits tie to rounding.
    _require(abs(facts["loss_kernel"] - facts["loss_plain"])
             <= 1e-5 * facts["loss_plain"], f"{phase}/head: loss {facts}")
    _require(facts["token_loss_max_abs"] <= 1e-3, f"{phase}/head: {facts}")
    _require(facts["hits_differ"] <= t // 1024, f"{phase}/head: {facts}")
    _require(facts["hit_plain"] > 0.4, f"{phase}/head: {facts}")
    return facts


def _operands_check(phase: str, tiny: bool, heads: int, hd: int,
                    theta: float) -> Dict[str, Any]:
    """The query operand of one sequence of 8,192 tokens at a token cell's
    shape (``heads`` of ``hd``, rotated and scaled): the one-pass kernel
    (``ops.rope_heads_pallas``) against ``rotate_half``, scale, cast and
    transpose as the plain path writes them, and the two gradients of a
    scalar of the operand. ``tiny``: 40 tokens of 2 heads, one block."""
    import jax
    import jax.numpy as jnp

    from mercury_tpu.models.decoder import rope_tables, rotate_half
    from mercury_tpu.ops import mercury_kernels

    t, heads = (40, 2) if tiny else (8192, heads)
    k1, k2 = jax.random.split(jax.random.key(heads))
    x = jax.random.normal(k1, (t, heads * hd))
    c = jax.random.normal(k2, (heads, t, hd))
    scale, tables = hd ** -0.5, rope_tables(t, hd, theta)

    def plain(x):
        x = rotate_half(x.reshape(t, heads, hd), theta) * scale
        return x.astype(jnp.bfloat16).transpose(1, 0, 2)

    def kernel(x):
        return mercury_kernels.rope_heads_pallas(x, tables, hd, scale,
                                                 jnp.bfloat16)

    def both(fn):
        return jax.jit(jax.grad(
            lambda x: jnp.sum(fn(x).astype(jnp.float32) * c)))(x), \
            jax.jit(fn)(x)

    (grad_k, out_k), (grad_p, out_p) = both(kernel), both(plain)
    out_k, out_p = (a.astype(jnp.float32) for a in (out_k, out_p))
    facts = dict(shape=f"{t}x{heads}x{hd}",
                 operand_differs=float(jnp.mean(out_k != out_p)),
                 operand_max_abs=float(jnp.max(jnp.abs(out_k - out_p))),
                 grad_max_abs=float(jnp.max(jnp.abs(grad_k - grad_p))))
    _say(f"{phase}/operands", **facts)
    # float32 on both sides, a multiply-add contracted here and not there:
    # the last bit, which a cast to bfloat16 shows once in thousands
    _require(facts["operand_differs"] <= 1e-2, f"{phase}/operands: {facts}")
    _require(facts["operand_max_abs"] <= 2 ** -5, f"{phase}/operands: {facts}")
    _require(facts["grad_max_abs"] <= 1e-5, f"{phase}/operands: {facts}")
    return facts


# -------------------------------------------------------------------- run
def run(tiny: bool = False) -> Dict[str, Any]:
    """The smoke body. ``tiny=True`` is the ``smallcnn`` miniature for the
    CPU mesh; the chip runs ``tiny=False``. Raises on the first check that
    does not hold."""
    import jax

    steps = 12 if tiny else 60
    scan = 3 if tiny else 25
    # ln 10 ≈ 2.303 is chance level. On the v5e the 60th IS step's loss
    # was 0.0021 and the 75th scanned step's 0.0022 (PR 21); smallcnn
    # barely moves in 12 steps, so tiny only asks for "not diverged".
    bound = 2.4 if tiny else 0.1
    out: Dict[str, Any] = {}
    out["one_chip_is"] = _train_phase(
        "one_chip_is", tiny, steps, loss_below=bound, deep=True,
        world_size=1)
    out["one_chip_uniform"] = _train_phase(
        "one_chip_uniform", tiny, 5 if tiny else 20, loss_below=10.0,
        world_size=1, use_importance_sampling=False)
    out["one_chip_scan"] = _train_phase(
        "one_chip_scan", tiny, 3 * scan, loss_below=bound,
        world_size=1, scan_steps=scan)
    # The token path (models/decoder.py at the CPU tests' size, both on and
    # off the chip: a head of 16 is not the splash kernel's, so this is the
    # blockwise XLA attention and the grouped expert products): rows of
    # ids, the per-sequence loss, a row at a time. ln 96 = 4.56 is chance.
    out["one_chip_tokens"] = _train_phase(
        "one_chip_tokens", tiny, 10, loss_below=5.0, world_size=1,
        model=register_tiny_lm(), dataset="tokens_zipf",
        model_cut=(4, 0, 4), num_classes=96, seq_len=32, batch_size=2,
        presample_batches=3, augmentation="none", pipelined_scoring=True)
    out["one_chip_tokens"]["head"] = _head_check(
        "one_chip_tokens", tiny, 2560, 18992)       # st21b-is-8k
    out["one_chip_tokens"]["operands"] = _operands_check(
        "one_chip_tokens", tiny, 28, 128, 1_500_000.0)
    # The same path through the other mixer and the other routing rule
    # (latent attention, a sigmoid router with a selection bias, a shared
    # expert, a leading dense layer) on a share of the heads: heads of
    # 128 + 64 against 128 at T = 128 are the splash kernel's on the chip.
    out["one_chip_latent_tokens"] = _train_phase(
        "one_chip_latent_tokens", tiny, 10, loss_below=5.0, world_size=1,
        model=register_tiny_latent_lm(), dataset="tokens_zipf",
        model_cut=(3, 0, 4, 0, 2), num_classes=96, seq_len=128,
        batch_size=2, presample_batches=3, augmentation="none",
        pipelined_scoring=True)
    out["one_chip_latent_tokens"]["head"] = _head_check(
        "one_chip_latent_tokens", tiny, 2048, 16032)    # kn2-is-8k
    out["kernels"] = _kernel_phase(tiny)
    if len(jax.devices()) >= 4:
        four = _train_phase("four_chip_is", tiny, steps,
                            loss_below=bound, world_size=4)
        _require(four["loss_last"] < four["loss_first"],
                 f"four_chip_is: loss did not fall: {four}")
        out["four_chip_is"] = four
    return out


def register_tiny_lm() -> str:
    """The token phase's model, added to the registry of published widths
    (``models/decoder.py::LM_WIDTHS`` holds only those): the same layers at
    a size the CPU runs too; the tests of the token path take it from
    here. Returns its name."""
    from mercury_tpu.models.decoder import LM_WIDTHS, LMWidths

    LM_WIDTHS.setdefault("smallthinker-tiny", LMWidths(
        num_layers=4, d_model=64, num_heads=4, num_kv_heads=1, head_dim=16,
        num_experts=16, top_k=3, expert_width=32, window=8,
        rope_theta=10_000.0))
    return "smallthinker-tiny"


def register_tiny_latent_lm() -> str:
    """:func:`register_tiny_lm`'s twin for the decoder's other mixer and
    routing rule: latent attention with heads of 128 + 64 against 128 (the
    kernel's, where the sequence is a multiple of 128), a sigmoid router
    with a selection bias over 16 SwiGLU experts, a shared expert, one
    leading dense layer. Returns its name."""
    from mercury_tpu.models.decoder import LM_WIDTHS, Latent, LMWidths

    LM_WIDTHS.setdefault("latent-tiny", LMWidths(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=128,
        num_experts=16, top_k=3, expert_width=32, window=None,
        rope_theta=10_000.0, latent=Latent(kv_rank=32, rope_dim=64,
                                           v_head_dim=128),
        router="sigmoid", routed_scale=2.448, activation="silu",
        shared_width=64, dense_layers=1, dense_width=96))
    return "latent-tiny"


def main() -> int:
    import jax

    from mercury_tpu.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {device}; refusing to "
              "run (tier-1 drives run(tiny=True) on the CPU mesh instead)",
              file=sys.stderr)
        return 1
    from importlib.metadata import version

    _say("env", platform=device["platform"],
         device_kind=repr(device["kind"]), device_count=device["count"],
         jax=version("jax"), jaxlib=version("jaxlib"),
         libtpu=version("libtpu"), compile_cache=cache_dir)
    t0 = time.perf_counter()
    phases = run(tiny=False)
    print(json.dumps({"phases": phases,
                      "wall_s": round(time.perf_counter() - t0, 1)}))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
