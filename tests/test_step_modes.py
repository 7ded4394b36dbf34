"""The step's mode, decided once (``train/mode.py::StepMode``).

Two things live here:

* ``MATRIX`` — the configurations a restructuring of ``train/step.py`` is
  held to: every sampler kind under every placement it runs on, with the
  telemetry off and on, and one row for each trace-time gate.
* the tests: ``StepMode.from_config`` agrees with what the program does
  (the optional fields of the state ``Trainer`` creates, the spec tree,
  the rows the host stream carries), and every illegal combination of
  fields raises the ``ValueError`` it always raised.

Run as a script it is the digest tool of PR 31's proof — the canonical
jaxpr digest of every ``MATRIX`` row, of ``lint.audit.PLAN_NAMES`` and of
the benchmark's own configuration, for whichever checkout ``--repo``
names, so a parent and a change are compared on one jax::

    JAX_PLATFORMS=cpu python tests/test_step_modes.py \
        --repo /root/scratch/parent --out /root/scratch/parent.json
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Any, Dict, List, Tuple

import pytest

#: The token row's model: ``chip_smoke.register_tiny_lm`` adds it to the
#: registry of the checkout that is traced (:func:`build`).
TINY_LM = "smallthinker-tiny"
TINY_LATENT_LM = "latent-tiny"
#: Which function of ``chip_smoke`` adds each.
REGISTERS = {TINY_LM: "register_tiny_lm",
             TINY_LATENT_LM: "register_tiny_latent_lm"}

BASE: Dict[str, Any] = dict(
    model="smallcnn", dataset="synthetic", world_size=2, batch_size=8,
    presample_batches=2, num_epochs=1, steps_per_epoch=100, eval_every=0,
    log_every=0, compute_dtype="float32", heartbeat_every=0, seed=0,
)

#: sampler kind → the fields that select it.
KINDS: Dict[str, Dict[str, Any]] = {
    "uniform": dict(use_importance_sampling=False),
    "pool": dict(),
    "pipelined": dict(pipelined_scoring=True),
    "cadence": dict(score_refresh_every=4),
    "groupwise": dict(sampler="groupwise"),
    "scoretable": dict(sampler="scoretable", refresh_size=8),
    "scoretable_async": dict(sampler="scoretable", refresh_size=8,
                             refresh_mode="async", scorer_workers=1,
                             snapshot_every=4),
}

#: the placements each kind runs on in the proof.
PLACEMENTS: Dict[str, Tuple[str, ...]] = {
    "uniform": ("replicated", "sharded", "host_stream"),
    "pool": ("replicated", "sharded", "host_stream"),
    "pipelined": ("replicated", "sharded"),
    "cadence": ("replicated", "sharded"),
    "groupwise": ("replicated",),
    "scoretable": ("replicated", "host_stream"),
    "scoretable_async": ("replicated", "host_stream"),
}


def _matrix() -> List[Tuple[str, Dict[str, Any]]]:
    rows = []
    for kind, fields in KINDS.items():
        for placement in PLACEMENTS[kind]:
            for telemetry in (False, True):
                name = f"{kind}-{placement}-tel{int(telemetry)}"
                rows.append((name, dict(
                    fields, data_placement=placement, telemetry=telemetry)))
    rows += [
        ("pool-zero", dict(zero_sharding=True)),
        ("pool-scan4", dict(scan_steps=4)),
        ("pool-int8", dict(grad_compression="int8")),
        ("pool-stochastic", dict(grad_compression="stochastic")),
        ("pool-bf16score", dict(scoring_dtype="bfloat16")),
        ("pool-gradnorm", dict(importance_score="grad_norm")),
        ("pool-probe2", dict(telemetry=True, variance_probe_every=2)),
        ("scoretable-hs-probe2", dict(
            KINDS["scoretable"], data_placement="host_stream",
            telemetry=True, variance_probe_every=2)),
        ("pool-fused", dict(fused_input=True)),
        ("pipelined-tp2", dict(
            model="transformer", dataset="synthetic_seq",
            augmentation="none", tensor_parallel=2, batch_size=4,
            pipelined_scoring=True)),
        # rows of token ids, per-token labels, a per-sequence loss
        ("pipelined-tokens", dict(
            model=TINY_LM, dataset="tokens_zipf",
            model_cut=(2, 0, 4), num_classes=96, seq_len=32,
            augmentation="none", batch_size=2, pipelined_scoring=True)),
        # the same through the decoder's other mixer and routing rule, on a
        # share of the heads (the five-field cut)
        ("pipelined-latent-tokens", dict(
            model=TINY_LATENT_LM, dataset="tokens_zipf",
            model_cut=(2, 0, 4, 0, 2), num_classes=96, seq_len=32,
            augmentation="none", batch_size=2, pipelined_scoring=True)),
    ]
    return rows


MATRIX: List[Tuple[str, Dict[str, Any]]] = _matrix()


def _register(model) -> None:
    """The token row's model is no published one: the checkout's
    ``chip_smoke.py`` adds it to the registry (ImportError from a checkout
    older than the row)."""
    if model in REGISTERS:
        import chip_smoke

        getattr(chip_smoke, REGISTERS[model])()


def build(fields: Dict[str, Any]):
    """``Trainer`` of one row, with its background workers stopped (the
    rows are traced, never run)."""
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.train.trainer import Trainer

    _register(fields.get("model"))
    trainer = Trainer(TrainConfig(**dict(BASE, **fields)))
    if trainer._scorer_fleet is not None:
        trainer._scorer_fleet.close()
    return trainer


def step_args(trainer, prime: bool = False):
    """What the row's step is traced with: the trainer's own committed
    inputs, and for a host stream a template of the slab."""
    import jax

    shard_indices = trainer.dataset.shard_indices
    if prime:
        return (trainer.state, shard_indices)
    x = trainer._step_x
    if trainer._stream_pipe is not None:
        staging = trainer._stream_pipe._staging[0]
        x = jax.ShapeDtypeStruct(staging.shape, staging.dtype)
    return (trainer.state, x, trainer._step_y, shard_indices)


# --------------------------------------------------------------------------
# (a) the mode agrees with what the program does
# --------------------------------------------------------------------------

OPTIONAL = ("groupwise", "pending", "cached_pool", "scoretable",
            "pending_sel", "sel_counts")


def _config_and_mode(fields):
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.train.mode import StepMode

    config = TrainConfig(**dict(BASE, **fields))
    axes = {config.mesh_axis: config.world_size}
    if config.tensor_parallel > 1:
        axes[config.model_axis] = config.tensor_parallel
    mode = StepMode.from_config(
        config, scan_steps=config.scan_steps, mesh_axes=axes,
        param_specs_pinned=config.tensor_parallel > 1)
    return config, mode


def _abstract_state(config, mode, shard_len=64):
    """Shapes of the state ``Trainer`` would create: the same
    ``create_state(...)`` call, never run."""
    import jax
    import jax.numpy as jnp

    from mercury_tpu.models import create_model
    from mercury_tpu.train.state import create_state, make_optimizer

    seq = config.dataset == "synthetic_seq"
    tokens = config.dataset == "tokens_zipf"
    _register(config.model)
    sample_shape = ((config.seq_len,) if tokens
                    else (16, 8) if seq else (32, 32, 3))
    model = create_model(config.model, num_classes=config.num_classes or 10,
                         compute_dtype=config.compute_dtype,
                         param_dtype=config.param_dtype,
                         **({"cut": config.model_cut} if tokens else {}))
    tx = make_optimizer(config.optimizer, config.lr, 100, config.weight_decay)
    return jax.eval_shape(lambda: create_state(
        jax.random.key(0), model, tx,
        jnp.zeros((1,) + sample_shape, jnp.int32 if tokens else jnp.float32),
        config.world_size, shard_len, pending_sample_shape=sample_shape,
        pending_label_shape=sample_shape if tokens else (),
        **mode.create_state_fields())), model, tx


@pytest.mark.parametrize("name,fields", MATRIX, ids=[n for n, _ in MATRIX])
def test_mode_names_the_states_optional_fields(name, fields):
    """The optional fields of the created state, the spec tree and the
    kind agree — one derivation, read three times."""
    import jax
    from jax.sharding import PartitionSpec as P

    from mercury_tpu.train.step import _state_specs

    config, mode = _config_and_mode(fields)
    assert mode.sampler == name.split("-")[0] in KINDS
    assert mode.placement == fields.get("data_placement", "replicated")
    state, _, _ = _abstract_state(config, mode)
    has = mode.state_fields()
    assert {f: getattr(state, f) is not None for f in OPTIONAL} == {
        f: has[f"has_{f}"] for f in OPTIONAL}
    specs = _state_specs(mode.axis, zero_sharding=mode.zero, **has)
    assert {f: getattr(specs, f) is not None for f in OPTIONAL} == {
        f: has[f"has_{f}"] for f in OPTIONAL}
    # the spec tree is a prefix of the state: every leaf finds its spec
    jax.tree_util.tree_map(lambda spec, sub: None, specs, state,
                           is_leaf=lambda x: isinstance(x, P))
    assert (specs.opt_state == P(mode.axis)) == mode.zero
    if mode.host_stream:
        assert state.pending_sel.slots.shape == (
            config.world_size, mode.depth, mode.emit_size)
    else:
        assert mode.depth == 0


STREAMED = [(n, f) for n, f in MATRIX
            if f.get("data_placement") == "host_stream" and "tel0" in n]


@pytest.mark.parametrize("name,fields", STREAMED,
                         ids=[n for n, _ in STREAMED])
def test_emit_size_is_what_the_prime_emits_and_the_step_consumes(name,
                                                                 fields):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.step import (
        make_host_stream_prime,
        make_train_step,
    )

    config, mode = _config_and_mode(fields)
    w, shard_len = config.world_size, 64
    state, model, tx = _abstract_state(config, mode, shard_len)
    mesh = make_mesh(w, config.mesh_axis)
    shard_indices = jax.ShapeDtypeStruct((w, shard_len), jnp.int32)
    _, gidx = jax.eval_shape(make_host_stream_prime(config, mesh), state,
                             shard_indices)
    assert gidx.shape == (mode.depth, w, mode.emit_size)
    step = make_train_step(model, tx, config, mesh, np.zeros(3, np.float32),
                           np.ones(3, np.float32), image_shape=(32, 32, 3))
    slab = jax.ShapeDtypeStruct((w, mode.emit_size, 32 * 32 * 3), jnp.uint8)
    labels = jax.ShapeDtypeStruct((w * shard_len,), jnp.int32)
    new_state, _, next_gidx = jax.eval_shape(step, state, slab, labels,
                                             shard_indices)
    assert next_gidx.shape == (w, mode.emit_size)
    assert new_state.pending_sel.slots.shape == state.pending_sel.slots.shape


@pytest.mark.parametrize("name", ["pipelined-replicated-tel0",
                                  "cadence-sharded-tel0",
                                  "scoretable-host_stream-tel1"])
def test_trainer_builds_the_state_its_mode_names(name):
    """``Trainer`` reads the same value: its state's optional fields, its
    placed shardings and its stream's width are the mode's."""
    trainer = build(dict(MATRIX)[name])
    try:
        mode = trainer._mode
        has = mode.state_fields()
        for f in OPTIONAL:
            assert (getattr(trainer.state, f) is not None) == has[f"has_{f}"]
            assert (getattr(trainer._state_shardings(), f)
                    is not None) == has[f"has_{f}"]
        assert trainer.train_step.__name__ == "sharded"
        assert not hasattr(trainer, "_stream_emit_size")
        if mode.host_stream:
            assert trainer._stream_pipe._staging[0].shape[:2] == (
                trainer.config.world_size, mode.emit_size)
    finally:
        trainer.close()


# --------------------------------------------------------------------------
# (b) every illegal combination raises what it always raised
# --------------------------------------------------------------------------

_HS = dict(data_placement="host_stream")
_TABLE = dict(sampler="scoretable")
_ASYNC = dict(sampler="scoretable", refresh_mode="async")
_TP = {"data": 2, "model": 2}

#: (fields, from_config keywords, a phrase of the message) — one per rule,
#: in the order ``StepMode.from_config`` checks them.
ILLEGAL = [
    (dict(zero_sharding=True), dict(mesh_axes=_TP),
     "zero_sharding flattens params to a vector"),
    (dict(use_pallas=True, label_smoothing=0.1), {},
     "use_pallas requires label_smoothing == 0"),
    (dict(sampler="weird"), {}, "unknown sampler 'weird'"),
    (dict(grad_compression="weird"), {}, "unknown grad_compression 'weird'"),
    (dict(grad_compression="int8"), dict(mesh_axes=_TP),
     "grad_compression='int8' under an active auto mesh axis needs"),
    (dict(pipelined_scoring=True, sampler="groupwise"), {},
     "pipelined_scoring requires sampler='pool', got 'groupwise'"),
    (dict(score_refresh_every=0), {},
     "score_refresh_every must be >= 1, got 0"),
    (dict(score_refresh_every=2, **_TABLE), {},
     "score_refresh_every > 1 requires sampler='pool'"),
    (dict(score_refresh_every=2, pipelined_scoring=True), {},
     "score_refresh_every > 1 does not compose with pipelined_scoring"),
    (dict(refresh_size=0, **_TABLE), {}, "refresh_size must be >= 1, got 0"),
    (dict(table_decay=1.5, **_TABLE), {},
     "table_decay must be in [0, 1], got 1.5"),
    (dict(scoring_dtype="bfloat16", use_importance_sampling=False), {},
     "scoring_dtype only affects the candidate-scoring forward"),
    (dict(refresh_mode="weird"), {}, "unknown refresh_mode 'weird'"),
    (dict(refresh_mode="async"), {},
     "refresh_mode='async' requires sampler='scoretable'"),
    (dict(scorer_workers=0, **_ASYNC), {},
     "scorer_workers must be >= 1, got 0"),
    (dict(snapshot_every=0, **_ASYNC), {},
     "snapshot_every must be >= 1, got 0"),
    (dict(scorer_throttle_s=-1.0, **_ASYNC), {},
     "scorer_throttle_s must be >= 0, got -1.0"),
    (dict(scorer_backend="weird"), {},
     "scorer_backend must be 'host' or 'device', got 'weird'"),
    (dict(scorer_backend="device"), {},
     "scorer_backend='device' requires refresh_mode='async'"),
    (dict(scorer_tenants=2), {},
     "scorer_tenants requires refresh_mode='async'"),
    (dict(importance_score="weird"), {}, "unknown importance_score 'weird'"),
    (dict(dataset="tokens_zipf", importance_score="grad_norm"), {},
     "rows of per-token labels train under sampler='pool'"),
    (dict(variance_probe_every=-1), {},
     "variance_probe_every must be >= 0, got -1"),
    (dict(telemetry=True, variance_probe_every=2), dict(scan_steps=4),
     "variance_probe_every > 0 requires scan_steps == 1"),
    (dict(data_placement="weird"), {}, "unknown data_placement 'weird'"),
    (dict(prefetch_depth=0, **_HS), {}, "prefetch_depth must be >= 1, got 0"),
    (dict(pipelined_scoring=True, **_HS), {},
     "host_stream already pipelines selection"),
    (dict(score_refresh_every=2, **_HS), {},
     "host_stream requires score_refresh_every == 1"),
    (dict(sampler="groupwise", **_HS), {},
     "host_stream supports sampler='pool'|'scoretable'"),
    (dict(**_HS), dict(scan_steps=4),
     "host_stream requires scan_steps == 1"),
    (dict(**_HS), dict(mesh_axes={"data": 2, "model": 1}),
     "host_stream requires a data-only mesh"),
    (dict(fused_input=True, augmentation="iid"), {},
     "fused_input fuses the noniid crop/flip augmentation"),
    (dict(fused_input=True, cutout=True), {},
     "fused_input does not fuse cutout; set cutout=False"),
]


@pytest.mark.parametrize("fields,kw,phrase", ILLEGAL,
                         ids=[p[:44] for _, _, p in ILLEGAL])
def test_illegal_combination_raises_its_message(fields, kw, phrase):
    import re

    from mercury_tpu.config import TrainConfig
    from mercury_tpu.train.mode import StepMode

    config = TrainConfig(**dict(BASE, **fields))
    with pytest.raises(ValueError, match=re.escape(phrase)):
        StepMode.from_config(config, **kw)


def test_one_case_per_rule_and_none_left_in_the_step_builder():
    """33 rules, all in ``StepMode.from_config``; ``make_train_step`` and
    ``make_host_stream_prime`` raise nothing themselves."""
    import ast
    import inspect

    from mercury_tpu.train import mode, step

    def raises(fn):
        tree = ast.parse(inspect.getsource(fn).lstrip())
        return sum(isinstance(n, ast.Raise) for n in ast.walk(tree))

    src = inspect.getsource(mode.StepMode.from_config)
    tree = ast.parse("class _:\n" + src)
    assert sum(isinstance(n, ast.Raise) for n in ast.walk(tree)) == len(
        ILLEGAL) == 33
    assert raises(step.make_train_step) == 0
    assert raises(step.make_host_stream_prime) == 0


def test_frozen_surface():
    import inspect

    from mercury_tpu.train import samplers, state, step

    assert state.MercuryState.__dataclass_fields__.keys() >= set(OPTIONAL)
    assert list(state.MercuryState.__dataclass_fields__)[:7] == [
        "step", "params", "batch_stats", "opt_state", "ema", "stream", "rng"]
    assert state.PendingBatch._fields == ("images", "labels", "scaled_probs")
    assert list(inspect.signature(step.make_train_step).parameters) == [
        "model", "tx", "config", "mesh", "mean", "std", "scan_steps",
        "state_out_shardings", "scoring_model", "io_constraints",
        "image_shape", "trace_facts"]
    from mercury_tpu.train.mode import SAMPLERS

    assert set(samplers.RESIDENT) == set(SAMPLERS)
    assert set(samplers.STREAMED) < set(samplers.RESIDENT)


# --------------------------------------------------------------------------
# the digest tool
# --------------------------------------------------------------------------

def _digest(fn, args, text_dir: str, name: str) -> str:
    import jax

    from mercury_tpu.lint.audit import _canonical_jaxpr_text

    text = _canonical_jaxpr_text(jax.make_jaxpr(fn)(*args))
    if text_dir:
        with open(os.path.join(text_dir, f"{name}.jaxpr.txt"), "w") as f:
            f.write(text)
    return hashlib.sha256(text.encode()).hexdigest()


def digests(text_dir: str = "", only: str = "") -> Dict[str, str]:
    """name → canonical jaxpr digest, for the checkout on ``sys.path``."""
    out: Dict[str, str] = {}

    def want(name):
        return not only or only in name

    for name, fields in MATRIX:
        if not want(name):
            continue
        try:
            trainer = build(fields)
        except ImportError:     # a row newer than the checkout traced
            print(name, "absent", flush=True)
            continue
        try:
            step = (trainer.train_step_many if fields.get("scan_steps", 1) > 1
                    else trainer.train_step)
            out[name] = _digest(step, step_args(trainer), text_dir, name)
            if trainer._stream_pipe is not None and "tel0" in name:
                pname = name.replace("-tel0", "-prime")
                out[pname] = _digest(
                    trainer._stream_prime, step_args(trainer, prime=True),
                    text_dir, pname)
        finally:
            trainer.close()
        print(name, out[name][:16], flush=True)

    from mercury_tpu.lint import audit

    for plan in audit.PLAN_NAMES:
        name = f"plan-{plan}"
        if want(name):
            out[name] = audit.measure_plan(plan).jaxpr_sha256
            print(name, out[name][:16], flush=True)

    name = "bench-r50c100-is"
    if want(name):
        from mercury_tpu.config import TrainConfig
        from mercury_tpu.train.trainer import Trainer
        from perfbench.cell import Cell

        fields = Cell("r50c100-is").train_config_fields(seed=7, trace=False)
        trainer = Trainer(TrainConfig(**fields))
        try:
            out[name] = _digest(trainer.train_step, step_args(trainer),
                                text_dir, name)
        finally:
            trainer.close()
        print(name, out[name][:16], flush=True)
    return out


def _main(argv: List[str]) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repo", required=True,
                    help="checkout whose mercury_tpu is digested")
    ap.add_argument("--out", required=True, help="JSON: name -> sha256")
    ap.add_argument("--texts", default="",
                    help="directory for the jaxpr texts (to diff a pair)")
    ap.add_argument("--only", default="", help="substring of a row's name")
    ns = ap.parse_args(argv)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:] = [os.path.abspath(ns.repo)] + [
        p for p in sys.path
        if os.path.abspath(p or ".") != os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))]
    if ns.texts:
        os.makedirs(ns.texts, exist_ok=True)
    import jax

    out = {"jax": jax.__version__, "digests": digests(ns.texts, ns.only)}
    with open(ns.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
