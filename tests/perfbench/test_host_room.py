"""The check's host footprint (``perfbench/README.md``, "The check's host
footprint"): whatever the check reads of a parameter-sized tree it reads a
leaf at a time and keeps as that leaf's number, so that after the window the
host never holds more than seven float32 trees of the parameters' size; and
the gaps read from leaf norms are the gaps read from trees. On the CPU at a
small size; nothing here is a device measurement."""

import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import register_tiny_latent_lm, register_tiny_lm
from perfbench import reference, replay, run

ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8}
VOCAB, T = 96, 32


# ------------------------------------------- (a) the number of trees held
def _register_room_lm() -> str:
    """``smallthinker-tiny``'s layers with experts wide enough that the
    parameters (6,489,216: twelve leaves of 524,288 and small ones) dwarf
    everything else a rehearsal holds on the host, and no leaf is more than
    a twelfth of them."""
    from mercury_tpu.models.decoder import LM_WIDTHS, LMWidths

    LM_WIDTHS.setdefault("smallthinker-room", LMWidths(
        num_layers=4, d_model=128, num_heads=4, num_kv_heads=1, head_dim=32,
        num_experts=16, top_k=3, expert_width=256, window=8,
        rope_theta=10_000.0))
    return "smallthinker-room"


PARAMETERS = 6_489_216
#: The cell's whole command at that size (``test_smallthinker.TINY``'s
#: shape: float32 on both sides).
ROOM = {
    "train_config": {"model": _register_room_lm(), "model_cut": [4, 0, 16],
                     "num_classes": VOCAB, "seq_len": T, "batch_size": 2,
                     "presample_batches": 3, "compute_dtype": "float32",
                     "base_lr": 1e-3, "log_every": 10},
    "steps_per_call": 10, "trace_calls": 2,
    "reference": {"file": "perfbench/references/smallthinker.py",
                  "head_dim": 32, "num_key_value_heads": 1,
                  "rope_theta": 10000.0, "rope_layout": [0, 1, 1, 1],
                  "sliding_window_layout": [0, 1, 1, 1],
                  "sliding_window_size": 8, "top_k": 3,
                  "first_expert_held": 0, "rms_norm_eps": 1e-6,
                  "query_block": 8,
                  "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9},
                  "adam": ADAM},
    "check": {"sample_rows": 2, "block_rows": 1, "train_block_rows": 1,
              "logit_gap_limit": 1e-4, "eval_loss_gap_limit": 1e-3,
              "loss_gap_limit": 1e-4, "grad_norm_gap_limit": 1e-3,
              "update_norm_gap_limit": 0.05, "weight_gap_limit": 1e-4,
              "window_update_rms_floor": 1e-5},
}
#: Seven trees (the start's ``params``, ``mu``, ``nu``, step 2's ``mu``, the
#: last ``params``, the warm and the final weights) and half a tree for one
#: leaf's float64 and Adam's temporaries of one leaf ...
TREES = 7.5
#: ... plus what NumPy holds that is no parameter: the data, the recorded
#: batches, the sample's logits.
CONSTANT = 4 * 2 ** 20


def _numpy_bytes() -> int:
    """What NumPy holds now, as ``tracemalloc`` sees it."""
    mine = tracemalloc.DomainFilter(True, np.lib.tracemalloc_domain)
    return sum(stat.size for stat in tracemalloc.take_snapshot()
               .filter_traces([mine]).statistics("filename"))


def test_after_the_window_the_host_holds_seven_trees_at_most(monkeypatch,
                                                             capsys):
    """From the end of the window to the last line, read by ``tracemalloc``
    (NumPy reports its buffers to it). The CPU backend hands NumPy a view of
    its own buffer where the chip's hands it a copy, and ``tracemalloc``
    sees no view: so the two functions that bring a tree to the host
    (``replay._host``, ``run._host_copy``) are made to copy here, as they do
    there. The peak is taken anew once the final weights are on the host,
    the worst instant by the count of trees; what the interpreter holds then
    beside NumPy's buffers (jax's caches, the trainer: a tree's worth at
    this size, and less once the trainer is let go) is taken off it. The
    parent's forms (two float64 trees of the program's side, the
    reference's gradient kept, three new trees an update, ``_diff``: 68 B a
    parameter) read 17.4 trees here."""
    real_host, real_copy = replay._host, run._host_copy
    own = lambda tree: jax.tree.map(np.array, tree)  # noqa: E731
    others = []     # the interpreter's own bytes as each tree arrived

    def host_copy(tree):
        tree = own(real_copy(tree))
        tracemalloc.reset_peak()    # the second time: the window has closed
        others.append(tracemalloc.get_traced_memory()[0] - _numpy_bytes())
        return tree

    monkeypatch.setattr(replay, "_host", lambda tree: own(real_host(tree)))
    monkeypatch.setattr(run, "_host_copy", host_copy)
    tracemalloc.start()
    try:
        result = run.run_cell("st21b-is-8k", 2 ** 31 + 48, 0.5, False,
                              rehearsal=ROOM)
        peak = tracemalloc.get_traced_memory()[1] - others[-1]
    finally:
        tracemalloc.stop()
    assert len(others) == 2     # the warm weights, the final weights
    assert result["correct"] is True, capsys.readouterr().out[-3000:]
    tree = 4 * PARAMETERS
    assert 7 * tree <= peak <= TREES * tree + CONSTANT, (
        f"{peak / tree:.2f} float32 trees of the parameters' size on the "
        f"host after the window, want 7 to {TREES} and {CONSTANT} bytes")


# ------------------------------------ (b) a gap from leaf norms is the gap
# What a gap is, stated plainly on whole trees (the forms the replay had
# until PR 48, which kept every tree they name).
def _tree_norms(tree):
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64)))
                     for a in jax.tree.leaves(tree)])


def _tree_diff(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - y, a, b)


def _tree_worst_leaf_gap(program, ref):
    p, r = _tree_norms(program), _tree_norms(ref)
    over = np.maximum(r, np.median(r))
    if not over.all():
        return 0.0 if (p == r).all() else float("inf")
    return float(np.max(np.abs(p - r) / over))


def _tree_norm_gap(program, ref):
    p = float(np.sqrt(np.sum(np.square(_tree_norms(program)))))
    r = float(np.sqrt(np.sum(np.square(_tree_norms(ref)))))
    return abs(p - r) / r if r else (0.0 if p == r else float("inf"))


def _tree_adam_update(params, mu, nu, count, grads, lr, b1, b2, eps):
    t, tree = count + 1, jax.tree.structure(params)
    new_p, new_mu, new_nu = [], [], []
    for p, m, v, g in zip(*map(jax.tree.leaves, (params, mu, nu, grads))):
        g = np.asarray(g, np.float32)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * np.square(g)
        step = (m / (1.0 - b1 ** t)) / (np.sqrt(v / (1.0 - b2 ** t)) + eps)
        new_p.append(np.asarray(p - lr * step, np.float32))
        new_mu.append(m.astype(np.float32))
        new_nu.append(v.astype(np.float32))
    return tuple(jax.tree.unflatten(tree, leaves)
                 for leaves in (new_p, new_mu, new_nu))


def _resnet_parameters():
    from test_perfbench import _tiny_resnet

    return _tiny_resnet("bottleneck", jnp.float32).init(
        jax.random.key(0), jnp.zeros((1, 32, 32, 3)), train=False)["params"]


def _decoder_parameters(name, cut):
    from mercury_tpu.models import create_model

    return create_model(name, num_classes=VOCAB, compute_dtype="float32",
                        cut=cut).init(
        jax.random.key(0), jnp.zeros((2, T), jnp.int32),
        train=False)["params"]


#: The parameter tree of each cell's family at its CPU size.
FAMILY_TREES = {
    "resnet": _resnet_parameters,
    "smallthinker": lambda: _decoder_parameters(register_tiny_lm(),
                                                (4, 0, 4)),
    "kanana": lambda: _decoder_parameters(register_tiny_latent_lm(),
                                          (3, 0, 4, 0, 2)),
}


def _seeded_sides(family, case, seed=48):
    """Seeded stand-ins, on the family's tree, for what a recorder keeps of
    the parameters and moments, and for the reference's first gradient and
    last parameters. ``zero_leaf``: a leaf that neither side moves and whose
    gradient is nought (a selection bias); ``still_reference``: a reference
    that moves no leaf, beside a program that moves; ``both_still``: and
    one that does not either."""
    shapes = jax.eval_shape(FAMILY_TREES[family])
    rng = np.random.default_rng(seed)

    def draw(scale, like=None):
        new = jax.tree.map(lambda a: (scale * rng.standard_normal(a.shape))
                           .astype(np.float32), shapes)
        return new if like is None else jax.tree.map(np.add, like, new)

    p0, mu0 = draw(0.02), draw(1e-3)
    grad = draw(1e-2)
    mu1 = jax.tree.map(lambda m, g: (0.9 * m + 0.1 * g).astype(np.float32),
                       mu0, draw(1e-4, grad))
    p3, p_ref = draw(3e-6, p0), draw(3e-6, p0)
    if case == "zero_leaf":
        first = lambda tree, new: jax.tree.unflatten(    # noqa: E731
            jax.tree.structure(tree), [new] + jax.tree.leaves(tree)[1:])
        still = jax.tree.leaves(p0)[0]
        zeros = np.zeros_like(still)
        p3, p_ref, grad = first(p3, still), first(p_ref, still), first(
            grad, zeros)
        mu0, mu1 = first(mu0, zeros), first(mu1, zeros)
    elif case in ("still_reference", "both_still"):
        p_ref, grad = p0, jax.tree.map(np.zeros_like, grad)
        if case == "both_still":
            p3, mu1 = p0, jax.tree.map(lambda m: np.float32(0.9) * m, mu0)
    steps = [dict(params=p0, mu=mu0)] + [
        dict(metrics={"train/loss": 1.0}) for _ in range(replay.STEPS)]
    steps[1]["mu"], steps[replay.STEPS]["params"] = mu1, p3
    return steps, grad, p_ref


@pytest.mark.parametrize("case", ["seeded", "zero_leaf", "still_reference",
                                  "both_still"])
@pytest.mark.parametrize("family", sorted(FAMILY_TREES))
def test_gaps_from_leaf_norms_are_the_gaps_from_trees(family, case):
    steps, ref_grad, p_ref = _seeded_sides(family, case)
    p0, b1 = steps[0]["params"], ADAM["b1"]
    want = dict(
        grad_norm_gap=_tree_norm_gap(jax.tree.map(
            lambda m1, m0: (np.asarray(m1, np.float64) - b1 * m0)
            / (1.0 - b1), steps[1]["mu"], steps[0]["mu"]), ref_grad),
        update_norm_gap=_tree_worst_leaf_gap(
            _tree_diff(steps[replay.STEPS]["params"], p0),
            _tree_diff(p_ref, p0)))
    system = replay.system_steps(steps, {"adam": ADAM})
    assert "mu" not in steps[1] and "params" not in steps[replay.STEPS]
    ref = dict(grad=replay._leaf_norms(lambda g: np.asarray(g, np.float64),
                                       ref_grad),
               change=replay._leaf_norms(replay._minus, p_ref, p0))
    got = dict(grad_norm_gap=replay.norm_gap(system["grad"], ref["grad"]),
               update_norm_gap=replay.worst_leaf_gap(system["change"],
                                                     ref["change"]))
    if case == "still_reference":
        assert want == got == dict(grad_norm_gap=float("inf"),
                                   update_norm_gap=float("inf"))
    elif case == "both_still":
        assert want == got == dict(grad_norm_gap=0.0, update_norm_gap=0.0)
    else:
        assert 0.0 < want["grad_norm_gap"] < 1.0
        assert 0.0 < want["update_norm_gap"] < 1.0
        for name in want:
            assert got[name] == pytest.approx(want[name], rel=1e-12), name


@pytest.mark.parametrize("family", sorted(FAMILY_TREES))
def test_adam_a_leaf_at_a_time_is_adam(family):
    """``reference.adam_update`` on lists of leaves, each replaced as its
    update exists, against the update that builds three new trees: the same
    bits after three updates, and the lists it was given hold the result."""
    steps, grad, _ = _seeded_sides(family, "seeded")
    p0, mu0 = steps[0]["params"], steps[0]["mu"]
    nu0 = jax.tree.map(np.square, mu0)
    want, lists = (p0, mu0, nu0), [jax.tree.leaves(t)
                                   for t in (p0, mu0, nu0)]
    for count in range(3, 6):
        args = (count, grad, 1e-3, ADAM["b1"], ADAM["b2"], ADAM["eps"])
        want = _tree_adam_update(*want, *args)
        reference.adam_update(*lists, count, jax.tree.leaves(grad),
                              *args[2:])
    for tree, leaves in zip(want, lists):
        for a, b in zip(jax.tree.leaves(tree), leaves):
            assert b.dtype == np.float32 and np.array_equal(a, b)
    assert not np.array_equal(jax.tree.leaves(p0)[0], lists[0][0])


def test_the_replay_reads_what_the_trees_read():
    """The whole replay on hand-made recorded steps (the token fixture):
    the three gaps of the leaf-by-leaf sides against those of a reference
    that keeps its trees (the loop the replay had until PR 48)."""
    from test_families import TOKENS, _token_data, _token_steps

    fields = {"world_size": 1, "base_lr": 0.01, "steps_per_epoch": 1,
              "num_epochs": 1000, "batch_size": 4, "presample_batches": 3}
    params, x, y = _token_data(n=40)
    steps = _token_steps(params, x, y, fields)
    adam, start = TOKENS["adam"], steps[0]
    b1 = float(adam["b1"])
    loss_and_grad = reference.make_loss_and_grad(TOKENS)
    state, losses = (start["params"], start["mu"], start["nu"]), []
    for i in range(replay.STEPS):
        batch, count = steps[i]["pending"], start["count"] + i
        loss, grads = loss_and_grad(state[0], *(
            batch[k][0] for k in (replay.INPUTS, replay.LABELS,
                                  replay.SCALED_PROBS)))
        grads = jax.tree.map(np.asarray, grads)
        losses.append(float(loss))
        if i == 0:
            first = grads
        state = _tree_adam_update(
            *state, count, grads, reference.cosine_lr(count, 0.01, 1000), b1,
            float(adam["b2"]), float(adam["eps"]))
    want = dict(
        loss_gap=max(abs(s["metrics"]["train/loss"] - b) / b
                     for s, b in zip(steps[1:], losses)),
        grad_norm_gap=_tree_norm_gap(jax.tree.map(
            lambda m1, m0: (np.asarray(m1, np.float64) - b1 * m0)
            / (1.0 - b1), steps[1]["mu"], start["mu"]), first),
        update_norm_gap=_tree_worst_leaf_gap(
            _tree_diff(steps[replay.STEPS]["params"], start["params"]),
            _tree_diff(state[0], start["params"])))
    got = replay.step_gaps(replay.system_steps(steps, TOKENS),
                           replay.reference_steps(steps, TOKENS, fields))
    assert "mu" not in start and "nu" not in start and "params" in start
    assert set(got) == set(want)
    for name in want:
        assert 0.0 < want[name] < 1e-3
        assert got[name] == pytest.approx(want[name], rel=1e-12), name


@pytest.mark.parametrize("family", sorted(FAMILY_TREES))
def test_the_windows_update_a_leaf_at_a_time_is_the_same_number(family):
    from perfbench import check

    steps, _, p_ref = _seeded_sides(family, "seeded")
    p0 = steps[0]["params"]
    a, b = jax.tree.leaves(p0), jax.tree.leaves(p_ref)
    total = sum(float(np.sum(np.square(np.asarray(y, np.float64) - x)))
                for x, y in zip(a, b))
    want = (total / sum(x.size for x in a)) ** 0.5 / 7
    assert check.update_rms(p0, p_ref, 7) == want > 0.0
