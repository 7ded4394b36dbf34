"""The partition of the step's device time over the decoder's leaves
(``perfbench/reducers/model_leaf_share.py``, PR 46) off the chip: a
hand-built capture of one step program with known answers, a program with
the parent's scopes only, a program with no decoder at all, and the eleven
metric files over it and over ``instant_arg``. Nothing here is a device
measurement."""

import pytest

from perfbench import cell as cell_mod
from perfbench.reducers import model_leaf_share
from perfbench import trace_reduce
from test_perfbench import MANIFEST

TOKEN_CELLS = ["st21b-is-8k", "kn2-is-8k"]
#: metric -> the cells that list it.
NEW = {
    "embed_share": TOKEN_CELLS, "norm_share": TOKEN_CELLS,
    "attention_proj_share": TOKEN_CELLS,
    "attention_kernel_share": TOKEN_CELLS,
    "attention_glue_share": TOKEN_CELLS, "dense_mlp_share": ["kn2-is-8k"],
    "train_recompute_share": TOKEN_CELLS,
    "model_unscoped_share": TOKEN_CELLS, "head_kernel_rows": TOKEN_CELLS,
    "moe_bounded_share": TOKEN_CELLS, "rows_share": TOKEN_CELLS,
}
#: The partition's metrics (the others read a mark and two counters).
LEAF_OF = {"embed_share": "mercury_embed", "norm_share": "mercury_norm",
           "attention_proj_share": "mercury_attention_proj",
           "attention_kernel_share": "attention_kernel",
           "attention_glue_share": "mercury_attention",
           "dense_mlp_share": "mercury_dense_mlp",
           "model_unscoped_share": "unscoped",
           "rows_share": "mercury_rows"}

SCORE = ("jit(step)/mercury_scoring/mercury_score_forward/M/mercury_rows/"
         "while/body/")
TRAIN = "jit(step)/mercury_train/jvp(M)/mercury_rows/while/body/"
BACK = "jit(step)/mercury_train/transpose(jvp(M))/mercury_rows/while/body/"
REMAT = BACK + "checkpoint/rematted_computation/"
#: One step's ops: (name, path, microseconds); 1,000 a step.
STEP_OPS = [
    ("fusion.23", "jit(step)/mercury_optimizer/adam", 90),
    ("fusion.1", "jit(step)/mercury_scoring/mercury_pool_ingest/gather", 10),
    ("fusion.2", SCORE + "mercury_embed/gather", 20),
    ("fusion.3", SCORE + "checkpoint/mercury_norm/mul", 30),
    ("fusion.4", SCORE + "checkpoint/mercury_attention/"
                 "mercury_attention_proj/dot_general", 100),
    ("fusion.5", SCORE + "checkpoint/mercury_attention/mul", 40),   # glue
    ("splash_mqa_fwd.6", SCORE + "checkpoint/mercury_attention/"
                         "mercury_mla/pallas_call", 150),
    # the latent's norm: inside three scopes, the innermost leaf wins
    ("fusion.7", SCORE + "checkpoint/mercury_attention/mercury_mla/"
                 "mercury_mla_latent/mercury_norm/rsqrt", 10),
    ("fusion.8", SCORE + "checkpoint/mercury_attention/mercury_mla/"
                 "mercury_mla_latent/concatenate", 20),             # glue
    ("fusion.9", SCORE + "checkpoint/mercury_moe/mercury_norm/mul", 20),
    ("sort.10", SCORE + "checkpoint/mercury_moe/mercury_moe_route/sort", 60),
    ("fusion.11", SCORE + "checkpoint/mercury_moe/mercury_moe_shared/"
                  "dot_general", 50),
    ("fusion.12", SCORE + "checkpoint/mercury_moe/add", 40),
    ("fusion.13", SCORE + "checkpoint/mercury_dense_mlp/dot_general", 50),
    ("mercury_head_nll.14", "jit(step)/mercury_scoring/mercury_score_loss/"
                            "while/body/mercury_lm_head/pallas_call", 60),
    ("fusion.15", SCORE + "dynamic_slice", 20),            # lax.map's own
    ("fusion.24", "jit(step)/mercury_scoring/mercury_score_forward/M/"
                  "convert_element_type", 10),      # once a pass: no scope
    ("fusion.16", "jit(step)/mercury_draw/mercury_score_draw_kernel", 10),
    ("fusion.17", TRAIN + "checkpoint/mercury_attention/"
                  "mercury_attention_proj/dot_general", 30),
    ("fusion.18", REMAT + "mercury_attention/mercury_attention_proj/"
                  "dot_general", 30),
    ("fusion.19", REMAT + "mercury_norm/mul", 10),
    ("splash_mqa_dkv.20", BACK + "checkpoint/mercury_attention/"
                          "pallas_call", 80),
    ("fusion.21", BACK + "mercury_embed/scatter-add", 20),
    ("fusion.22", BACK + "closed_call", 20),               # in a row's body
    ("copy.25", "", 20),                                          # no path
]
WANT = {
    "mercury_pool_ingest": 1.0, "mercury_draw": 1.0,
    "mercury_optimizer": 9.0, "mercury_rows": 4.0,
    "mercury_embed": 4.0, "mercury_norm": 7.0,
    "mercury_attention_proj": 16.0, "attention_kernel": 23.0,
    "mercury_attention": 6.0, "mercury_moe_route": 6.0,
    "mercury_moe_shared": 5.0, "mercury_moe": 4.0,
    "mercury_dense_mlp": 5.0, "mercury_lm_head": 6.0, "unscoped": 3.0,
}


def _capture(ops, steps=2):
    """A capture of ``steps`` runs of the step program ``jit_step`` on one
    chip, each holding ``ops`` end to end: ``(name, path, microseconds)``."""
    events = []
    for step in range(steps):
        at = 2000.0 * step
        events.append({"ph": "X", "name": "jit_step(1)", "ts": at,
                       "dur": float(sum(us for _, _, us in ops)), "pid": 1,
                       "tid": 1, "_pname": "/device:TPU:0",
                       "_tname": "XLA Modules"})
        for name, path, us in ops:
            events.append({"ph": "X", "name": name, "ts": at, "dur": us,
                           "pid": 1, "tid": 2, "_pname": "/device:TPU:0",
                           "_tname": "XLA Ops", "args": {"tf_op": path}})
            at += us
    return trace_reduce.Capture(events, "jit_step")


def _ctx(ops=STEP_OPS, spans=()):
    return dict(capture=_capture(ops), steps=2, spans=list(spans),
                peak_flops=None)


def _metric(ctx, name):
    spec = cell_mod.layer_metric(name)
    return cell_mod.reducer(spec["reducer"])(ctx, **spec.get("args", {}))


@pytest.mark.parametrize("leaf", sorted(WANT))
def test_a_leafs_share_of_a_hand_built_step(leaf):
    assert model_leaf_share.reduce(_ctx(), leaf) == pytest.approx(WANT[leaf])


def test_the_leaves_and_the_remainder_sum_to_100():
    ctx = _ctx()
    leaves = model_leaf_share.LEAVES + (model_leaf_share.UNSCOPED,)
    shares = {leaf: model_leaf_share.reduce(ctx, leaf) for leaf in leaves}
    assert shares.pop("mercury_grad_sync") is None      # one chip: no op
    assert shares == pytest.approx(WANT)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert sum(WANT.values()) == 100.0
    # ... and the capture is walked once for all of them
    assert list(ctx["_model_leaves"][0]) and len(ctx["_model_leaves"]) == 1


@pytest.mark.parametrize("text, want", [
    # the innermost leaf on the path wins, whatever it is nested in
    ("jit(s)/mercury_train/mercury_moe/mercury_norm/mul", "mercury_norm"),
    ("jit(s)/mercury_moe/mercury_moe_route/sort", "mercury_moe_route"),
    ("jit(s)/mercury_attention/mercury_mla/mercury_mla_latent/"
     "mercury_attention_proj/dot_general", "mercury_attention_proj"),
    ("jit(s)/mercury_attention/mercury_mla/mercury_mla_latent/slice",
     "mercury_attention"),
    # a name that begins as a leaf's does is not that leaf
    ("jit(s)/mercury_moe_shared/dot_general", "mercury_moe_shared"),
    ("jit(s)/mercury_attention_proj/dot_general", "mercury_attention_proj"),
    ("jit(s)/transpose(jvp(mercury_moe))/mercury_moe_route/gather",
     "mercury_moe_route"),
    # a kernel's name wins over every scope on its path
    ("splash_mqa_fwd.3 jit(s)/mercury_attention/mercury_attention_proj/x",
     "attention_kernel"),
    ("mercury_head_nll.6 jit(s)/mercury_scoring/mercury_lm_head/"
     "mercury_norm/x", "mercury_lm_head"),
    # the step's scopes that are no leaf name nothing
    ("jit(s)/mercury_scoring/mercury_score_forward/while/body/copy",
     "unscoped"),
    ("jit(s)/mercury_train/mercury_optimizer/mul", "mercury_optimizer"),
    ("fusion.12", "unscoped"),
])
def test_the_order_of_precedence(text, want):
    assert model_leaf_share.leaf_of(text) == want


def test_the_recomputed_forward_is_the_marked_ops_of_the_train_pass():
    ctx = _ctx()
    assert _metric(ctx, "train_recompute_share") == pytest.approx(4.0)
    # the mark outside mercury_train (an evaluated checkpoint) is not it
    elsewhere = _ctx([("fusion.1", SCORE + "rematted_computation/"
                       "mercury_norm/mul", 10),
                      ("fusion.2", TRAIN + "mercury_norm/mul", 90)])
    assert _metric(elsewhere, "train_recompute_share") is None


def test_the_parents_scopes_leave_a_fifth_unscoped():
    """The program before PR 46 under this reducer: ``mercury_attention``,
    ``mercury_moe`` and the head are there, the inner leaves are not: their
    metrics are left out, nothing raises, and the remainder is a number."""
    was = "jit(step)/mercury_scoring/mercury_score_forward/M/while/body/"
    ctx = _ctx([
        ("fusion.8", "jit(step)/mercury_optimizer/adam", 20),
        ("fusion.1", was + "gather", 20),                  # the embedding
        ("fusion.2", was + "checkpoint/M._layer/reduce_sum", 80),
        ("fusion.3", was + "checkpoint/M._layer/mercury_attention/"
                     "dot_general", 300),
        ("splash_mqa_fwd.4", was + "checkpoint/M._layer/"
                             "mercury_attention/pallas_call", 200),
        ("fusion.5", was + "checkpoint/M._layer/mercury_moe/"
                     "mercury_moe_route/sort", 100),
        ("fusion.6", was + "checkpoint/M._layer/add", 100),
        ("fusion.7", "jit(step)/mercury_scoring/mercury_score_loss/"
                     "mercury_lm_head/dot_general", 180),
    ])
    got = {name: _metric(ctx, name) for name in NEW}
    assert got == {
        "embed_share": None, "norm_share": None,
        "attention_proj_share": None, "dense_mlp_share": None,
        "train_recompute_share": None, "head_kernel_rows": None,
        "moe_bounded_share": None, "rows_share": None,
        "attention_kernel_share": pytest.approx(20.0),
        "attention_glue_share": pytest.approx(30.0),
        "model_unscoped_share": pytest.approx(20.0)}


def test_a_program_without_a_decoder_reports_none_of_it():
    """``r50c100-is``: the step's own leaves are there, no model leaf is,
    so the partition says nothing, not even its remainder."""
    ctx = _ctx([
        ("fusion.1", "jit(step)/mercury_scoring/mercury_pool_ingest/x", 10),
        ("fusion.2", "jit(step)/mercury_scoring/mercury_score_forward/"
                     "ResNet/conv", 700),
        ("fusion.3", "jit(step)/mercury_train/transpose(jvp(ResNet))/"
                     "conv", 200),
        ("fusion.4", "jit(step)/mercury_draw/x", 50),
        ("fusion.5", "jit(step)/mercury_optimizer/adam", 40)],
        spans=[{"name": "trainer/head_kernel_rows", "ph": "i",
                "args": {"rows": 0, "plain_rows": 0}}])
    for leaf in model_leaf_share.LEAVES + (model_leaf_share.UNSCOPED,):
        assert model_leaf_share.reduce(ctx, leaf) is None
    assert _metric(ctx, "train_recompute_share") is None
    reported = {m["name"] for m in cell_mod.Cell("r50c100-is").per_layer()}
    assert not reported & set(NEW)


def test_the_two_counters_are_read_off_their_instants():
    spans = [
        {"name": "trainer/head_kernel_rows", "ph": "i",
         "args": {"rows": 10, "plain_rows": 0}},
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"held_pair_share": 0.1, "bounded_share": 1.0}},
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"held_pair_share": 0.1, "bounded_share": 0.75}}]
    ctx = _ctx(spans=spans)
    assert _metric(ctx, "head_kernel_rows") == pytest.approx(10.0)
    assert _metric(ctx, "moe_bounded_share") == pytest.approx(0.875)
    assert _metric(_ctx(), "head_kernel_rows") is None
    assert _metric(_ctx(), "moe_bounded_share") is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metrics_file_entry_and_cells(name):
    """Each of the eleven is a file over a reducer and arguments that exist,
    an entry that says what the file says, and the token cells' list."""
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    spec = cell_mod.layer_metric(name)
    assert entry["workloads"] == NEW[name]
    assert (entry["layer"], entry["moves"]) == ("model step",
                                                "train_examples_per_s")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for cell in NEW[name]:
        assert name in {m["name"] for m in cell_mod.Cell(cell).per_layer()}
    if name in LEAF_OF:
        assert spec["reducer"] == "model_leaf_share"
        assert spec["args"] == {"leaf": LEAF_OF[name]}
        assert LEAF_OF[name] in (model_leaf_share.MODEL_LEAVES
                                 + (model_leaf_share.UNSCOPED,))
        assert (spec["unit"], spec["source"]) == ("%", "device_trace")
    # the reducer takes the file's arguments and reads them off the step
    value = _metric(_ctx(spans=[
        {"name": "trainer/head_kernel_rows", "ph": "i", "args": {"rows": 10}},
        {"name": "trainer/moe_load", "ph": "i",
         "args": {"bounded_share": 1.0}}]), name)
    assert value is not None and value > 0


def test_the_new_entries_stand_at_the_end_in_the_issues_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
