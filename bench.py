"""Headline benchmark: Mercury importance-sampled training throughput on one
TPU chip (images/sec/chip), ResNet-18 @ CIFAR-10 shapes — the reference's
live config (``pytorch_collab.py:255``, batch 32, 320-candidate pool).

``vs_baseline`` follows BASELINE.json's metric definition — "images/sec/chip
vs uniform-SGD baseline": the ratio of Mercury-IS training throughput to the
same fused pipeline with importance sampling disabled (uniform draws, unit
weights). IS scores a 10× candidate pool per step, so this ratio is the
per-step cost Mercury pays for its sample-efficiency win; the time-to-
accuracy comparison is in benchmarks/ (convergence runs need real CIFAR).

An additional diagnostic (not the JSON line) reports the fused step against
a faithful *unfused* reproduction of the reference's loop structure — 10
separate scoring forwards + host-side multinomial + separate train step
(``pytorch_collab.py:95-117``) — i.e. what a direct port would do.

One process, which measures on the accelerator jax finds — and only
there: with no chip it exits non-zero and prints no record. There is no
probe subprocess, no cached record, no CPU stand-in; a run that could not
reach the chip is a failed run. Every arm must succeed.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"platform", "device_kind", "device_count", ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HEADLINE_METRIC = "resnet18_cifar10_mercury_is_train_throughput"
#: Record schema: v2 added the ``schema`` field itself and the optional
#: ``plan`` block (--plan: resolved plan + auto-planner decision table).
BENCH_SCHEMA = "mercury_bench_v2"

#: The headline protocol: the reference's live shape, 25-step scan chunks.
SCALE = dict(batch=32, pool=10, warmup=5, steps=30, scan=25, scan_calls=8)


def _build(sc: dict, use_is: bool = True, scan_steps: int = 1, **kw):
    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    config = TrainConfig(
        **kw,
        model="resnet18",
        dataset="synthetic",
        world_size=1,
        batch_size=sc["batch"],
        presample_batches=sc["pool"],
        use_importance_sampling=use_is,
        steps_per_epoch=sc["steps"],
        num_epochs=1,
        eval_every=0,
        log_every=0,
        scan_steps=scan_steps,
        seed=0,
    )
    mesh = make_mesh(1, config.mesh_axis)
    return Trainer(config, mesh=mesh)


def _step_flops(trainer) -> float:
    """FLOPs of one dispatch of the measured step, from XLA's compiled
    cost analysis."""
    ds = trainer.dataset
    step_fn = trainer.train_step_many or trainer.train_step
    cost = step_fn.lower(
        trainer.state, trainer._step_x, ds.y_train, ds.shard_indices
    ).compile().cost_analysis()
    return float(cost["flops"])


def bench_fused(trainer, sc: dict) -> float:
    """Throughput of the fused step; with config.scan_steps > 1 each
    dispatch advances a whole K-step chunk (one host round-trip per chunk —
    the TPU-native answer to being dispatch-latency-bound at batch 32)."""
    ds = trainer.dataset
    state = trainer.state
    step_fn = trainer.train_step_many or trainer.train_step
    k = trainer.scan_steps
    calls = sc["scan_calls"] if k > 1 else sc["steps"]
    # Warmup covers the compile (Trainer commits state and inputs on the
    # mesh, so there is one) and lets the dispatch queue settle.
    for _ in range(3 if k > 1 else sc["warmup"]):
        state, metrics = step_fn(state, trainer._step_x, ds.y_train, ds.shard_indices)
        np.asarray(metrics["train/loss"])
    # Timing fence = host fetch of the final loss: the transfer cannot
    # complete before the last step that produced it has run.
    t0 = time.perf_counter()
    for _ in range(calls):
        state, metrics = step_fn(state, trainer._step_x, ds.y_train, ds.shard_indices)
    np.asarray(metrics["train/loss"])
    dt = time.perf_counter() - t0
    trainer.state = state
    return sc["batch"] * calls * k / dt


def bench_unfused(trainer, sc: dict) -> float:
    """Reference-loop-shaped baseline: 10 separate jitted scoring forwards
    with host-side accumulation + host-side multinomial + separate jitted
    train step (the structure of ``update_samples`` + ``train``,
    ``pytorch_collab.py:89-164``)."""
    import jax
    import jax.numpy as jnp
    import optax

    from mercury_tpu.models import create_model
    from mercury_tpu.sampling.importance import per_sample_loss, reweighted_loss

    ds, cfg = trainer.dataset, trainer.config
    batch, pool = sc["batch"], sc["pool"]
    # Local (unsynced) BN, like the reference's per-worker nets — and this
    # baseline runs under plain jit, outside any mesh axis.
    model = create_model(cfg.model, num_classes=ds.num_classes,
                         compute_dtype=cfg.compute_dtype, param_dtype=cfg.param_dtype)
    params = trainer.state.params
    batch_stats = trainer.state.batch_stats
    opt_state = trainer.tx.init(params)

    @jax.jit
    def score_one(params, batch_stats, images, labels):
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, train=True,
            mutable=["batch_stats"],
        )
        return per_sample_loss(logits, labels)

    @jax.jit
    def train_one(params, batch_stats, opt_state, images, labels, scaled_probs):
        def loss_fn(p):
            logits, st = model.apply(
                {"params": p, "batch_stats": batch_stats}, images, train=True,
                mutable=["batch_stats"],
            )
            return reweighted_loss(per_sample_loss(logits, labels), scaled_probs), st

        (loss, st), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = trainer.tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, st["batch_stats"], opt_state, loss

    host_rng = np.random.default_rng(0)
    x = np.asarray(ds.x_train, np.float32) / 255.0
    y = np.asarray(ds.y_train)
    n_train = len(x)

    def one_step(params, batch_stats, opt_state):
        losses, datas, labels = [], [], []
        for _ in range(pool):  # 10 separate device calls (:95)
            idx = host_rng.integers(0, n_train, batch)
            img = jnp.asarray(x[idx])
            lab = jnp.asarray(y[idx])
            losses.append(np.asarray(score_one(params, batch_stats, img, lab)))
            datas.append(img)
            labels.append(lab)
        pool_losses = np.concatenate(losses)  # host cat (:108)
        scores = pool_losses + 0.5 * pool_losses.mean()
        probs = scores / scores.sum()
        sel = host_rng.choice(len(probs), batch, replace=True, p=probs)  # host multinomial (:114)
        pool_x = jnp.concatenate(datas)
        pool_y = jnp.concatenate(labels)
        scaled = jnp.asarray(probs[sel] * len(probs), jnp.float32)
        return train_one(params, batch_stats, opt_state,
                         pool_x[sel], pool_y[sel], scaled)

    for _ in range(sc["warmup"]):
        params, batch_stats, opt_state, loss = one_step(params, batch_stats, opt_state)
    np.asarray(loss)
    t0 = time.perf_counter()
    for _ in range(sc["steps"]):
        params, batch_stats, opt_state, loss = one_step(params, batch_stats, opt_state)
    np.asarray(loss)
    dt = time.perf_counter() - t0
    return sc["batch"] * sc["steps"] / dt


def _run_bench(plan: str = "", plan_budget: int = 0) -> dict:
    """The measurement itself; ``main()`` has checked that an accelerator
    is there.

    With ``plan`` set (``--plan auto`` or a concrete plan name) the
    headline IS trainer resolves through the auto-planner
    (plan/auto.py) and the record carries the resolved plan + decision
    table — the next chip window then measures what the planner would
    actually pick. Plan mode pins ``scan_steps=1``: several plans
    (host_stream family) reject scan chunking, and the planner must be
    free to pick them."""
    import jax

    from mercury_tpu.obs.accounting import peak_flops

    dev = jax.devices()[0]
    platform = dev.platform
    sc = SCALE

    plan_kw = {}
    if plan:
        plan_kw = {"plan": plan, "plan_memory_budget_bytes": plan_budget}
    trainer = _build(sc, use_is=True,
                     scan_steps=1 if plan else sc["scan"], **plan_kw)
    if plan and trainer.config.data_placement == "host_stream":
        # The planner picked a host-streamed plan: the bare step has the
        # pop→step→push signature, so measure through fit() (eval/log/
        # checkpoint cadences are all off in the bench config).
        t0 = time.perf_counter()
        trainer.fit()
        dt = time.perf_counter() - t0
        fused_ips = sc["batch"] * sc["steps"] / dt
    else:
        fused_ips = bench_fused(trainer, sc)
    # FLOPs AFTER the timing: .lower().compile() is an AOT path that does
    # not share the jit dispatch cache, so doing it first would pay the
    # scan-chunk compile twice before any measurement. With the persistent
    # compilation cache enabled (main()) this compile is a disk hit.
    flops_per_dispatch = _step_flops(trainer)
    uniform_ips = bench_fused(_build(sc, use_is=False, scan_steps=sc["scan"]), sc)
    pipelined_ips = bench_fused(
        _build(sc, use_is=True, scan_steps=sc["scan"],
               pipelined_scoring=True), sc)
    # Score-refresh cadence K=8: the measured cost lever (the full
    # ladder is benchmarks/is_cost_ladder.py). Diagnostic only — the
    # headline keeps the reference's every-step-scoring semantics.
    cadence_ips = bench_fused(
        _build(sc, use_is=True, scan_steps=sc["scan"],
               score_refresh_every=8), sc)
    per_step_trainer = _build(sc, use_is=True)
    per_step_ips = bench_fused(per_step_trainer, sc)
    unfused_ips = bench_unfused(per_step_trainer, sc)
    headline_ips = max(fused_ips, pipelined_ips)  # best IS variant

    # MFU: FLOPs/img (from the compiled step) × img/s ÷ chip peak. KNOWN
    # UNDER-COUNT, left for the benchmark rewrite (ROADMAP S0): XLA's cost
    # analysis counts a scan body ONCE, so dividing the chunk program's
    # FLOPs by batch × scan under-states FLOPs/img by the scan length.
    flops_per_img = flops_per_dispatch / (sc["batch"] * sc["scan"])
    mfu = round(flops_per_img * headline_ips / peak_flops(dev.device_kind), 4)

    print(
        f"# diagnostics [{platform}/{dev.device_kind}]: "
        f"fused_is_scan{sc['scan']}={fused_ips:.1f} "
        f"pipelined_is_scan{sc['scan']}={pipelined_ips:.1f} "
        f"cadence_k8_scan{sc['scan']}={cadence_ips:.1f} "
        f"uniform_sgd_scan{sc['scan']}={uniform_ips:.1f} "
        f"fused_is_per_step_dispatch={per_step_ips:.1f} "
        f"unfused_reference_loop={unfused_ips:.1f} img/s"
        f" (fused vs unfused: {fused_ips / unfused_ips:.1f}x)",
        file=sys.stderr,
    )
    record = {
        "schema": BENCH_SCHEMA,
        "metric": HEADLINE_METRIC,
        "value": round(headline_ips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(headline_ips / uniform_ips, 3),
        "mfu": mfu,
        "platform": platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        # The cost lever's recovery, alongside the reference-semantics
        # headline: cadence K=8 throughput and its ratio to uniform.
        "cadence_k8": round(cadence_ips, 2),
        "cadence_k8_vs_baseline": round(cadence_ips / uniform_ips, 3),
    }
    if plan:
        # Resolved plan + full decision table: what the auto-planner
        # picked for THIS device/topology, and why everything else lost.
        decision = getattr(trainer, "_plan_decision", None)
        record["plan"] = {
            "requested": plan,
            "selected": decision.selected if decision else plan,
            "memory_budget_bytes": plan_budget,
            "decision_table": decision.table() if decision else None,
        }
        trainer.close()  # plan arms may own scorer/prefetch fleets
    return record


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--plan", default=os.environ.get("MERCURY_BENCH_PLAN", ""),
        help="resolve the headline IS trainer through the auto-planner: "
             "'auto' picks the ranked winner, a concrete plan name "
             "(dp, zero, hs, async, …) forces that plan; the record "
             "carries the resolved plan + decision table (schema "
             f"{BENCH_SCHEMA}). Default: $MERCURY_BENCH_PLAN, else off")
    p.add_argument(
        "--plan-memory-budget-bytes", type=int,
        default=int(os.environ.get("MERCURY_BENCH_PLAN_BUDGET", "0") or 0),
        help="auto-planner per-device memory budget in bytes (0 = "
             "unbounded); candidates over budget are hard-excluded")
    p.add_argument(
        "--profile-breakdown",
        default=os.environ.get("MERCURY_BENCH_BREAKDOWN", ""),
        help="path to a device_time_breakdown.json (obs.profile_parse "
             "output) to attach to the emitted record; default "
             "$MERCURY_BENCH_BREAKDOWN, else ./device_time_breakdown.json "
             "when present")
    return p.parse_args(argv)


def _attach_breakdown(record: dict, path: str) -> None:
    """Fold an ``obs.profile_parse`` breakdown into the bench record
    (scope fractions + overlap/idle summaries). With no ``path``,
    ``./device_time_breakdown.json`` is used when present; a file that is
    named, or found, must load."""
    if not path:
        candidate = os.path.join(os.getcwd(), "device_time_breakdown.json")
        path = candidate if os.path.exists(candidate) else ""
    if not path:
        return
    with open(path) as f:
        bd = json.load(f)
    if not str(bd.get("schema", "")).startswith(
            "mercury_device_time_breakdown"):
        raise ValueError(
            f"{path}: unrecognized schema {bd.get('schema')!r}")
    record["device_time_breakdown"] = {
        "source": bd.get("source"),
        "total_device_time_us": bd.get("total_device_time_us"),
        "attributed_frac": bd.get("attributed_frac"),
        "scope_frac": {name: stats.get("frac")
                       for name, stats in bd.get("scopes", {}).items()},
        "h2d_overlap_frac": bd.get("h2d", {}).get("overlap_frac"),
        "idle_frac": bd.get("idle", {}).get("idle_frac"),
    }


def main():
    args = _parse_args()

    from mercury_tpu.platform import configure_compile_cache

    # Scan-chunk compiles are minutes-long: cache them across runs and
    # across the timing / cost-analysis double compile.
    configure_compile_cache()

    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(f"bench.py: needs an accelerator, jax found "
              f"{dev.platform}/{dev.device_kind}; a CPU run is not a "
              "measurement — no record written", file=sys.stderr)
        sys.exit(1)

    record = _run_bench(plan=args.plan,
                        plan_budget=args.plan_memory_budget_bytes)
    _attach_breakdown(record, args.profile_breakdown)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
