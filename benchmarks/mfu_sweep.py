"""MFU vs batch size on one chip: where the reference's pinned shape sits
on the utilization curve.

The headline bench (`bench.py`) reports ~1.85% MFU — an honest number for
ResNet-18 at the reference's batch 32 on CIFAR shapes (3.3 GFLOP of work
per step against a 197 TFLOP/s v5e peak leaves the chip latency- and
bandwidth-bound). This sweep measures the same fused uniform-SGD step at
growing per-step batch so the record shows the framework rides the
utilization curve up when the work grows, i.e. the low headline MFU is a
property of the pinned workload shape, not of the step program.

Usage (on the real chip)::

    python benchmarks/mfu_sweep.py [--batches 32,128,512,1024]

Appends one JSON record to ``benchmarks/results_mfu_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import _bootstrap  # noqa: F401

import numpy as np  # noqa: E402

# One source of truth for per-device peaks: the live MFU accounting and
# this offline sweep must never disagree on the denominator.
from mercury_tpu.obs.accounting import peak_flops  # noqa: E402


def measure(batch: int, args) -> dict:
    import jax

    from mercury_tpu.config import TrainConfig
    from mercury_tpu.parallel.mesh import make_mesh
    from mercury_tpu.train.trainer import Trainer

    config = TrainConfig(
        model=args.model,
        dataset=args.dataset,
        augmentation="noniid" if args.dataset == "synthetic" else "none",
        world_size=1,
        batch_size=batch,
        use_importance_sampling=False,
        steps_per_epoch=10_000,
        num_epochs=1,
        eval_every=0,
        log_every=0,
        scan_steps=args.scan,
        seed=0,
    )
    trainer = Trainer(config, mesh=make_mesh(1, config.mesh_axis))
    ds = trainer.dataset
    step_fn = trainer.train_step_many or trainer.train_step
    state = trainer.state
    for _ in range(3):
        state, m = step_fn(state, ds.x_train, ds.y_train, ds.shard_indices)
        np.asarray(m["train/loss"])
    t0 = time.perf_counter()
    for _ in range(args.calls):
        state, m = step_fn(state, ds.x_train, ds.y_train, ds.shard_indices)
    np.asarray(m["train/loss"])
    dt = time.perf_counter() - t0
    ips = batch * args.calls * args.scan / dt
    cost = step_fn.lower(
        state, ds.x_train, ds.y_train, ds.shard_indices
    ).compile().cost_analysis()
    flops_per_img = float(cost.get("flops", 0.0)) / (batch * args.scan)
    peak = peak_flops(jax.devices()[0].device_kind)  # None on the CPU
    mfu = (flops_per_img * ips / peak) if (peak and flops_per_img) else None
    return {
        "batch": batch,
        "images_per_sec": round(ips, 1),
        "gflops_per_image": round(flops_per_img / 1e9, 3),
        "mfu": round(mfu, 4) if mfu else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--dataset", default="synthetic",
                    help="synthetic (CIFAR-shaped) or synthetic_seq for "
                         "the transformer family")
    ap.add_argument("--batches", default="32,128,512,1024")
    ap.add_argument("--scan", type=int, default=25)
    ap.add_argument("--calls", type=int, default=6)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "results_mfu_sweep.jsonl"))
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    rows = []
    for b in (int(x) for x in args.batches.split(",")):
        try:
            row = measure(b, args)
        except Exception as e:
            print(f"# batch {b} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            row = {"batch": b, "failed": True}
        rows.append(row)
        print(f"# {row}", file=sys.stderr)
    record = {
        "schema": "mfu_sweep_v1",
        "model": args.model,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "rows": rows,
    }
    with open(args.out, "a") as f:
        f.write(json.dumps(record) + "\n")
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
