#!/usr/bin/env bash
# One-shot on-chip capture queue: run everything that needs the real TPU,
# one process after another (a chip belongs to one process at a time; this
# shell never touches jax). The steps are independent, so a failed step
# does not stop the ones after it — but it fails the queue: the script
# exits non-zero if any step did. Run from the repo root:
#
#   bash benchmarks/capture_on_chip.sh
#
set -u
cd "$(dirname "$0")/.."

failed=0
run() {
  echo "== $*" >&2
  timeout "${STEP_TIMEOUT:-2400}" "$@" || {
    echo "== FAILED (rc=$?): $*" >&2
    failed=$((failed + 1))
  }
}

# 1. Headline bench (with the cadence-K8 diagnostic fields).
run python bench.py

# 2. MFU vs batch sweep (where the pinned batch-32 shape sits on the
#    utilization curve), plus two chip-filling configs the round-3 verdict
#    asked for: large-batch ResNet-50 and a bf16 transformer (what the
#    chip CAN reach when the workload has the FLOPs).
run python benchmarks/mfu_sweep.py
run python benchmarks/mfu_sweep.py --model resnet50 --batches 128,256,512
run python benchmarks/mfu_sweep.py --model transformer \
    --dataset synthetic_seq --batches 64,256,1024

# 4. PP bubble on the chip (the CPU record says: re-measure here before
#    ruling a 1F1B schedule in or out).
run python benchmarks/pp_bubble.py

# 5. BASELINE rows 1-3 on the real bundled digits data (time-to-target
#    with honest provenance; CIFAR bytes are absent from this image).
for p in 1 2 3; do
  run python benchmarks/run.py --preset "$p" --dataset digits \
      --steps 1500 --eval-every 100 --target-acc 0.80
done

# 6. The round-4 flagship-WIN regime on chip: (a) the transformer IS cost
#    ladder (per-step price of IS on this model family — the conversion
#    factor for the CPU-measured steps-to-target win on
#    synthetic_seq_hard), and (b) the time-to-target experiment itself at
#    chip speed, 3 seeds.
run python benchmarks/is_cost_ladder.py --model transformer \
    --dataset synthetic_seq_hard --batch-size 16
run python benchmarks/sample_efficiency.py --model transformer \
    --dataset synthetic_seq_hard --arms is_loss,is_k8,uniform --seeds 3 \
    --steps 300 --eval-every 10 --batch-size 16 --target-acc 0.995 \
    --world-size 1 \
    --out benchmarks/results_sample_efficiency_seq_hard_tpu.jsonl

# 7. The round-5 FOUND-data win experiment at chip speed (real digit
#    scanlines, rare-class protocol — the mechanism probe measured
#    loss-score variance ratio 0.40 by step 1600 on this task, 3 seeds):
#    does the 2.5x variance reduction convert to wall-clock on chip?
run python benchmarks/sample_efficiency.py --model transformer \
    --dataset digits_seq_imb --world-size 1 --batch-size 16 \
    --presample-batches 10 --steps 2000 --eval-every 50 \
    --metric rare_acc --target-acc 0.75 --seeds 3 \
    --arms is_loss,uniform \
    --out benchmarks/results_sample_efficiency_digits_seq_tpu.jsonl

if [ "$failed" -ne 0 ]; then
  echo "== capture finished with $failed FAILED step(s)" >&2
  exit 1
fi
echo "== capture complete" >&2
