"""What the training side of the plain reference holds on the chip for a
next-token family, by the reference functions alone (no ``Trainer``): the
token fixture's model at language-model widths through
``reference.score_pool`` and ``reference.make_loss_and_grad``.

    python3 tests/perfbench/capacity_probe.py --train_block_rows 4
    python3 tests/perfbench/capacity_probe.py --train_block_rows 0   # whole

One process a reading (a process's peak never falls again). Prints one JSON
line last: the peak (``peak_bytes_in_use + peak_bytes_reserved``) after the
pool is scored and after the batch is differentiated, or the message the
call ended with; and how far the first block's losses lie from those of the
same rows scored alone. On the chip only; no test collects this file.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from perfbench import reference, run  # noqa: E402


def peak_bytes() -> int:
    """As the benchmark reads ``memory_peak_bytes``."""
    return run._memory_peak(jax.devices()[0].memory_stats() or {})


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--train_block_rows", type=int, required=True,
                        help="0: the whole pool and batch in one forward")
    parser.add_argument("--d_model", type=int, default=2048)
    parser.add_argument("--vocab", type=int, default=16160)
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--pool", type=int, default=80)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"capacity_probe: needs a TPU, jax found {device}",
              file=sys.stderr)
        return 1
    block = args.train_block_rows or None
    arch = {"file": "tests/perfbench/token_family.py",
            "d_model": args.d_model,
            "sampling": {"is_alpha": 0.5, "ema_alpha": 0.9}}
    fam = reference.family(arch)
    rng = np.random.default_rng(args.seed)
    params = {"embed": rng.normal(0, 0.02, (args.vocab, args.d_model)
                                  ).astype(np.float32)}
    tokens = rng.integers(0, args.vocab, (2 * args.pool, args.seq + 1)
                          ).astype(np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    out = dict(vars(args), device=device.device_kind,
               logits_bytes_whole_pool=4 * args.pool * args.seq * args.vocab)

    def attempt(name, call):
        t0 = time.perf_counter()
        try:
            result = call()
            jax.block_until_ready(result)
        except Exception as exc:  # noqa: BLE001  (the message is the reading)
            out[name] = {"failed": f"{type(exc).__name__}: {exc}"[:600]}
            return None
        out[name] = {"seconds": time.perf_counter() - t0,
                     "peak_bytes": peak_bytes()}
        return result

    scored = attempt("score_pool", lambda: reference.score_pool(
        params, jax.random.key(args.seed), rng.permutation(len(x)), 0, 0.0,
        0, x, y, np.arange(len(x)), arch, args.pool, block_rows=block))
    rows = slice(0, block or args.batch)
    if scored is None:      # the batch still needs rows and weights
        inputs, labels = x[:args.pool], y[:args.pool]
        scaled = np.ones(args.pool)
    else:
        inputs, labels, losses, scaled = scored
        alone = np.asarray(jax.jit(lambda p, i, t: fam.example_loss(
            fam.forward(p, None, i, arch), t))(params, inputs[rows],
                                               labels[rows]), np.float64)
        out["score_pool"].update(
            first_block_vs_alone=float(np.max(np.abs(losses[rows] - alone)
                                              / alone)),
            mean_loss=float(losses.mean()), uniform=float(np.log(args.vocab)))
    drawn = rng.choice(args.pool, args.batch, replace=False)
    got = attempt("loss_and_grad", lambda: reference.make_loss_and_grad(
        arch, block_rows=block)(params, inputs[drawn], labels[drawn],
                                scaled[drawn].astype(np.float32)))
    if got is not None:
        out["loss_and_grad"].update(
            loss=float(got[0]),
            grad_norm=float(np.linalg.norm(np.asarray(got[1]["embed"]))))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
