"""Multi-process distributed backend test.

Everything else in the suite simulates multi-device on ONE process; this
test actually launches two OS processes that form a JAX distributed CPU
cluster (2 processes × 4 virtual devices = one 8-device mesh) and run
cross-process collectives — the closest a single host gets to the
reference's 4-process gloo world (``pytorch_collab.py:269-292``) and the
proof that ``parallel/distributed.py`` composes into a working multi-host
program, not just a wrapper.
"""

import os
import socket
import subprocess
import sys

import pytest


WORKER = os.path.join(os.path.dirname(__file__), "_dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_cluster_collectives(tmp_path):
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["MERCURY_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir)]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(port), str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    # The worker prints an explicit "SKIP:" marker (and exits 0) when the
    # installed jaxlib's CPU backend forms the cluster but cannot EXECUTE
    # cross-process collectives — an environment limitation, not a defect
    # in parallel/distributed.py. Only that narrowly-matched marker skips;
    # every other nonzero exit or wrong result still fails loudly.
    skip_lines = [l for out in outs for l in out.splitlines()
                  if l.startswith("SKIP:")]
    if skip_lines and all(p.returncode == 0 for p in procs):
        pytest.skip(skip_lines[0])
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert "OK 12.0 3.5" in out, f"worker {pid} wrong result:\n{out}"
    # Both processes ran the same global program — the training losses
    # (replicated global scalars, printed as float hex) must match
    # bit-for-bit, including the post-checkpoint-restore step and the
    # host_stream trajectories. (The worker-row slices legitimately
    # differ per host: [0-3] vs [4-7].)
    losses, hs_hex = [], []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("OK")][0]
        losses.append(line.split("loss=")[1])
        hs_hex.append(line.split(" hs=")[1].split()[0])
        assert ("[0, 1, 2, 3]" in line) or ("[4, 5, 6, 7]" in line), line
    assert losses[0] == losses[1], f"losses diverge: {losses}"

    # Solo arm: re-run the host_stream pool config in ONE process (8 local
    # devices) — the per-host prefetch split must be a pure dataflow
    # change, so the 2-process streamed trajectory matches the 1-process
    # one bit-for-bit. The solo run then restores the cluster's mid-epoch
    # host_stream checkpoints elastically (W=8 → W=4, 2 processes → 1),
    # asserting the stream cursor and score table survive the world change.
    solo = subprocess.run(
        [sys.executable, WORKER, "--solo", env["MERCURY_TEST_CKPT_DIR"]],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, timeout=540,
    )
    assert solo.returncode == 0, f"solo arm failed:\n{solo.stdout}"
    assert "SOLO elastic_ok" in solo.stdout, solo.stdout
    solo_hs = solo.stdout.split("SOLO hs=")[1].split()[0]
    # The two hosts of the cluster agree to the bit (one program). Against
    # the solo run the loss may differ by ONE float32 ulp: it is a pmean
    # over 8 workers, which one process of 8 devices adds up in another
    # order than two processes of 4 (the cross-process all-reduce adds the
    # hosts' partial sums), and float addition does not associate. Seen on
    # jax 0.9.0's CPU collectives: 0x1.3234cep+1 against 0x1.3234d0p+1.
    assert hs_hex[0] == hs_hex[1], hs_hex
    import numpy as np

    got = np.float32(float.fromhex(hs_hex[0]))
    want = np.float32(float.fromhex(solo_hs))
    assert abs(got - want) <= np.spacing(max(abs(got), abs(want))), (
        hs_hex, solo_hs)
