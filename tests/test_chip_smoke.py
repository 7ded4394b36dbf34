"""``chip_smoke.py`` off the chip: its body runs tiny on the CPU mesh, and
neither it nor ``bench.py`` will stand a CPU run in for a chip run."""

import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_body_runs_tiny_on_the_cpu_mesh():
    """The same phases the chip runs — IS / uniform / scan trainers,
    kernel parity (interpret mode here), the four-device placement phase
    — at ``smallcnn`` size, and the token path at the decoder's CPU size.
    Any failed check raises."""
    out = chip_smoke.run(tiny=True)
    assert set(out) == {"one_chip_is", "one_chip_uniform", "one_chip_scan",
                        "one_chip_tokens", "one_chip_latent_tokens", "kernels",
                        "four_chip_is"}
    for name, facts in out.items():
        if name != "kernels":
            assert facts["compiles_after_first"] == 0, name
    assert out["one_chip_is"]["restored_step"] == out["one_chip_is"]["steps"]
    for name in ("one_chip_tokens", "one_chip_latent_tokens"):
        head = out[name]["head"]    # the kernel, interpreted, beside the plain
        assert head["shape"] == "32x64x96" and head["hits_differ"] == 0
        assert head["loss_kernel"] == pytest.approx(head["loss_plain"],
                                                    rel=1e-5)
    assert out["one_chip_is"]["mosaic_in_step"] is False  # off the chip


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="loss"):
        chip_smoke._require(False, "loss did not fall")


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_refuses_to_run_off_the_chip(script):
    """No accelerator: non-zero exit, nothing trained, no result line —
    and no child process doing the work on the CPU instead."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "cpu" in r.stderr
