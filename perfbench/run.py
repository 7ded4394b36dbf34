"""The benchmark's command: one cell, one run, one result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches jax. It refuses to run off a TPU or
on fewer chips than the cell asks for (non-zero exit, no result line).

Set-up (``setup_s``: process start to the first instant of the window)
builds ``TrainConfig`` from the cell's two files, constructs ``Trainer``
(weights and the stand-in data come from ``--seed``; where the cell's file
names a ``weights_seed``, the weights from that and the data from
``--seed``), drives its first
steps through ``fit()`` under the replay's recorder (``replay.py``), and
warms up through ``fit()`` until a log gate has fired and one whole call
has run with zero compiles. The window calls
``trainer.fit(num_epochs=steps_per_call)`` again and again until
``--seconds`` have passed and ends when the call in flight returns; each
``fit()`` ends in ``evaluate()``, whose host floats are the fence.
``--trace 1`` runs ``trace_calls`` such calls under ``jax.profiler``
instead and reports the per-layer metrics. ``perfbench/check.py`` says what
decides ``correct``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as this file can see it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import check, reference, replay  # noqa: E402
from perfbench.cell import Cell, layer_metric, reducer  # noqa: E402
from perfbench.flops import (step_program_flops,  # noqa: E402
                             train_flops_per_example)
from perfbench.peaks import peak  # noqa: E402
from perfbench.trace_reduce import Capture, load_events  # noqa: E402

#: Profiler captures of ``--trace 1`` runs: inside the checkout, git-ignored.
TRACE_DIR = os.path.join(ROOT, "perfbench", "_trace")


def say(text: str) -> None:
    print(f"[perfbench] +{time.perf_counter() - _T0:.1f}s {text}",
          flush=True)


class Refused(Exception):
    """The run cannot stand for the cell (no chip, too few chips)."""


def find_devices(chips: int, rehearsal: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not rehearsal and device["platform"] != "tpu":
        raise Refused(f"needs a TPU, jax found {device}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chip(s), jax found "
                      f"{device}")
    return device


def build_trainer(fields: Dict[str, Any], data_seed: Optional[int] = None):
    """``Trainer(TrainConfig(**fields))`` — the system under test. A
    function of its own so that a test can break what it returns.

    The program draws its weights, its stream and its data from the one
    ``seed``, and on a routed model the weights decide how many pairs fall
    on the held experts: the seed then sets the rate's level (PERF.md
    section 6, PR 48). With ``data_seed`` the data alone is that seed's —
    the dataset ``TrainConfig(seed=data_seed)`` would have built, handed to
    ``Trainer`` as a user hands it one — under the weights of
    ``fields["seed"]``: one model, other documents."""
    from mercury_tpu import TrainConfig
    from mercury_tpu.train import Trainer, build_dataset

    config = TrainConfig(**fields)
    if data_seed is None:
        return Trainer(config)
    return Trainer(config, dataset=build_dataset(
        config, seed_offset=data_seed - config.seed))


def _host_copy(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda a: np.asarray(a), jax.device_get(tree))


def _peak_rss() -> str:
    """The process's peak resident size so far, and the peak of its cgroup
    where that can be read (the machine ends what passes the cgroup's
    limit): for the log, no metric."""
    import resource

    kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    text = f"{kib / 2 ** 20:.2f} GiB"
    for path in ("/sys/fs/cgroup/memory.peak",
                 "/sys/fs/cgroup/memory/memory.max_usage_in_bytes"):
        try:
            with open(path) as f:
                return f"{text} (cgroup {int(f.read()) / 2 ** 30:.2f} GiB)"
        except (OSError, ValueError):
            continue
    return text


def _memory_stats(mesh_devices) -> Dict[str, Any]:
    """``memory_stats()`` of the fullest device of the mesh."""
    return max((d.memory_stats() or {} for d in mesh_devices),
               key=_memory_peak)


def _memory_peak(stats: Dict[str, Any]) -> int:
    """Peak bytes on the fullest chip. The TPU runtime counts live arrays
    under ``peak_bytes_in_use`` and the scratch space of the running
    program (XLA's temporaries: activations kept for the backward pass, the
    pool's scoring forward) under ``peak_bytes_reserved`` (my chip run,
    PR 24: a program with 2 GiB of temporaries left ``peak_bytes_in_use``
    where it was and moved ``peak_bytes_reserved`` by 2 GiB); the device
    holds both at once."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: Optional[Dict[str, Any]] = None,
             control: bool = False) -> Dict[str, Any]:
    """The whole run; returns the result line's object (also printed as the
    last line). ``rehearsal`` (tests only) lifts the TPU requirement and
    shrinks the job: ``{"train_config": {...}, "steps_per_call": n, ...}``
    laid over the cell's files. ``control`` adds the readings of the
    lower-precision reference put in the program's place
    (``readings.py``)."""
    import jax
    import numpy as np

    from mercury_tpu.lint.tracecheck import CompileMonitor
    from mercury_tpu.platform import configure_compile_cache

    # libtpu would log to the fixed path /tmp/tpu_logs; a run writes only
    # inside its checkout and the caches it is given. Read at backend start.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_dir = configure_compile_cache()
    cell = Cell(workload, rehearsal)
    limits, arch = cell.config["check"], cell.config["reference"]
    train_block = check.train_block_rows(limits,
                                         reference.rows_independent(arch))
    device = find_devices(cell.chips, rehearsal is not None)
    fields = cell.train_config_fields(seed, trace)
    data_seed = cell.data_seed(seed)
    steps_per_call = cell.steps_per_call
    log_every = int(fields["log_every"])
    say(f"cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device} jax={jax.__version__} compile_cache={cache_dir}")
    say(f"train_config={json.dumps(fields, sort_keys=True)}"
        + ("" if data_seed is None else
           f"; the weights are seed {fields['seed']}'s, the data seed "
           f"{data_seed}'s"))

    # ------------------------------------------------------------- set-up
    losses: List[float] = []
    with CompileMonitor() as setup_monitor:
        trainer = (build_trainer(fields) if data_seed is None
                   else build_trainer(fields, data_seed=data_seed))
    say("trainer built")
    try:
        trainer.logger.add_observer(
            lambda rec: losses.append(float(rec["train/loss"])))
        chips = trainer.mesh.devices.size
        step0 = int(trainer.state.step)
        evals: List[Dict[str, float]] = []
        with setup_monitor:
            with replay.Recorder(trainer) as recorder:
                evals.append(trainer.fit(num_epochs=replay.STEPS + 1))
            say(f"first {replay.STEPS + 1} steps recorded for the replay")
            # on to the first log gate, which lowers the step once more
            evals.append(trainer.fit(
                num_epochs=log_every - replay.STEPS - 1))
        say(f"first log gate passed ({log_every} steps)")
        with CompileMonitor() as second:
            evals.append(trainer.fit(num_epochs=steps_per_call))
        if second.snapshot()[1]:
            raise RuntimeError(
                f"warm-up: the call after the first log gate compiled "
                f"{second.snapshot()[1]} program(s); the window would too")
        warm_steps = int(trainer.state.step) - step0
        say(f"set-up: {warm_steps} warm-up steps, compile "
            f"{setup_monitor.compile_secs:.2f} s in {setup_monitor.compiles} "
            f"program(s), cache hits {setup_monitor.cache_hits} misses "
            f"{setup_monitor.cache_misses}; loss at the first log gates "
            f"{[round(v, 5) for v in losses[:5]]}")

        # The inference check's system side: seeded held-out rows,
        # at the warm weights (the same for a seed whatever --seconds is).
        # The program's ``batch_stats`` is what a family's forward takes as
        # ``model_state``.
        ds = trainer.dataset
        x_test, y_test = np.asarray(ds.x_test), np.asarray(ds.y_test)
        train_split = (np.asarray(ds.x_train), np.asarray(ds.y_train),
                       np.asarray(ds.shard_indices))
        idx = check.sample_indices(seed, x_test.shape[0],
                                   check.sample_rows(limits))
        system_outputs = trainer.predict(x_test[idx])
        warm_weights = _host_copy((trainer.state.params,
                                   trainer.state.batch_stats))

        say("the inference check's system side taken; the window starts")

        # ---------------------------------------------------------- window
        n_before = len(losses)
        step_before = int(trainer.state.step)
        calls = 0
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # no per-call Python events:
            options.host_tracer_level = 1     # they slow the host loop
            trainer.tracer.instant("perfbench/trace_start")
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        with CompileMonitor() as window_monitor:
            setup_s = time.perf_counter() - _T0
            t0 = time.perf_counter()
            try:
                # The window's calls, each ending in evaluate(), whose
                # floats are the fence: until --seconds have passed, or, in
                # a traced run, the cell's trace_calls of them.
                while True:
                    evals.append(trainer.fit(num_epochs=steps_per_call))
                    calls += 1
                    if (calls >= cell.trace_calls if trace
                            else time.perf_counter() - t0 >= seconds):
                        break
            finally:
                window_s = time.perf_counter() - t0
                if trace:
                    jax.profiler.stop_trace()
        compiles = window_monitor.snapshot()[1]
        steps = calls * steps_per_call
        advanced = int(trainer.state.step) - step_before
        window_losses = losses[n_before:]
        spans = _spans_since(trainer.tracer, "perfbench/trace_start")
        final_weights = _host_copy((trainer.state.params,
                                    trainer.state.batch_stats))
        # XLA names a compiled module after the jitted function.
        programs = {
            "step": "jit_" + getattr(trainer.train_step, "__name__", ""),
            "evaluate": "jit_" + getattr(trainer.eval_epoch, "__name__", "")}
        memory = _memory_stats(trainer.mesh.devices.flat)
        say(f"memory_stats of the fullest device after the window: {memory}")
    finally:
        trainer.close()
    del trainer
    gc.collect()    # the program's state leaves the chip and the host now

    # ------------------------------------------------- the reference check
    # Whatever is read of a parameter-sized tree is read a leaf at a time
    # and kept as its leaves' numbers, and the tree let go before the next
    # is made (README.md, "The check's host footprint"): seven such trees
    # now, five once the program's side of the replay is read, three when
    # the reference starts to follow the steps.
    t_ref = time.perf_counter()
    say(f"the window closed at a peak resident size of {_peak_rss()}")
    system = replay.system_steps(recorder.steps, arch)
    window_update_rms = check.update_rms(warm_weights[0], final_weights[0],
                                         steps)
    block = check.block_rows(limits, x_test.shape[0])
    # (outputs of the sample at the warm weights, loss over the whole test
    # split at the final weights) by the plain reference, and by the control
    ref_sides = {
        quantize: (reference.outputs(*warm_weights, x_test[idx], arch,
                                     quantize,
                                     block_rows=check.block_rows(limits)),
                   reference.eval_loss(*final_weights, x_test, y_test, arch,
                                       quantize, block_rows=block))
        for quantize in ((None, "fp8") if control else (None,))}
    del warm_weights, final_weights
    ref_outputs, ref_eval_loss = ref_sides[None]
    say(f"reference: inference and evaluate sides took "
        f"{time.perf_counter() - t_ref:.2f} s")
    replayed = replay.compare(system, recorder.steps, train_split, arch,
                              fields, "fp8" if control else None, train_block)
    if control:
        replayed, replayed_lower = replayed
    numbers = check.numbers(
        limits, system_outputs=system_outputs,
        ref_outputs=ref_outputs, eval_loss=evals[-1].get("test/eval_loss"),
        ref_eval_loss=ref_eval_loss, replay=replayed,
        window_update_rms=window_update_rms,
        window_losses=window_losses, steps_counted=steps,
        steps_advanced=advanced, compiles=compiles)
    for n in numbers:
        print(n.line(), flush=True)
    say(f"reference check took {time.perf_counter() - t_ref:.2f} s (not in "
        f"setup_s) and ended at a peak resident size of {_peak_rss()}; "
        f"system test/eval_loss {evals[-1].get('test/eval_loss')} "
        f"reference {ref_eval_loss}")
    extra: Dict[str, Any] = {}
    if control:
        quant_outputs, quant_eval_loss = ref_sides["fp8"]
        extra["numbers"] = {n.name: n.value for n in numbers}
        extra["control"] = dict(
            replayed_lower,
            logit_gap=check.logit_gap(quant_outputs, ref_outputs),
            eval_loss_gap=check.eval_loss_gap(quant_eval_loss,
                                              ref_eval_loss))
        say(f"control (fp8 reference): {extra['control']}")

    # ------------------------------------------------------------ metrics
    say(f"setup_s {setup_s:.3f}; all losses logged "
        f"{[round(v, 4) for v in losses]}")
    say("evaluate() at the end of each call, warm-up's three first: "
        + "; ".join(" ".join(f"{k}={v:.4f}" for k, v in e.items())
                    for e in evals))
    say(f"window: {calls} call(s), {steps} steps in {window_s:.4f} s; "
        f"losses logged {len(window_losses)}, last {window_losses[-3:]}")
    per_chip = (fields["batch_size"] * fields["world_size"] * steps
                / window_s / chips)
    peak_flops = _peak_or_none(device["kind"])
    memory_peak = _memory_peak(memory)
    values: Dict[str, float] = {}
    if trace:
        t_load = time.perf_counter()
        events, source = load_capture(TRACE_DIR)
        capture = Capture(events, programs["step"])
        say(f"capture read in {time.perf_counter() - t_load:.1f} s; device "
            f"lanes {capture.census}")
        ctx = dict(capture=capture, spans=spans, steps=steps,
                   call_wall_s=window_s, programs=programs,
                   counters={"compiles_in_window": compiles},
                   step_flops=step_program_flops(
                       cell.config["fwd_flops_per_example"], fields),
                   peak_flops=peak_flops, chips=chips)
        say(f"capture {source}: {len(events)} events, {len(capture.planes)} "
            f"device plane(s), {capture.step_count()} runs of "
            f"{programs['step']}")
        note = lost_steps_note(capture, steps)
        if note:
            say(note)
        for m in cell.per_layer():
            spec = layer_metric(m["name"])
            value = reducer(spec["reducer"])(ctx, **spec.get("args", {}))
            if value is not None:
                values[m["name"]] = float(value)
        device.update(busy_s=(capture.whole_busy_us() or 0.0) / 1e6,
                      window_s=window_s)
        extra["breakdown"] = {"device_ops": capture.top_ops(10),
                              "idle_gaps": capture.gap_summary(10)}
    else:
        values = {
            "train_examples_per_s": per_chip,
            # not measured off the chip (a rehearsal): no peak, 0
            "mfu": (100.0 * per_chip * train_flops_per_example(
                cell.config["fwd_flops_per_example"]) / peak_flops
                if peak_flops else 0.0),
            "peak_hbm_mib": memory_peak / 2 ** 20,
            "setup_s": setup_s,
        }
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end() + cell.per_layer()}
    device["memory_peak_bytes"] = memory_peak
    result = {
        "correct": all(n.ok for n in numbers), "attempted": int(steps),
        "failed": check.failed_steps(window_losses, log_every),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if k in units},
        "device": device, **extra,
        # every number compared beside its limit, last in the line
        "checks": {n.name: n.entry() for n in numbers},
    }
    # ... and as the last lines on standard error
    for n in numbers:
        print(n.line(), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


def lost_steps_note(capture, steps: int) -> Optional[str]:
    """What to say where the capture holds fewer executions of the step
    program than the traced calls took steps (a just-compiled step's first
    traced run: PERF.md, PR 26)."""
    held = capture.steps_held(steps)
    if held == steps:
        return None
    return (f"the capture lost {steps - held} of {steps} step programs: the "
            f"per-step device readings divide by the {held} it holds")


def load_capture(root: str):
    """The capture's events. The raw ``*.xplane.pb`` where the profiler
    wrote one (every event, with its stats); else the Chrome trace."""
    planes = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                              recursive=True), key=os.path.getmtime)
    return load_events(planes[-1] if planes else root)


def _peak_or_none(kind: str) -> Optional[float]:
    """The chip's peak; None on the CPU of a rehearsal, where a
    utilization is not measured."""
    return None if kind.lower().startswith("cpu") else peak(kind)


def _spans_since(tracer, marker: str) -> List[Dict[str, Any]]:
    """The program's host spans recorded after ``marker`` (none where the
    run is untraced and the tracer is the no-op one)."""
    snapshot = getattr(tracer, "snapshot", None)
    events = snapshot() if snapshot else []
    at = max((i for i, e in enumerate(events) if e["name"] == marker),
             default=None)
    return [] if at is None else events[at + 1:]


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as exc:
        print(f"perfbench: {exc}; refusing to run", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
