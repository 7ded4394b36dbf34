"""Command-line entry point.

The reference has no CLI: its config is module-level globals edited in
source (``pytorch_collab.py:21-33``) and launch is ``python
pytorch_collab.py`` forking ``world_size`` gloo processes (``:279-292``,
hardcoded master addr/port — including the invalid port 295001 noted in
SURVEY.md "known defects"). Here every :class:`TrainConfig` field is a flag,
launch is single-controller (``python -m mercury_tpu``), and multi-host
initialization is one flag (``--distributed``; see
``mercury_tpu.parallel.distributed``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Optional, Sequence

from mercury_tpu.config import TrainConfig


def _add_config_flags(parser: argparse.ArgumentParser) -> None:
    """Generate one flag per TrainConfig field (source of truth: the
    dataclass — no drift)."""
    for field in dataclasses.fields(TrainConfig):
        name = "--" + field.name.replace("_", "-")
        default = field.default
        ftype = field.type
        if ftype == "bool" or isinstance(default, bool):
            parser.add_argument(
                name, type=lambda s: s.lower() in ("1", "true", "yes"),
                default=default, metavar="BOOL",
                help=f"(default: {default})",
            )
        elif isinstance(default, int) and not isinstance(default, bool):
            parser.add_argument(name, type=int, default=default,
                                help=f"(default: {default})")
        elif isinstance(default, float):
            parser.add_argument(name, type=float, default=default,
                                help=f"(default: {default})")
        else:  # str / Optional[str] / Optional[int]
            parser.add_argument(name, type=str, default=default,
                                help=f"(default: {default})")


def parse_config(argv: Optional[Sequence[str]] = None) -> tuple[TrainConfig, argparse.Namespace]:
    parser = argparse.ArgumentParser(
        prog="mercury_tpu",
        description="TPU-native importance-sampled distributed training",
    )
    _add_config_flags(parser)
    parser.add_argument("--distributed", action="store_true",
                        help="initialize jax.distributed for multi-host pods")
    parser.add_argument("--dry-run", action="store_true",
                        help="build everything, run one step, print metrics, exit")
    parser.add_argument("--audit", action="store_true",
                        help="build everything, trace (don't run) the train "
                             "step, print its structural footprint — "
                             "collective counts, host callbacks, jaxpr "
                             "digest (see docs/LINT.md) — and exit")
    parser.add_argument("--print-config", action="store_true",
                        help="print the resolved config as JSON and exit")
    args = parser.parse_args(argv)

    kw = {}
    for f in dataclasses.fields(TrainConfig):
        name, ftype = f.name, str(f.type)
        value = getattr(args, name)
        # Optional[int] fields arrive as strings from argparse; coerce.
        if isinstance(value, str) and value.isdigit() and "int" in ftype:
            value = int(value)
        # "none"/"" mean None only for Optional fields — plain-str enums
        # legitimately use "none" as a value (e.g. grad_compression).
        if (isinstance(value, str) and value.lower() in ("none", "")
                and "Optional" in ftype):
            value = None
        # Tuples of ints (model_cut) arrive as "4,0,8".
        if isinstance(value, str) and "Tuple[int" in ftype:
            value = tuple(int(v) for v in value.split(","))
        # Optional[bool] fields (e.g. use_pallas) arrive as strings; a bare
        # string "false" would be truthy downstream.
        if (isinstance(value, str) and "bool" in ftype
                and value.lower() in ("true", "false", "yes", "no", "1", "0")):
            value = value.lower() in ("true", "yes", "1")
        kw[name] = value
    return TrainConfig(**kw), args


def main(argv: Optional[Sequence[str]] = None) -> int:
    config, args = parse_config(argv)
    if args.print_config:
        print(json.dumps(dataclasses.asdict(config), indent=2, default=str))
        return 0

    # A virtual-CPU-device request (the CI/dev recipe) means "run on the
    # host CPU" even on a machine that holds an accelerator — selecting
    # CPU is only possible before the first backend touch, so do it here,
    # first thing, together with the compile-cache placement.
    from mercury_tpu.platform import (
        configure_compile_cache,
        select_cpu_if_requested,
    )

    select_cpu_if_requested()
    configure_compile_cache()

    if args.distributed:
        from mercury_tpu.parallel.distributed import initialize

        initialize()

    from mercury_tpu.train.trainer import Trainer

    # Context manager: drains + closes the async metric writer on exit
    # (--log-every streams to log_dir, --heartbeat-every paces the stdout
    # one-liner — both flags generated from TrainConfig above).
    with Trainer(config) as trainer:
        print(f"run: {config.run_name()}  mesh: {trainer.mesh.shape}  "
              f"steps/epoch: {trainer.steps_per_epoch}")
        if args.audit:
            import jax

            from mercury_tpu.analysis import collective_footprint

            # host_stream's step takes a streamed pixel batch instead of
            # the resident array; a shape/dtype template traces identically
            # (make_jaxpr never touches values).
            step_x = trainer._step_x
            if config.data_placement == "host_stream":
                staging = trainer._stream_pipe._staging[0]
                step_x = jax.ShapeDtypeStruct(staging.shape, staging.dtype)
            fp = collective_footprint(
                trainer.train_step, trainer.state, step_x,
                trainer._step_y, trainer.dataset.shard_indices,
                telemetry=config.telemetry,
            )
            print(json.dumps(fp, indent=2))
            return 0
        if args.dry_run:
            if config.data_placement == "host_stream":
                # pop→step→push, including the lookahead index hand-off —
                # the same loop fit() drives.
                metrics = trainer._host_stream_step()
            else:
                state, metrics = trainer.train_step(
                    trainer.state, trainer._step_x, trainer._step_y,
                    trainer.dataset.shard_indices,
                )
                trainer.state = state
            print(json.dumps({k: float(v) for k, v in metrics.items()}))
            return 0
        final = trainer.fit()
        print(json.dumps(final))
    return 0


if __name__ == "__main__":
    sys.exit(main())
