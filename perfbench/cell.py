"""A cell's data: manifest entry + workload file + configuration file.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name the manifest
(``BENCHMARK.json``) gives it — so a later PR adds a cell, a configuration
or a metric by adding files and manifest entries, never by editing this.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: The job every cell runs, before its two files speak: the reference's
#: logging cadence, no periodic eval/checkpoint/heartbeat (``fit()`` closes
#: each call with one ``evaluate()``), one step per "epoch" so that
#: ``fit(num_epochs=n)`` takes exactly n steps, and a cosine schedule that
#: spans 200,000 steps. Every other field is ``TrainConfig``'s default —
#: what ``python -m mercury_tpu`` gives a user.
JOB_SHAPE: Dict[str, Any] = dict(
    log_every=100, eval_every=0, checkpoint_every=0, heartbeat_every=0,
    steps_per_epoch=1, num_epochs=200_000,
)


def _read(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return _read(ROOT, "BENCHMARK.json")


class Cell:
    """One entry of the manifest's ``workloads`` with its files read."""

    def __init__(self, name: str,
                 rehearsal: Optional[Dict[str, Any]] = None) -> None:
        self.manifest = manifest()
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"perfbench: no workload {name!r} in "
                             f"BENCHMARK.json (has: {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.workload = _read(HERE, "workloads", f"{name}.json")
        cfg_entry = next(c for c in self.manifest["configs"]
                         if c["name"] == self.entry["config"])
        self.config = _read(ROOT, cfg_entry["file"])
        for key in ("config", "chips"):
            if self.workload[key] != self.entry[key]:
                raise SystemExit(
                    f"perfbench: {name}.json says {key}="
                    f"{self.workload[key]!r}, BENCHMARK.json "
                    f"{self.entry[key]!r}")
        if rehearsal:
            # A test's tiny job, laid over the two files: the sizes go to
            # the workload, the plain reference's shape and the check's
            # limits to the configuration.
            rehearsal = dict(rehearsal)
            self.workload["train_config"] = dict(
                self.workload.get("train_config", {}),
                **rehearsal.pop("train_config", {}))
            for key in ("reference", "check"):
                if key in rehearsal:
                    self.config[key] = rehearsal.pop(key)
            self.workload.update(rehearsal)

    # ------------------------------------------------------------ the job
    def train_config_fields(self, seed: int, trace: bool) -> Dict[str, Any]:
        fields = dict(JOB_SHAPE)
        fields.update(self.config["train_config"])
        fields.update(self.workload.get("train_config", {}))
        # ``--seed`` may pass 2**31; the program's seed feeds numpy and a
        # jax key, which take 31 bits safely. A workload that names a
        # ``weights_seed`` trains one set of weights on every seed's data
        # (``data_seed``): the program draws weights, stream and data from
        # its one ``seed``.
        fields.update(seed=int(self.workload.get("weights_seed", seed))
                      % (2 ** 31 - 1), trace=bool(trace))
        return fields

    def data_seed(self, seed: int) -> Optional[int]:
        """The seed of the run's data where the workload's file fixes the
        weights' (``weights_seed``): ``--seed`` as the program would have
        taken it. None where ``--seed`` is the program's one seed."""
        if "weights_seed" not in self.workload:
            return None
        return int(seed) % (2 ** 31 - 1)

    @property
    def steps_per_call(self) -> int:
        return int(self.workload["steps_per_call"])

    @property
    def trace_calls(self) -> int:
        return int(self.workload["trace_calls"])

    # ------------------------------------------------------------ metrics
    def _reported(self, group: str) -> List[Dict[str, Any]]:
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def end_to_end(self) -> List[Dict[str, Any]]:
        return self._reported("end_to_end")

    def per_layer(self) -> List[Dict[str, Any]]:
        return self._reported("per_layer")


def layer_metric(name: str) -> Dict[str, Any]:
    return _read(HERE, "layer_metrics", f"{name}.json")


def reducer(name: str) -> Callable[..., Any]:
    """``perfbench/reducers/<name>.py``'s ``reduce(ctx, **args)``."""
    return importlib.import_module(f"perfbench.reducers.{name}").reduce
