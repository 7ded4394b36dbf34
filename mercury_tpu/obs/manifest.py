"""Run manifest: the one JSON file that makes a metrics stream
interpretable a month later.

Written once at trainer start, next to ``metrics.jsonl``: the resolved
config (every knob, post-defaulting), the software versions the numbers
were produced under, the mesh/device topology they were produced on, and
the git revision of the code — the fields every "which run was that?"
question needs and the reference never recorded (its config was
module-level globals edited in source, ``pytorch_collab.py:21-33``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import time
from typing import Dict, Optional


def git_revision(cwd: Optional[str] = None) -> Optional[str]:
    """Current git sha (with ``-dirty`` suffix when the tree has local
    modifications), or None when git/repo is unavailable."""
    try:
        root = cwd or os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=5,
        )
        if sha.returncode != 0:
            return None
        rev = sha.stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root, capture_output=True,
            text=True, timeout=5,
        )
        if dirty.returncode == 0 and dirty.stdout.strip():
            rev += "-dirty"
        return rev
    except Exception:
        return None


def build_run_manifest(config, mesh=None,
                       extra: Optional[Dict] = None) -> Dict:
    """Assemble the manifest dict (pure; no filesystem)."""
    import jax
    import jaxlib

    manifest: Dict = {
        "schema": "mercury_run_manifest_v1",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "run_name": config.run_name(),
        "config": dataclasses.asdict(config),
        "jax_version": jax.__version__,
        "jaxlib_version": getattr(jaxlib, "__version__", None),
        "git_sha": git_revision(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }
    dev = jax.devices()[0]
    manifest["device_kind"] = dev.device_kind
    manifest["platform"] = dev.platform
    manifest["device_count"] = jax.device_count()
    if mesh is not None:
        manifest["mesh_shape"] = {str(a): int(s)
                                  for a, s in dict(mesh.shape).items()}
        manifest["mesh_axis_names"] = [str(a) for a in mesh.axis_names]
    from mercury_tpu.obs.accounting import peak_flops

    manifest["peak_flops"] = peak_flops(dev.device_kind)
    if extra:
        manifest.update(extra)
    return manifest


def write_run_manifest(log_dir: str, config, mesh=None,
                       extra: Optional[Dict] = None) -> str:
    """Write ``run_manifest.json`` into ``log_dir`` (rank 0 only in
    multi-controller runs — every process computes the same content, one
    writes). Returns the path."""
    import jax

    manifest = build_run_manifest(config, mesh, extra)
    path = os.path.join(log_dir, "run_manifest.json")
    if jax.process_index() == 0:
        os.makedirs(log_dir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(manifest, f, indent=2, default=str)
            f.write("\n")
    return path
