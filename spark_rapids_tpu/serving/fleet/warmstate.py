"""Shared fleet warm state: one directory, three artifacts.

A fleet shares warmth through ``spark.rapids.tpu.fleet.dir``:

  ``<dir>/compilecache/``      the shared compile manifest
                               (obs/compilecache.py). The EXECUTABLES
                               are not here: they live in jax's
                               persistent cache, whose directory every
                               worker inherits from the router's
                               environment (JAX_COMPILATION_CACHE_DIR,
                               else the package's fixed default);
  ``<dir>/warm.jsonl``         the warm-state manifest: one flock-
                               serialized REPLAYABLE record per real
                               compile anywhere in the fleet (kernel,
                               kernelKey, avals, argspec, op, seconds —
                               appended by ``SharedCompileCache.
                               _note_warm``), directly consumable as
                               ``compile.aot.manifest``;
  ``<dir>/events-<rid>.jsonl`` per-replica event journals, foldable
                               into one report by tools/qualification.py
                               and tools/history_server.py;
  ``<dir>/worker-<rid>.json``  the spec file a worker process boots from.

The division of labor: any replica's FIRST compile of a shape lands the
executable in jax's persistent cache and a replayable record in
``warm.jsonl``; every OTHER replica's first touch of that shape is a
persistent-cache steal (no compile), and a REPLACEMENT replica replays
the whole manifest via ``serving/prewarm.py`` BEFORE taking traffic —
the rolling-restart zero-warm-up path.

Stdlib-only helpers; the router and tests import this without touching
the session.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional


def fleet_paths(fleet_dir: str) -> Dict[str, str]:
    return {
        "dir": fleet_dir,
        "compileCache": os.path.join(fleet_dir, "compilecache"),
        "warmManifest": os.path.join(fleet_dir, "warm.jsonl"),
    }


def event_log_path(fleet_dir: str, replica: str) -> str:
    return os.path.join(fleet_dir, f"events-{replica}.jsonl")


def worker_conf(base_conf: Optional[Dict[str, Any]], fleet_dir: str,
                replica: str, prewarm: bool = False,
                event_log: bool = True) -> Dict[str, Any]:
    """The conf dict one worker session boots from: the caller's base
    settings plus the shared-warmth wiring. ``prewarm=True`` (a rolling
    restart's replacement) additionally points ``compile.aot.manifest``
    at the shared warm manifest so the worker AOT-replays the fleet's
    whole compile history before taking traffic."""
    paths = fleet_paths(fleet_dir)
    conf: Dict[str, Any] = dict(base_conf or {})
    conf.setdefault("spark.rapids.tpu.compile.sharedCache.dir",
                    paths["compileCache"])
    conf.setdefault("spark.rapids.tpu.fleet.warmManifest",
                    paths["warmManifest"])
    if prewarm:
        conf.setdefault("spark.rapids.tpu.compile.aot.manifest",
                        paths["warmManifest"])
    if event_log:
        conf.setdefault("spark.rapids.tpu.eventLog.path",
                        event_log_path(fleet_dir, replica))
    return conf


def write_worker_spec(fleet_dir: str, replica: str,
                      conf: Dict[str, Any],
                      **extras: Any) -> str:
    """Write ``<dir>/worker-<rid>.json``, the argv[1] of
    ``python -m spark_rapids_tpu.serving.fleet.worker``. Extras land
    top-level in the spec (e.g. ``schedulerWorkers=2``)."""
    os.makedirs(fleet_dir, exist_ok=True)
    spec = {"replica": replica, "conf": conf}
    spec.update(extras)
    path = os.path.join(fleet_dir, f"worker-{replica}.json")
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(spec, f, indent=1, default=str)
    os.replace(tmp, path)  # atomic: a booting worker never reads a torn spec
    return path
