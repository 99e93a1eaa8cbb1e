"""The benchmark's files, found by name.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics.  Everything that belongs to one of them sits in a file of its own
under ``bench/``, found from the name alone:

* a configuration: the ``file`` its entry names (``bench/configs/<name>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None`` (None: nothing to read in this run).

So a later change adds a deployment, a mix or a metric by adding files and
entries, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    config: dict       # the configuration file's contents
    traffic: dict      # the traffic file's contents
    chips: int
    end_to_end: list   # metric entries this cell reports with --trace 0
    per_layer: list    # metric entries this cell reports with --trace 1


def load_benchmark(root: Path) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from None


def resolve(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and
    metrics."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have: {', '.join(sorted(cells))})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names no known config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench.get("end_to_end", [])
           if "workloads" not in m or workload in m["workloads"]]
    for m in bench.get("per_layer", []):
        if "workloads" not in m:
            raise SpecError(f"per-layer metric {m['name']!r} names no "
                            "workloads: list the cells that report it")
    per_layer = [m for m in bench.get("per_layer", [])
                 if workload in m["workloads"]]
    for m in per_layer:
        metric_path(root, m["name"])  # every reader exists
    return Cell(workload, config, traffic, int(w.get("chips", 1)), e2e,
                per_layer)


def metric_path(root: Path, name: str) -> Path:
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name!r} has no reader at {path}")
    return path


def load_reader(root: Path, name: str):
    """The ``read`` function of metric ``name``'s reader module."""
    path = metric_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod.read
