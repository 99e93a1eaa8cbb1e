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

Two optional keys lay out a deployment across chips and break it in the
window:

* ``"deployment": {"shards": S, "replicas": R}`` in a configuration file:
  the index is list-hash-split into S shards, one to each of the cell's
  chips (S must equal the cell's ``chips``), each list held by R of them
  (``1 <= R <= S``).  A ``deployment`` given as a string only describes
  the deployment in words; the index is then served unsharded.
* ``"faults": [{"at_s": t, "shard": s}, ...]`` in a traffic file: shard s
  is killed at the first wave after t seconds from the window's start and
  restored from an arena checkpoint.  Needs a configuration with a
  ``deployment`` block of ``R >= 2``, so a killed shard's lists keep a
  live replica.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
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
    layout: dict | None = None   # {"shards": S, "replicas": R}, or unsharded
    faults: list = field(default_factory=list)  # shard kills, by ``at_s``


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
    chips = int(w.get("chips", 1))
    layout = _layout(workload, config, chips)
    return Cell(workload, config, traffic, chips, e2e, per_layer, layout,
                _faults(workload, traffic, layout))


def _whole(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _layout(workload: str, config: dict, chips: int) -> dict | None:
    """The configuration's ``deployment`` block, checked against the cell."""
    dep = config.get("deployment")
    if not isinstance(dep, dict):
        return None
    S, R = dep.get("shards"), dep.get("replicas")
    if not (_whole(S) and _whole(R)):
        raise SpecError(f"workload {workload!r}: a deployment block needs whole "
                        f"numbers 'shards' and 'replicas', got {S!r} and {R!r}")
    if S != chips:
        raise SpecError(f"workload {workload!r}: the configuration lays the "
                        f"index out in {S} shards, one to a chip, but the cell "
                        f"asks for {chips} chips")
    if not 1 <= R <= S:
        raise SpecError(f"workload {workload!r}: replicas must be 1 to {S} "
                        f"(the shards), got {R}")
    return {"shards": S, "replicas": R}


def _faults(workload: str, traffic: dict, layout: dict | None) -> list:
    """The traffic's shard kills, checked against the layout, by ``at_s``."""
    faults = traffic.get("faults")
    if faults is None:
        return []
    if layout is None or layout["replicas"] < 2:
        raise SpecError(f"workload {workload!r}: the traffic kills shards, so "
                        "the configuration needs a deployment block with "
                        "replicas >= 2 (a killed shard's lists need a live "
                        "replica)")
    if not isinstance(faults, list) or not faults:
        raise SpecError(f"workload {workload!r}: 'faults' is a non-empty list")
    for f in faults:
        g = f if isinstance(f, dict) else {}
        at, shard = g.get("at_s"), g.get("shard")
        if not (isinstance(at, (int, float)) and not isinstance(at, bool)
                and at >= 0 and _whole(shard)
                and 0 <= shard < layout["shards"]):
            raise SpecError(f"workload {workload!r}: a fault is "
                            '{"at_s": seconds >= 0, "shard": 0 to '
                            f'{layout["shards"] - 1}}}, got {f!r}')
    return sorted(({"at_s": float(f["at_s"]), "shard": int(f["shard"])}
                   for f in faults), key=lambda f: f["at_s"])


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
