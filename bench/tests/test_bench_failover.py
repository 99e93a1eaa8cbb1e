"""A sharded layout and a mid-window shard kill, at a tiny size on the CPU.

A scratch configuration lays a tiny Gov2-shaped index out as 4 shards x 2
replicas (``codecs: "svb"``, so the shards run under one ``shard_map``),
and scratch traffic mixes kill shard 1 during the window.  The runs go
through every step a chip run takes but the look for a chip, in a
subprocess with four forced host devices and the jitted device path
(``REPRO_BACKEND=ref``), so that the mesh path and its fault boundary run.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402

TOPK = "tiny-top10-failover"
AND = "tiny-and-failover"
LATE = "tiny-top10-late-kill"
BASE_CHECKS = {"wrong_answers", "unanswered"}
FAULT_CHECKS = BASE_CHECKS | {"failover_wrong_answers", "degraded_answers",
                              "unrecovered_shards"}


def _tiny_config(deployment) -> dict:
    cfg = json.loads((ROOT / "bench/configs/gov2-web.json").read_text())
    cfg["corpus"].update(postings=40_000, n_lists=48, n_docs=41_000)
    cfg["index"]["codecs"] = "svb"
    cfg["deployment"] = deployment
    return cfg


def _tiny_mix(name: str, faults) -> dict:
    tr = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
    tr["popularity"]["skip_top"] = 2
    tr.update(batch=8, check_sample=12, rate_qps=40.0, log_queries=96)
    tr["warmup"].update(segment_s=0.5, min_segments=1, max_segments=2,
                        max_rounds=2)
    if faults is not None:
        tr["faults"] = faults
    return tr


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped root with the scratch layouts, mixes and cells."""
    root = tmp_path_factory.mktemp("failover")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {"tiny-4x2": {"shards": 4, "replicas": 2},
               "tiny-4x1": {"shards": 4, "replicas": 1},
               "tiny-words": "a deployment described in words only"}
    for name, dep in configs.items():
        (root / f"bench/configs/{name}.json").write_text(
            json.dumps(_tiny_config(dep)))
        bench["configs"].append({"name": name, "source": "https://example.org",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    kill = [{"at_s": 0.3, "shard": 1}]
    mixes = {"top10-failover": ("top10-overload", kill),
             "and-failover": ("and-replay", kill),
             "top10-late-kill": ("top10-overload", [{"at_s": 5.0, "shard": 1}]),
             "top10-plain": ("top10-overload", None)}
    for name, (base, faults) in mixes.items():
        (root / f"bench/traffic/{name}.json").write_text(
            json.dumps(_tiny_mix(base, faults)))
    cells = [(TOPK, "tiny-4x2", "top10-failover", 4),
             (AND, "tiny-4x2", "and-failover", 4),
             (LATE, "tiny-4x2", "top10-late-kill", 4),
             ("tiny-top10-sharded", "tiny-4x2", "top10-plain", 4),
             ("tiny-one-replica", "tiny-4x1", "top10-failover", 4),
             ("tiny-one-chip", "tiny-4x2", "top10-plain", 1),
             ("tiny-words", "tiny-words", "top10-plain", 1),
             ("tiny-words-kill", "tiny-words", "top10-failover", 1)]
    for name, config, traffic, chips in cells:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


RUNS = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, "bench")
    from repro.ranked import topk_engine

    # the chip's residency, so that the ranked engine's waves dispatch
    # through the shard_map pivot and scorer as they do there
    topk_engine.default_resident = lambda: "kernel"
    from harness import cell
    from repro.distributed.resilient import ResilientEngine

    def run(workload):
        try:
            out = cell.run(".", workload, 2**31 + 17, 1.0, False,
                           time.perf_counter(), require_chip=False)
        except cell.BenchFailure as e:
            return {"failure": str(e)}
        return {k: out[k] for k in ("correct", "attempted", "failed", "checks")}

    got = {w: run(w) for w in sys.argv[1:]}

    # an answer altered in every wave served with shard 1 failed or dead
    real = ResilientEngine.topk_batch

    def planted(self, queries, k=10):
        results, info = real(self, queries, k)
        if info.failed_shards or self.sa.dead.any():
            results = [(d + 1, s) for d, s in results]
        return results, info

    ResilientEngine.topk_batch = planted
    got["planted"] = run(sys.argv[1])
    print(json.dumps(got))
""")


@pytest.fixture(scope="module")
def runs(root):
    """One process with four host devices runs the cells once each."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_BACKEND="ref",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", RUNS, TOPK, AND, LATE,
                        "tiny-top10-sharded"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-6000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    out["stderr"] = p.stderr
    return out


def _lines(stderr: str, tag: str) -> list[dict]:
    return [dict(kv.split("=", 1) for kv in line.split()[1:])
            for line in stderr.splitlines() if line.startswith(f"[{tag}] ")]


@pytest.mark.parametrize("workload", [TOPK, AND])
def test_sound_failover_run_is_correct(runs, workload):
    out = runs[workload]
    assert out["correct"] is True, out
    assert set(out["checks"]) == FAULT_CHECKS
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


def test_one_kill_fails_over_and_recovers(runs):
    lines = _lines(runs["stderr"], "failover")
    assert len(lines) >= 2  # the two sound runs, before the planted one
    for f in lines[:2]:
        assert f["scheduled"] == "1" and f["fired"] == "1"
        assert int(f["failovers"]) >= 1 and int(f["failover_waves"]) >= 1
        assert f["degraded_answers"] == "0"
        assert f["health"] == "HEALTHY,HEALTHY,HEALTHY,HEALTHY"
        assert f["readmitted_at"] != "-"
        assert float(f["killed_at"]) >= float(f["armed_at"]) >= 0.3
    rehearsals = [w for w in _lines(runs["stderr"], "warmup") if "fault" in w]
    assert rehearsals and all(w["shard"] == "1" for w in rehearsals)
    setups = _lines(runs["stderr"], "setup")
    assert all("checkpoint_s" in s for s in setups[:2])


def test_planted_failover_answer_is_caught(runs):
    out = runs["planted"]
    assert out["correct"] is False
    assert out["checks"]["failover_wrong_answers"]["value"] > 0


def test_kill_that_never_fires_fails_the_run(runs):
    assert "0 of the traffic's 1 faults fired" in runs[LATE]["failure"]


def test_sharded_layout_without_faults_keeps_todays_checks(runs):
    out = runs["tiny-top10-sharded"]
    assert out["correct"] is True
    assert set(out["checks"]) == BASE_CHECKS


def test_layout_and_faults_resolve(root):
    c = spec.resolve(root, TOPK)
    assert c.layout == {"shards": 4, "replicas": 2}
    assert c.faults == [{"at_s": 0.3, "shard": 1}]
    words = spec.resolve(root, "tiny-words")
    assert words.layout is None and words.faults == []
    assert spec.resolve(root, "gov2-top10-overload").layout is None


@pytest.mark.parametrize("workload,message", [
    ("tiny-one-replica", "replicas >= 2"),
    ("tiny-words-kill", "replicas >= 2"),
    ("tiny-one-chip", "asks for 1 chips"),
])
def test_inconsistent_layouts_are_refused(root, workload, message):
    with pytest.raises(spec.SpecError, match=message):
        spec.resolve(root, workload)


def test_malformed_faults_are_refused(root, tmp_path):
    shutil.copytree(root / "bench", tmp_path / "bench")
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    mix = tmp_path / "bench/traffic/top10-failover.json"
    tr = json.loads(mix.read_text())
    for bad in ([{"at_s": 1.0, "shard": 4}], [{"at_s": -1, "shard": 0}],
                [{"shard": 0}], []):
        tr["faults"] = bad
        mix.write_text(json.dumps(tr))
        with pytest.raises(spec.SpecError):
            spec.resolve(tmp_path, TOPK)


def test_chip_run_refuses_a_layout_without_its_mesh():
    """A host shard loop (mesh None) is refused in a chip run, with the
    reason, and a mesh of the layout's size is taken."""
    from types import SimpleNamespace

    from harness import cell

    layout = {"shards": 4, "replicas": 2}
    loop = SimpleNamespace(sharded=SimpleNamespace(mesh=None))
    with pytest.raises(cell.BenchFailure, match="host loop on one chip"):
        cell.require_mesh(loop, layout, require_chip=True)
    cell.require_mesh(loop, layout, require_chip=False)
    cell.require_mesh(loop, None, require_chip=True)
    mesh = SimpleNamespace(sharded=SimpleNamespace(
        mesh=SimpleNamespace(shape={"shard": 4})))
    cell.require_mesh(mesh, layout, require_chip=True)
    small = SimpleNamespace(sharded=SimpleNamespace(
        mesh=SimpleNamespace(shape={"shard": 2})))
    with pytest.raises(cell.BenchFailure, match="host loop on one chip"):
        cell.require_mesh(small, layout, require_chip=True)
