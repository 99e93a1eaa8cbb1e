"""A whole run at a tiny size on the CPU: sound, with faults, and the control.

The run skips the look for a chip (``require_chip=False``) and otherwise
goes through every step a chip run takes: set-up, warm-up, the measured
window, the check against the plain reference and the metrics.  Faults are
planted in the engines' batched entry points, where answers are produced.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell, control, spec  # noqa: E402

TOPK = "gov2-top10-overload"
AND = "gov2-and-replay"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A checkout-shaped root whose configurations and mixes are cut to a
    size the CPU runs in a second."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        p = root / c["file"]
        cfg = json.loads(p.read_text())
        # the universe shrinks with the postings, so lists keep their density
        cfg["corpus"].update(postings=40_000, n_lists=48, n_docs=41_000)
        p.write_text(json.dumps(cfg))
    for p in (root / "bench" / "traffic").glob("*.json"):
        tr = json.loads(p.read_text())
        tr["popularity"]["skip_top"] = 2
        tr.update(batch=8, check_sample=12, rate_qps=40.0, log_queries=256)
        tr["warmup"].update(segment_s=0.5, min_segments=1, max_segments=2)
        p.write_text(json.dumps(tr))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, workload, traced=False, seed=2**31 + 5):
    return cell.run(root, workload, seed, 1.0, traced, time.perf_counter(),
                    require_chip=False)


@pytest.mark.parametrize("workload", [TOPK, AND])
def test_sound_run_is_correct(tiny, workload):
    out = run(tiny, workload)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    want = {m["name"] for m in spec.resolve(tiny, workload).end_to_end}
    assert set(out["metrics"]) == want
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["metrics"]["qps"]["value"] > 0
    assert out["device"]["count"] >= 1


def test_traced_run_reports_per_layer_metrics(tiny):
    out = run(tiny, TOPK, traced=True)
    assert out["correct"] is True
    assert "serve.admit_lag_p95_ms" in out["metrics"]
    assert out["metrics"]["serve.request_p95_ms"]["value"] > 0
    assert "build.index_bits_per_posting" in out["metrics"]
    assert "p95_ms" not in out["metrics"]
    assert out["device"]["window_s"] > 0.5
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter(answers):
    """Every answer altered where it is produced: its first docID moved."""
    out = []
    for a in answers:
        if isinstance(a, tuple):
            d = np.array(a[0], copy=True)
            if len(d):
                d[0] += 1
            out.append((d, a[1]))
        else:
            a = np.array(a, copy=True)
            if len(a):
                a[0] += 1
            out.append(a)
    return out


def _drop_half(answers):
    """Half of the batch left out: the second half answered empty."""
    h = len(answers) // 2
    empty = [(np.zeros(0, np.int64), np.zeros(0)) if isinstance(a, tuple)
             else np.zeros(0, np.int64) for a in answers[h:]]
    return list(answers[:h]) + empty


@pytest.mark.parametrize("fault", [_alter, _drop_half])
@pytest.mark.parametrize("workload", [TOPK, AND])
def test_faults_are_not_correct(tiny, workload, fault, monkeypatch):
    from repro.core.query_engine import QueryEngine
    from repro.ranked.topk_engine import TopKEngine

    topk, inter = TopKEngine.topk_batch, QueryEngine.intersect_batch
    monkeypatch.setattr(TopKEngine, "topk_batch",
                        lambda self, q, k: fault(topk(self, q, k)))
    monkeypatch.setattr(QueryEngine, "intersect_batch",
                        lambda self, q: fault(inter(self, q)))
    out = run(tiny, workload)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("workload", [TOPK, AND])
def test_control_in_the_programs_place_is_not_correct(tiny, workload, monkeypatch):
    real = cell.setup

    def with_control(c, seed, log, require_chip):
        s = real(c, seed, log, require_chip)
        s.engine = control.ControlEngine(s.lists, s.freqs, c.config["scoring"])
        return s

    monkeypatch.setattr(cell, "setup", with_control)
    out = run(tiny, workload)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("workload", [TOPK, AND])
def test_control_readings(tiny, workload):
    c = spec.resolve(tiny, workload)
    for seed in (11, 12, 13):
        r = control.readings(c, seed, 1.0, closed_qps=200.0)
        assert r["compared"] >= 12 and r["wrong_answers"] > 0


def _cli(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TOPK, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _cli(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_system(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
