"""BENCHMARK.json resolves to its files, and new files are found by name."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves(workload):
    cell = spec.resolve(ROOT, workload)
    assert cell.config["corpus"]["postings"] > 0
    assert cell.traffic["operation"] in ("topk", "and")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names, (m["name"], "moves a metric the cell reports")
        assert callable(spec.load_reader(ROOT, m["name"]))


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used and c["file"].startswith("bench/")
        for key in c["reduced"]:
            assert key in json.loads((ROOT / c["file"]).read_text())["reduced"]
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_added_files_are_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric are picked up
    from new files and new entries alone."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((ROOT / "bench/configs/gov2-web.json").read_text())
    cfg["name"] = "cc-news-small"
    cfg["corpus"]["n_docs"] = 1_000_000
    (tmp_path / "bench/configs/cc-news-small.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/top10-overload.json").read_text())
    mix["popularity"]["s"] = 0.0
    (tmp_path / "bench/traffic/top10-uniform.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/serve.waves_per_s.py").write_text(
        "def read(run):\n    return 7.0 if run.operation == 'topk' else None\n")
    bench["configs"].append({"name": "cc-news-small", "source": "https://example.org",
                             "file": "bench/configs/cc-news-small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "cc-top10-uniform", "config": "cc-news-small",
                               "traffic": "top10-uniform", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "serve.waves_per_s", "unit": "waves/s",
                               "better": "higher", "source": "program_counter",
                               "layer": "serving loop", "moves": "qps",
                               "workloads": ["cc-top10-uniform"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gov2-top10-overload" in m.get("workloads", []):
            m["workloads"].append("cc-top10-uniform")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve(tmp_path, "cc-top10-uniform")
    assert cell.config["corpus"]["n_docs"] == 1_000_000
    assert cell.traffic["popularity"]["s"] == 0.0
    got = {m["name"] for m in cell.per_layer}
    assert "serve.waves_per_s" in got and "device.idle_share.serve" in got
    assert spec.load_reader(tmp_path, "serve.waves_per_s")(
        type("R", (), {"operation": "topk"})()) == 7.0
    for rel, data in before.items():  # no file that was there changed
        assert (tmp_path / rel).read_bytes() == data, rel


def test_metric_without_workloads_is_refused(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    del bench["per_layer"][0]["workloads"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match="names no workloads"):
        spec.resolve(tmp_path, CELLS[0])


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.resolve(ROOT, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_reader(ROOT, "no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.resolve(tmp_path, CELLS[0])  # no BENCHMARK.json there
