"""Smoke-run every benchmark module on tiny corpora (ISSUE-2 satellite).

Benchmark drift used to rot silently until someone ran ``benchmarks.run`` by
hand; here each module executes its --smoke profile inside the tier-1 suite,
and the --json plumbing is exercised end-to-end.  Timing ASSERTIONS inside
the benchmarks are relaxed in smoke mode (tiny corpora time unreliably);
correctness assertions (identical results vs oracles) still run.
"""

import json
import pathlib
import sys

import pytest

# repo root: `benchmarks` is a plain package next to src/ and tests/
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import (  # noqa: E402
    bench_build_time,
    bench_codecs,
    bench_competitors,
    bench_faults,
    bench_fig1_distribution,
    bench_kernels,
    bench_nextgeq,
    bench_obs,
    bench_partition_space,
    bench_queries,
    bench_ranked,
    bench_serve,
    bench_vbyte_family,
    roofline,
)
from benchmarks.common import RESULTS, reset_results  # noqa: E402

MODULES = {
    "bench_fig1_distribution": bench_fig1_distribution,
    "bench_vbyte_family": bench_vbyte_family,
    "bench_partition_space": bench_partition_space,
    "bench_build_time": bench_build_time,
    "bench_queries": bench_queries,
    "bench_competitors": bench_competitors,
    "bench_faults": bench_faults,
    "bench_nextgeq": bench_nextgeq,
    "bench_kernels": bench_kernels,
    "bench_ranked": bench_ranked,
    "bench_serve": bench_serve,
    "bench_obs": bench_obs,
    "bench_codecs": bench_codecs,
    "roofline": roofline,
}


@pytest.mark.parametrize("name", sorted(MODULES))
def test_benchmark_smoke(name, capsys):
    reset_results()
    MODULES[name].run(quick=True, smoke=True)
    out = capsys.readouterr().out
    if name == "roofline":  # table generator: silent without dryrun JSONs
        return
    assert out.strip(), f"{name} emitted nothing"
    # every emitted line is well-formed CSV and registered for --json
    lines = [l for l in out.strip().splitlines() if "," in l]
    assert len(lines) == len(RESULTS) > 0
    for line in lines:
        _, us, _ = line.split(",", 2)
        assert float(us) >= 0.0


def test_run_json_appends_history(tmp_path, monkeypatch, capsys):
    """--json keeps a HISTORY of runs (git sha + timestamp per entry) while
    mirroring the newest run at the top level for old readers."""
    from benchmarks import run as bench_run

    monkeypatch.chdir(tmp_path)
    # main() turns the compile cache on; with the variable set it leaves
    # this process's jax config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setattr(
        sys, "argv",
        ["benchmarks.run", "--smoke", "--json", "--only", "table5"],
    )
    bench_run.main()
    capsys.readouterr()
    data = json.loads((tmp_path / "BENCH_queries.json").read_text())
    assert data["profile"] == "smoke"
    recs = {r["name"]: r for r in data["records"]}
    fused = recs["table5_and_fused_vbyte_opt"]
    assert fused["module"] == "table5"
    for field in ("ops_per_sec", "p50_us", "p99_us", "speedup_vs_pr1"):
        assert field in fused, field
    assert fused["ops_per_sec"] > 0
    assert fused["p99_us"] >= fused["p50_us"] > 0
    assert len(data["history"]) == 1

    # second run APPENDS instead of overwriting
    bench_run.main()
    capsys.readouterr()
    data2 = json.loads((tmp_path / "BENCH_queries.json").read_text())
    assert len(data2["history"]) == 2
    for entry in data2["history"]:
        assert entry["profile"] == "smoke"
        assert "sha" in entry and "timestamp" in entry
        assert {r["name"] for r in entry["records"]} == set(recs)
    # top level mirrors the newest entry
    assert data2["records"] == data2["history"][-1]["records"]


def test_run_json_migrates_pre_history_file(tmp_path, monkeypatch, capsys):
    """A PR-2-era BENCH file (no history) becomes history entry #1."""
    from benchmarks import run as bench_run

    monkeypatch.chdir(tmp_path)
    # main() turns the compile cache on; with the variable set it leaves
    # this process's jax config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    old = {"profile": "quick",
           "records": [{"name": "legacy_record", "us_per_call": 1.0,
                        "derived": ""}]}
    (tmp_path / "BENCH_queries.json").write_text(json.dumps(old))
    monkeypatch.setattr(
        sys, "argv",
        ["benchmarks.run", "--smoke", "--json", "--only", "fig7"],
    )
    bench_run.main()
    capsys.readouterr()
    data = json.loads((tmp_path / "BENCH_queries.json").read_text())
    assert len(data["history"]) == 2
    assert data["history"][0]["sha"] == "pre-history"
    assert data["history"][0]["records"][0]["name"] == "legacy_record"
    assert data["history"][1]["profile"] == "smoke"
