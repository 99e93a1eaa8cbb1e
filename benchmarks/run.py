"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Default is the quick profile
(CI-sized datasets); ``--full`` uses paper-scale list lengths and ``--smoke``
tiny corpora (seconds total -- the tier-1 drift check).  ``--json`` also
maintains machine-readable ``BENCH_<group>.json`` files (ops/sec + latency
percentiles per record): each run APPENDS a history entry stamped with the
git sha and a UTC timestamp, so the perf trajectory across PRs is actually
recorded -- the top-level ``profile``/``records`` keys always mirror the
newest entry for old readers, and ``tools/check_bench.py`` diffs the last
two same-profile entries to flag regressions.

  PYTHONPATH=src python -m benchmarks.run [--full|--smoke] [--only tableN] [--json]
"""

from __future__ import annotations

import argparse
import datetime
import json
import subprocess
import sys
import time

from repro import obs
from repro.launch.compile_cache import enable_compile_cache

from . import (
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
from .common import RESULTS, reset_results

MODULES = {
    "fig1": bench_fig1_distribution,
    "table2": bench_vbyte_family,
    "table3": bench_partition_space,
    "table4": bench_build_time,
    "table5": bench_queries,
    "table6": bench_competitors,
    "fig7": bench_nextgeq,
    "faults": bench_faults,
    "kernels": bench_kernels,
    "ranked": bench_ranked,
    "serve": bench_serve,
    "roofline": roofline,
    "obs": bench_obs,
    "codecs": bench_codecs,
}

# history entries kept per BENCH_*.json: enough trajectory for the
# regression gate and for eyeballing trends, without unbounded file growth
MAX_HISTORY = 40

# module key -> BENCH_<group>.json the records belong to
JSON_GROUPS = {
    "table5": "queries",
    "fig7": "queries",
    "faults": "faults",
    "kernels": "kernels",
    "ranked": "ranked",
    "serve": "serve",
    "obs": "obs",
    "codecs": "codecs",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpora; assertions that need real timing "
                         "spreads are skipped")
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys (e.g. table5,ranked); "
                         "tools/tier1.sh uses this to re-measure only the "
                         "regressed groups on a flaked gate")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_queries.json / BENCH_kernels.json")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    profile = "full" if args.full else ("smoke" if args.smoke else "quick")
    enable_compile_cache()
    only = None
    if args.only:
        only = {m.strip() for m in args.only.split(",") if m.strip()}
        unknown = only - MODULES.keys()
        if unknown:
            ap.error(f"unknown --only modules {sorted(unknown)}; "
                     f"known: {sorted(MODULES)}")
        if args.json:
            # a BENCH_<group>.json history entry must stay COMPLETE (its
            # records mirror the whole group): selecting one module of a
            # shared group pulls in the siblings, else the appended entry
            # would silently drop their records
            groups_hit = {JSON_GROUPS.get(m) for m in only} - {None}
            only |= {m for m, g in JSON_GROUPS.items() if g in groups_hit}
    print("name,us_per_call,derived")
    # the bench run is the one place the obs layer is always armed: each
    # history entry below carries the counter DELTAS its module produced,
    # so a perf regression in BENCH_*.json comes with its internal context
    # (cache hit ratios, rescore rounds, shard dispatch mix, ...)
    obs.enable()
    obs.reset()
    groups: dict[str, list[dict]] = {}
    obs_by_group: dict[str, dict[str, dict]] = {}
    for name, mod in MODULES.items():
        if only is not None and name not in only:
            continue
        reset_results()
        before = obs.snapshot(events=False)
        t0 = time.time()
        try:
            mod.run(quick=not args.full, smoke=args.smoke)
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0.00,{type(e).__name__}: {e}", file=sys.stdout)
            raise
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        group = JSON_GROUPS.get(name)
        if group:
            groups.setdefault(group, []).extend(
                {**rec, "module": name} for rec in RESULTS
            )
            obs_by_group.setdefault(group, {})[name] = obs.diff(
                obs.snapshot(events=False), before
            )
    if args.json:
        for group, records in groups.items():
            path = f"BENCH_{group}.json"
            entry = {
                "sha": _git_sha(),
                "timestamp": datetime.datetime.now(
                    datetime.timezone.utc
                ).isoformat(timespec="seconds"),
                "profile": profile,
                "records": records,
                "obs": obs_by_group.get(group, {}),
            }
            history = _load_history(path)
            history.append(entry)
            history = history[-MAX_HISTORY:]
            with open(path, "w") as fh:
                # top-level profile/records mirror the NEWEST entry so
                # pre-history readers keep working; history has them all
                json.dump(
                    {
                        "profile": profile,
                        "records": records,
                        "history": history,
                    },
                    fh, indent=1,
                )
                fh.write("\n")
            print(
                f"# appended to {path} ({len(records)} records, "
                f"{len(history)} history entries)", file=sys.stderr,
            )
        # NOT BENCH_*.json: tools/check_bench.py globs that pattern and
        # would choke on the snapshot schema.  CI uploads this next to
        # the bench artifacts (tier1.yml).
        obs.write_snapshot("OBS_snapshot.json", events=False)
        print("# wrote OBS_snapshot.json", file=sys.stderr)


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001  (no git / not a repo: still record)
        return "unknown"


def _load_history(path: str) -> list[dict]:
    """Existing history entries; a pre-history file becomes entry #1."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return []
    if "history" in data:
        return list(data["history"])
    if "records" in data:  # migrate the old single-run schema
        return [{
            "sha": "pre-history",
            "timestamp": None,
            "profile": data.get("profile", "unknown"),
            "records": data["records"],
        }]
    return []


if __name__ == "__main__":
    main()
