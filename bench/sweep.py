#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, in one process.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 10 20 40 ...

One set-up (as ``bench/run.py``'s), then for each rate of the ladder, in
order, a window of ``--seconds`` of the cell's own traffic at that rate.
A rate is sustained when its backlog does not grow: every request due in
the window is answered by the close plus two median latencies (at least
one second), and the median latency of the last third of the requests is
at most 1.5 times that of the first third plus a quarter second.  Prints
one JSON line per rate and last the knee, the highest rate sustained.
The ladder stops after two rates in a row that are not sustained.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from harness import cell, spec, traffic
    from repro.launch.compile_cache import enable_compile_cache

    c = spec.resolve(BENCH.parent, args.workload)
    if c.traffic["loop"] != "open":
        print("sweep: the cell is not an open loop", file=sys.stderr)
        return 1
    try:
        cell.check_device(c.chips)
    except cell.BenchFailure as e:
        print(f"sweep: FAILED: {e}", file=sys.stderr)
        return 1
    cell.keep_every_program(enable_compile_cache())
    log = cell.CompileLog()
    s = cell.setup(c, args.seed, log, True)
    s.phases.update(cell.warm_up(s, log))
    cell.say("setup", setup_s=round(time.perf_counter() - T_START, 3),
             **{k: round(v, 3) for k, v in s.phases.items()}, **log.snapshot())
    knee, misses = None, 0
    for i, rate in enumerate(args.rates):
        rng = cell.rng_stream(args.seed, 10 + i)
        n = max(int(round(rate * args.seconds)), 1)
        queries = traffic.query_log(rng, c.traffic, s.rank_to_list, n, 10 + i)
        due = traffic.poisson_due(rng, rate, n)
        loaded, hits = log.loaded(), log.hits
        admit, answer, wait_s, _, t_close = cell.open_loop(s, queries, due, args.seconds)
        ok = np.isfinite(answer)
        lat = np.where(ok, answer - due, t_close - due)
        third = max(n // 3, 1)
        p50 = float(np.median(lat))
        early, late = float(np.median(lat[:third])), float(np.median(lat[-third:]))
        last = float(np.nanmax(answer)) if ok.any() else float("inf")
        sustained = bool(ok.all() and last <= args.seconds + max(1.0, 2 * p50)
                         and late <= 1.5 * early + 0.25)
        print(json.dumps({
            "rate_qps": rate, "requests": n, "answered": int(ok.sum()),
            "completed_qps": cell.completed_rate(answer, args.seconds),
            "sustained": sustained, "p50_ms": p50 * 1e3,
            "p95_ms": cell.nearest_rank(lat, 0.95) * 1e3,
            "early_p50_ms": early * 1e3, "late_p50_ms": late * 1e3,
            "last_answer_s": last, "loaded_in_window": log.loaded() - loaded,
            "from_cache_in_window": log.hits - hits,
            "admit_lag_p95_ms": cell.nearest_rank(admit - due, 0.95) * 1e3,
        }), flush=True)
        if sustained:
            knee, misses = rate, 0
        else:
            misses += 1
            if misses >= 2:
                break
    print(json.dumps({"workload": args.workload, "knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
