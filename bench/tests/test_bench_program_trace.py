"""Idle device time split by program span, piece by piece, and the readers
of it; ``trace.reduce`` reports what it did before on the same trace."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import program_trace, spec  # noqa: E402
from harness.cell import Run  # noqa: E402
from harness.program_trace import (  # noqa: E402
    COMPILE, NO_SPAN, ProgramGaps, attribute, depths)
from harness.trace import WINDOW, Interval, reduce  # noqa: E402

I = Interval


def fixture():
    """The window 10-20 s of ``test_bench_trace``'s fixture, with program
    spans on its thread: two waves, each seed -> pivot -> rescore, one
    compile inside the second wave's pivot."""
    host = [
        I(10.0, 20.0, WINDOW),
        I(11.0, 13.0, "bench.topk_batch"),
        I(13.0, 13.5, "bench.wave_form"),
        I(15.0, 19.0, "bench.topk_batch"),
        I(1.0, 2.0, "bench.warmup"),
    ]
    dev = [
        I(9.0, 10.5, "decode_search_blocks"),
        I(11.5, 12.0, "pivot_select_blocks"),
        I(11.8, 12.2, "fusion.3"),
        I(16.0, 17.0, "bm25_score_blocks.1"),
        I(19.5, 21.0, "bm25_score_probe_blocks"),
    ]
    prog = [
        I(11.0, 13.0, "repro.topk_batch"),
        I(11.0, 11.4, "repro.seed"),
        I(11.1, 11.3, "repro.membership"),
        I(11.4, 12.5, "repro.pivot"),
        I(12.5, 13.0, "repro.rescore"),
        I(12.6, 12.9, "repro.membership"),
        I(15.0, 19.0, "repro.topk_batch"),
        I(15.0, 15.5, "repro.seed"),
        I(15.5, 18.0, "repro.pivot"),
        I(17.2, 17.7, COMPILE),
        I(18.0, 19.0, "repro.rescore"),
    ]
    return [dev], host, prog


def test_reduce_reads_what_it_read_before():
    """The existing per-layer numbers and ``idle_gaps`` on the existing
    fixture, pinned: the program pass adds to them and moves none."""
    (dev,), host, _ = fixture()
    s = reduce([dev], host)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(2.7)
    assert s.gap_s == pytest.approx({"bench.topk_batch": 3.5, "host.none": 3.8})
    assert s.kernel_s(["pivot_select_blocks"]) == pytest.approx(0.5)
    assert s.kernel_s(["bm25_score_blocks", "bm25_score_probe_blocks"]) == (
        pytest.approx(1.5))


def test_program_spans_in_a_real_trace(tmp_path):
    """On a trace written by ``jax.profiler`` with the program armed,
    ``trace.load`` still hands ``reduce`` the ``bench.*`` spans alone, and
    the program pass finds the window and the ``repro.*`` spans of its
    thread."""
    import jax

    from harness import trace as trace_mod
    from repro import obs

    armed = obs.enabled()
    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            with jax.profiler.TraceAnnotation("bench.topk_batch"):
                with obs.span("topk_batch"):
                    with obs.span("seed"):
                        jax.jit(lambda x: x + 1)(1.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        obs.enable(armed)
    _, host = trace_mod.load(str(tmp_path))
    assert sorted(h.name for h in host) == ["bench.topk_batch", WINDOW]
    (path,) = tmp_path.glob("**/*.xplane.pb")
    devices, (lo, hi), spans = program_trace.load(str(path))
    names = [sp.name for sp in spans]
    assert {"repro.topk_batch", "repro.seed", COMPILE} <= set(names)
    assert all(lo <= sp.start <= sp.end <= hi for sp in spans)
    assert devices == []  # the CPU is no device here, as for ``trace.load``
    g = program_trace.attribute(devices, spans, lo, hi)
    assert g.n_devices == 0 and "repro.seed" in g.seen


def test_idle_split_piecewise_by_innermost_span():
    (dev,), _, prog = fixture()
    g = attribute([dev], prog, 10.0, 20.0)
    # idle: 10.5-11.5, 12.2-16, 17-19.5
    assert sum(g.self_s.values()) == pytest.approx(10.0 - 2.7)
    assert g.self_s == pytest.approx({
        NO_SPAN: 0.5 + 2.0 + 0.5,                # 10.5-11, 13-15, 19-19.5
        "repro.seed": 0.1 + 0.1 + 0.5,           # 11-11.1, 11.3-11.4, 15-15.5
        "repro.membership": 0.2 + 0.3,           # 11.1-11.3, 12.6-12.9
        "repro.pivot": 0.1 + 0.3 + 0.5 + 0.2 + 0.3,  # ..., 17-17.2, 17.7-18
        "repro.rescore": 0.1 + 0.1 + 1.0,        # 12.5-12.6, 12.9-13, 18-19
        COMPILE: 0.5,                            # 17.2-17.7
    })
    assert g.incl_s["repro.topk_batch"] == pytest.approx(1.3 + 3.0)
    assert g.incl_s["repro.seed"] == pytest.approx(0.4 + 0.5)
    assert g.incl_s["repro.pivot"] == pytest.approx(0.1 + 0.3 + 0.5 + 1.0)
    assert g.incl_s["repro.rescore"] == pytest.approx(0.5 + 1.0)
    assert g.incl_s["repro.membership"] == pytest.approx(0.5)
    assert g.incl_s[COMPILE] == pytest.approx(0.5)
    # the phases of a wave cover its whole idle time
    waves = sum(g.incl_s[n] for n in ("repro.seed", "repro.pivot", "repro.rescore"))
    assert waves == pytest.approx(g.incl_s["repro.topk_batch"])
    assert g.share(["repro.rescore"]) == pytest.approx(15.0)
    assert g.share(["repro.gather"]) is None
    assert g.share(["repro.gather"], absent=0.0) == 0.0
    assert g.top_self(1)[0][0] == NO_SPAN


def test_no_midpoint_rule_inside_one_idle_stretch():
    """One idle stretch over a whole wave is split at every boundary, not
    credited whole to the span that holds its middle."""
    spans = [I(0.0, 10.0, "repro.topk_batch"), I(0.0, 2.0, "repro.seed"),
             I(2.0, 9.0, "repro.pivot"), I(9.0, 10.0, "repro.rescore")]
    g = attribute([[I(10.0, 11.0, "bm25_score_blocks")]], spans, 0.0, 11.0)
    assert g.self_s == pytest.approx(
        {"repro.seed": 2.0, "repro.pivot": 7.0, "repro.rescore": 1.0})
    assert g.incl_s["repro.topk_batch"] == pytest.approx(10.0)


def test_compile_event_names():
    for name in ("backend_compile_and_load", "lower_sharding_computation",
                 "PJRT_Client_Compile linkage", "DeserializeExecutable"):
        assert program_trace.is_compile(name), name
    for name in ("PjitFunction(fn)", "np.asarray(jax.Array)", "shard_args",
                 "repro.seed", "bench.topk_batch"):
        assert not program_trace.is_compile(name), name


def test_jitted_call_that_lowers_counts_as_compile():
    """A cache load has no event of its own: the jitted call that lowered
    counts whole; a call on the fast path does not."""
    thread = [
        I(0.0, 10.0, WINDOW),
        I(1.0, 5.0, "repro.score_rows"),
        I(1.5, 3.0, "PjitFunction(fn)"),      # lowers, then loads from cache
        I(1.6, 1.7, "lower_sharding_computation"),
        I(3.5, 3.6, "PjitFunction(fn)"),      # the fast path
        I(4.0, 4.9, "PjitFunction(fn)"),      # lowers and compiles
        I(4.1, 4.2, "lower_sharding_computation"),
        I(4.2, 4.8, "backend_compile_and_load"),
        I(6.0, 6.1, "shard_args"),
    ]
    got = [(sp.start, sp.end, sp.name) for sp in program_trace.program_spans(thread)]
    assert got == [
        (1.0, 5.0, "repro.score_rows"),
        (1.5, 3.0, COMPILE), (1.6, 1.7, COMPILE),
        (4.0, 4.9, COMPILE), (4.1, 4.2, COMPILE), (4.2, 4.8, COMPILE),
    ]
    g = attribute([[I(9.0, 10.0, "x")]], program_trace.program_spans(thread),
                  0.0, 10.0)
    assert g.self_s[COMPILE] == pytest.approx(1.5 + 0.9)
    assert g.incl_s["repro.score_rows"] == pytest.approx(4.0)


def test_depths_and_devices():
    spans = [I(0, 10, "repro.a"), I(1, 5, "repro.b"), I(2, 3, COMPILE),
             I(2, 4, "repro.c"), I(6, 7, "repro.d")]
    assert depths(spans) == [0, 1, float("inf"), 2, 1]
    # two devices: the mean of their idle time
    g = attribute([[I(0, 1, "x")], [I(1, 2, "y")]], [I(0, 2, "repro.a")], 0, 2)
    assert g.incl_s["repro.a"] == pytest.approx(1.0) and g.n_devices == 2
    # no device in the trace: nothing to split
    g = attribute([], [I(0, 2, "repro.a")], 0, 2)
    assert g.self_s == {} and g.n_devices == 0


def test_for_run_finds_its_own_trace(tmp_path, monkeypatch):
    """The newest trace whose window is the run's; None without one, on a
    trace without program spans, and on a run not traced."""
    monkeypatch.setattr(program_trace.tempfile, "tempdir", str(tmp_path))
    paths = {}
    for name, window in (("old", 10.0), ("mine", 7.5)):
        p = tmp_path / f"bench-trace-{name}" / "plugins" / "h.xplane.pb"
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        paths[str(p)] = ProgramGaps(window, incl_s={"repro.seed": 1.5},
                                    seen={"repro.seed"})
    monkeypatch.setattr(program_trace, "of_file", paths.__getitem__)
    run = Run("topk")
    assert program_trace.for_run(run) is None  # not traced
    run.trace = reduce(*fixture()[:2])
    run.trace.window_s = 7.5
    assert program_trace.for_run(run).share(["repro.seed"]) == pytest.approx(20.0)
    run.trace.window_s = 3.0
    assert program_trace.for_run(run) is None  # no trace of this window
    for g in paths.values():
        g.seen = {COMPILE}  # a program that opens no repro. span
    run.trace.window_s = 7.5
    assert program_trace.for_run(run) is None


NEW = {
    "topk": {
        "ranked.seed.idle_share.topk": 9.0,
        "ranked.pivot.idle_share.topk": 19.0,
        "ranked.rescore.idle_share.topk": 15.0,
        "ranked.membership.idle_share.topk": 5.0,
    },
    "and": {
        "engine.seed_candidates.idle_share.and": 4.0,
        "engine.staging.idle_share.and": 6.0,
        "engine.member_filter.idle_share.and": 20.0,
    },
}


def and_gaps() -> ProgramGaps:
    incl = {"repro.gather": 0.4, "repro.member_filter": 2.0,
            "repro.group_cursors": 0.1, "repro.codec_split": 0.2,
            "repro.stage": 0.3, "repro.fetch": 0.5}
    return ProgramGaps(10.0, incl_s=incl, seen=set(incl))


@pytest.mark.parametrize("op", ["topk", "and"])
def test_new_readers(op, monkeypatch):
    """Each new reader reads its own operation's cells and nothing in the
    other's; the counters' readers read the engines' stats."""
    (dev,), host, prog = fixture()
    gaps = {"topk": attribute([dev], prog, 10.0, 20.0), "and": and_gaps()}
    run = Run(op)
    run.trace = reduce([dev], host)
    monkeypatch.setattr(program_trace, "for_run", lambda r: gaps[r.operation])
    read = lambda name: spec.load_reader(ROOT, name)(run)  # noqa: E731
    other = "and" if op == "topk" else "topk"
    for name, want in NEW[op].items():
        assert read(name) == pytest.approx(want), name
    for name in NEW[other]:
        assert read(name) is None, name
    assert read("jit.compile.idle_share") == pytest.approx(
        5.0 if op == "topk" else 0.0)
    counters = {"topk": "ranked.round_trips_per_batch",
                "and": "engine.round_trips_per_batch.and"}
    assert read(counters[op]) is None  # the parent's stats: no counter
    run.stats = {"batches": 4, "device_round_trips": 26}
    assert read(counters[op]) == pytest.approx(6.5)
    assert read(counters[other]) is None
    monkeypatch.setattr(program_trace, "for_run", lambda r: None)
    for name in [*NEW[op], "jit.compile.idle_share"]:
        assert read(name) is None, name


def test_new_metrics_are_declared():
    bench = spec.load_benchmark(ROOT)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for cells in NEW.values():
        for name in cells:
            assert declared[name]["moves"] == "qps"
    assert set(declared["jit.compile.idle_share"]["workloads"]) == {
        "gov2-top10-overload", "gov2-and-replay"}
