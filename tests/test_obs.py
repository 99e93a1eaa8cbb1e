"""repro.obs (ISSUE-8 tentpole): metrics registry, span tracing, exporters.

The layer's two contracts, tested from both sides:

* ARMED: counters/gauges/histograms aggregate correctly (exact small-N
  percentiles, bucket fallback within its documented error), spans nest,
  the exporters round-trip through Prometheus text / JSON / a live HTTP
  server, and the instrumented engines surface their internals.
* DISARMED (the default): every instrumentation point is a no-op -- no
  metric materializes, no trace event lands, and instrumented engines
  return BIT-IDENTICAL answers either way.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import RAW_CAP


@pytest.fixture(autouse=True)
def obs_state():
    """Arm a clean registry per test; restore the ambient state after."""
    was = obs.enabled()
    obs.enable(True)
    obs.reset()
    yield
    obs.reset()
    obs.enable(was)


# ----------------------------------------------------------------------
# metrics primitives
# ----------------------------------------------------------------------
def test_counter_and_gauge_basics():
    c = obs.counter("widgets", kind="a")
    c.inc()
    c.add(4)
    assert c.value == 5
    # labels address distinct metrics; same labels return the same object
    assert obs.counter("widgets", kind="b").value == 0
    assert obs.counter("widgets", kind="a") is c
    g = obs.gauge("depth")
    g.set(3.5)
    g.add(0.5)
    assert g.value == 4.0
    obs.count("widgets", 2, kind="a")
    obs.set_gauge("depth", 9)
    assert c.value == 7 and g.value == 9


def test_histogram_exact_percentiles_and_summary():
    h = obs.histogram("lat_ms")
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    for x in xs:
        h.observe(x)
    for q in (0, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    s = h.summary()
    assert s["count"] == 5 and s["sum"] == pytest.approx(15.0)
    assert s["min"] == 1.0 and s["max"] == 5.0
    assert s["p50"] == pytest.approx(3.0)
    assert set(s) == {"count", "sum", "min", "max", "p50", "p90", "p99", "p999"}


def test_histogram_bucket_fallback_past_raw_cap():
    h = obs.histogram("long_run_ms")
    rng = np.random.default_rng(0)
    xs = rng.uniform(1.0, 100.0, RAW_CAP + 2_000)
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs) > RAW_CAP
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        # documented bucket-interpolation bound: <=12.5% relative error
        assert abs(h.percentile(q) - exact) / exact < 0.125, q


def test_percentile_of_edge_cases():
    p = obs.Histogram.percentile_of
    assert p([], 99) == 0.0
    assert p([7.0], 50) == 7.0
    assert p([1.0, 2.0], 50) == pytest.approx(1.5)
    assert p([1.0, 2.0, 3.0, 4.0], 99.9) == pytest.approx(
        np.percentile([1, 2, 3, 4], 99.9)
    )


def test_thread_safety_exact_totals():
    c = obs.counter("contended")
    h = obs.histogram("contended_ms")

    def work():
        for _ in range(10_000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000
    assert h.count == 80_000


def test_counterdict_is_a_dict_that_mirrors():
    d = obs.CounterDict("eng", {"hits": 0, "rows": 0}, backend="numpy")
    assert isinstance(d, dict) and d["hits"] == 0
    d["hits"] += 3
    d["hits"] += 2
    d["rows"] = 10
    assert d["hits"] == 5 and d["rows"] == 10  # the dict contract holds
    assert obs.counter("eng_hits", backend="numpy").value == 5
    assert obs.counter("eng_rows", backend="numpy").value == 10
    # non-numeric values pass through without a mirror
    d["samples"] = [1.0]
    d["samples"].append(2.0)
    assert d["samples"] == [1.0, 2.0]
    snap = obs.snapshot(events=False)
    assert not any(k.startswith("eng_samples") for k in snap["counters"])


# ----------------------------------------------------------------------
# the disarmed contract
# ----------------------------------------------------------------------
def test_disabled_is_a_complete_noop():
    obs.enable(False)
    obs.count("ghost")
    obs.observe("ghost_ms", 1.0)
    obs.set_gauge("ghost_depth", 2)
    obs.event("ghost_event", x=1)
    sp = obs.span("ghost_span")
    assert sp is obs.NULL_SPAN  # shared singleton, no allocation
    with sp:
        pass
    d = obs.CounterDict("ghost", {"n": 0})
    d["n"] += 5
    assert d["n"] == 5  # dict behavior intact...
    with obs.timer("ghost_timer_ms") as t:
        pass
    assert t.elapsed_s >= 0.0  # timers still measure for their caller
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["events"] == []


# ----------------------------------------------------------------------
# tracing
# ----------------------------------------------------------------------
def test_spans_nest_and_feed_span_ms():
    with obs.span("outer", path="t"):
        with obs.span("inner"):
            pass
    obs.event("marker", shard=3)
    evs = obs.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["path"] == "t"
    assert by_name["marker"]["kind"] == "event"
    assert by_name["marker"]["shard"] == 3
    # inner closes before outer: ring order is completion order
    assert [e["name"] for e in evs] == ["inner", "outer", "marker"]
    assert obs.REGISTRY.histogram("span_ms", span="outer", path="t").count == 1
    assert obs.REGISTRY.histogram("span_ms", span="inner").count == 1
    obs.clear_trace()
    assert obs.events() == []


def test_timer_records_ms():
    with obs.timer("step_ms", phase="x") as t:
        pass
    assert t.elapsed_s >= 0.0
    h = obs.REGISTRY.histogram("step_ms", phase="x")
    assert h.count == 1
    assert h.max == pytest.approx(t.elapsed_s * 1e3)


def _profiled_host_events(logdir, body) -> list:
    """(name, start_ns, end_ns) of every host event named ``repro.*`` in a
    ``jax.profiler`` trace of ``body()`` written under ``logdir``."""
    import jax

    jax.profiler.start_trace(str(logdir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = logdir.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return [
        (ev.name, ev.start_ns, ev.end_ns)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("repro.")
    ]


def test_armed_spans_are_profiler_host_spans(tmp_path):
    def body():
        with obs.span("outer", path="t"):
            with obs.span("inner"):
                sum(range(1000))

    evs = {name: (t0, t1) for name, t0, t1 in _profiled_host_events(tmp_path, body)}
    assert set(evs) == {"repro.outer", "repro.inner"}
    (o0, o1), (i0, i1) = evs["repro.outer"], evs["repro.inner"]
    assert o0 <= i0 <= i1 <= o1  # inner nests in outer on the profiler's clock
    # the ring and span_ms still see both
    assert [e["name"] for e in obs.events()] == ["inner", "outer"]
    assert obs.REGISTRY.histogram("span_ms", span="outer", path="t").count == 1


def test_disarmed_span_writes_no_profiler_span(tmp_path):
    obs.enable(False)
    spans = []

    def body():
        sp = obs.span("ghost")
        spans.append(sp)
        with sp:
            pass

    assert _profiled_host_events(tmp_path, body) == []
    assert spans == [obs.NULL_SPAN]


# ----------------------------------------------------------------------
# exporters
# ----------------------------------------------------------------------
def _populate():
    obs.count("reqs", 3, backend="numpy")
    obs.set_gauge("theta", 1.25)
    for v in (1.0, 2.0, 100.0):
        obs.observe("lat_ms", v)


def test_snapshot_and_prometheus_rendering():
    _populate()
    snap = obs.snapshot()
    assert snap["counters"]['reqs{backend="numpy"}'] == 3
    assert snap["gauges"]["theta"] == 1.25
    assert snap["histograms"]["lat_ms"]["count"] == 3
    text = obs.render_prometheus()
    assert "# TYPE reqs counter" in text
    assert 'reqs{backend="numpy"} 3' in text
    assert "# TYPE theta gauge" in text and "theta 1.25" in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_sum 103" in text and "lat_ms_count 3" in text
    # cumulative bucket counts are monotone
    cum = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
           if l.startswith("lat_ms_bucket")]
    assert cum == sorted(cum) and cum[-1] == 3


def test_snapshot_diff():
    _populate()
    old = obs.snapshot(events=False)
    obs.count("reqs", 2, backend="numpy")
    obs.observe("lat_ms", 5.0)
    d = obs.diff(obs.snapshot(events=False), old)
    assert d["counters"]['reqs{backend="numpy"}'] == 2
    assert d["gauges"]["theta"] == 0
    assert d["histograms"]["lat_ms"]["count"] == 1
    assert d["histograms"]["lat_ms"]["sum"] == pytest.approx(5.0)


def test_write_snapshot_roundtrip(tmp_path):
    _populate()
    path = tmp_path / "snap.json"
    wrote = obs.write_snapshot(str(path))
    back = json.loads(path.read_text())
    assert back["counters"] == {k: v for k, v in wrote["counters"].items()}
    assert back["histograms"]["lat_ms"]["count"] == 3


def test_metrics_server_http_roundtrip():
    _populate()
    with obs.MetricsServer(0) as srv:
        assert srv.port > 0
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'reqs{backend="numpy"} 3' in text
        snap = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read().decode()
        )
        assert snap["counters"]['reqs{backend="numpy"}'] == 3
        assert urllib.request.urlopen(f"{base}/snapshot").status == 200


# ----------------------------------------------------------------------
# instrumented engines: identity + coverage
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def ranked_index():
    from repro.core.index import build_partitioned_index
    from repro.data.postings import make_corpus, make_freqs, make_queries

    rng = np.random.default_rng(42)
    corpus = make_corpus(rng, n_lists=6, min_len=300, max_len=2_000,
                         mean_dense_gap=2.13, frac_dense=0.8)
    idx = build_partitioned_index(corpus, "optimal",
                                  freqs=make_freqs(rng, corpus))
    queries = [[int(t) for t in q] for q in make_queries(rng, 6, 12, 2)]
    return idx, queries


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_topk_bit_identical_with_obs_on(ranked_index, backend):
    """Arming the layer must not perturb a single score or doc id."""
    from repro.ranked.topk_engine import TopKEngine

    idx, queries = ranked_index
    eng = TopKEngine(idx, backend=backend, seed_blocks=2)
    obs.enable(False)
    want = eng.topk_batch(queries, 10)
    obs.enable(True)
    got = eng.topk_batch(queries, 10)
    for (gd, gs), (wd, ws) in zip(got, want):
        assert np.array_equal(gd, wd)
        assert np.array_equal(gs, ws)
    snap = obs.snapshot(events=False)
    # the ranked phases and counters surfaced
    assert any(k.startswith('span_ms{path="ranked"')
               or 'span="seed"' in k for k in snap["histograms"])
    assert any(k.startswith("ranked_") for k in snap["counters"])


def test_snapshot_covers_every_instrumented_subsystem(tmp_path, ranked_index):
    """One snapshot after touching engine, shards, resilience and
    checkpointing carries metrics from all four subsystems -- what a
    live ``--metrics-port`` scrape of a serving process shows."""
    from repro.checkpoint import CheckpointManager
    from repro.core.index import build_partitioned_index
    from repro.core.query_engine import QueryEngine
    from repro.data.postings import make_corpus
    from repro.distributed.resilient import ResilientEngine, ShardFaultInjector

    idx, queries = ranked_index
    # ref backend: the numpy backend serves sharded queries through the
    # global flat mirror and never touches the per-shard dispatch
    res = ResilientEngine(
        QueryEngine(idx, backend="ref", shards=2, replicas=2,
                    shard_mesh=None),
        injector=ShardFaultInjector(at_batches=(1,), shards=(0,)),
        backoff_s=1e-4,
    )
    for i in range(0, len(queries), 4):
        res.intersect_batch(queries[i : i + 4])
    rng = np.random.default_rng(3)
    # NextGEQ probes route through the per-shard fused_search dispatch
    res.search_batch(rng.integers(0, 6, 40), rng.integers(0, 1_000_000, 40))
    m = CheckpointManager(tmp_path, async_save=False)
    # non-monotone payload: stays raw (a monotone one would OptVB-pack,
    # making saved bytes the compressed size)
    tree = {"a": np.random.default_rng(5).standard_normal(100)}
    m.save(0, tree)
    m.restore(tree)
    snap = obs.snapshot(events=False)
    c, h = snap["counters"], snap["histograms"]
    assert any(k.startswith("engine_") for k in c)            # EngineCore
    assert any(k.startswith("shard_dispatch") for k in c)     # ShardedArena
    assert any(k.startswith("resilient_") for k in c)         # ResilientEngine
    assert c["checkpoint_saves"] == 1 and c["checkpoint_restores"] == 1
    assert c["checkpoint_saved_bytes"] == c["checkpoint_restored_bytes"] == 800
    assert h["checkpoint_save_ms"]["count"] == 1
    assert h["checkpoint_restore_ms"]["count"] == 1


def _spy(monkeypatch, cls, name) -> dict:
    """Count the calls of ``cls.name`` (still calling through)."""
    calls = {"n": 0}
    real = getattr(cls, name)

    def spy(self, *a, **k):
        calls["n"] += 1
        return real(self, *a, **k)

    monkeypatch.setattr(cls, name, spy)
    return calls


def test_topk_counts_batches_and_device_round_trips(ranked_index, monkeypatch):
    """One ``topk_batch`` adds one batch and one round trip per fetch."""
    from repro.ranked.topk_engine import TopKEngine

    idx, queries = ranked_index
    eng = TopKEngine(idx, backend="ref", seed_blocks=2, resident="kernel")
    eng.topk_batch(queries, 10)  # warm: jit traces, score cache
    fetches = _spy(monkeypatch, TopKEngine, "_fetch")
    before = dict(eng.stats)
    eng.topk_batch(queries, 10)
    assert eng.stats["batches"] - before["batches"] == 1
    trips = eng.stats["device_round_trips"] - before["device_round_trips"]
    assert trips == fetches["n"] >= 1
    # the host-only backend never waits on a device
    eng_np = TopKEngine(idx, backend="numpy", seed_blocks=2)
    eng_np.topk_batch(queries, 10)
    assert eng_np.stats["batches"] == 1
    assert eng_np.stats["device_round_trips"] == 0


def test_intersect_counts_batches_and_device_round_trips(ranked_index,
                                                         monkeypatch):
    """One ``intersect_batch`` adds one batch and one round trip per
    device dispatch of the fused pipeline."""
    from repro.core.engine_core import EngineCore
    from repro.core.query_engine import QueryEngine

    idx, queries = ranked_index
    eng = QueryEngine(idx, backend="ref")
    eng.intersect_batch(queries)
    dispatches = _spy(monkeypatch, EngineCore, "_dispatch_jax")
    before = dict(eng.stats)
    eng.intersect_batch(queries)
    assert eng.stats["batches"] - before["batches"] == 1
    trips = eng.stats["device_round_trips"] - before["device_round_trips"]
    assert trips == dispatches["n"] >= 1
    eng_np = QueryEngine(idx, backend="numpy")
    eng_np.intersect_batch(queries)
    assert eng_np.stats["batches"] == 1
    assert eng_np.stats["device_round_trips"] == 0


@pytest.mark.parametrize("resident", ["mirror", "kernel"])
def test_topk_counts_membership_pairs(ranked_index, monkeypatch, resident):
    """``stats["membership_pairs"]`` adds T x D per query of every
    membership pass (seed and rescore), mirrored to the armed registry."""
    from repro.ranked.topk_engine import TopKEngine

    idx, queries = ranked_index
    eng = TopKEngine(idx, backend="numpy", seed_blocks=2, resident=resident)
    passes = []
    real = TopKEngine._score_specs

    def spy(self, specs, *a, **k):
        passes.append(sum(len(t) * len(d) for t, _, d in specs))
        return real(self, specs, *a, **k)

    monkeypatch.setattr(TopKEngine, "_score_specs", spy)
    eng.topk_batch(queries, 10)
    assert len(passes) >= 2  # the seed pass and at least one rescore
    assert eng.stats["membership_pairs"] == sum(passes) > 0
    counter = obs.counter("ranked_membership_pairs", engine="topk")
    assert counter.value == sum(passes)
