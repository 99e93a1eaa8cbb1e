"""The reduction from trace events to busy, idle, kernel and gap times."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402
from harness.trace import (  # noqa: E402
    NO_SPAN, WINDOW, Interval, base_name, merge, op_name, reduce)

I = Interval


def fixture():
    host = [
        I(10.0, 20.0, WINDOW),
        I(11.0, 13.0, "bench.topk_batch"),
        I(13.0, 13.5, "bench.wave_form"),
        I(15.0, 19.0, "bench.topk_batch"),
        I(1.0, 2.0, "bench.warmup"),  # outside the window
    ]
    dev = [
        I(9.0, 10.5, "decode_search_blocks"),       # straddles the window's start
        I(11.5, 12.0, "pivot_select_blocks"),
        I(11.8, 12.2, "fusion.3"),            # overlaps the kernel
        I(16.0, 17.0, "bm25_score_blocks.1"),
        I(19.5, 21.0, "bm25_score_probe_blocks"),  # straddles its end
    ]
    return [dev], host


def test_busy_union_and_idle_share():
    s = reduce(*fixture())
    assert s.window_s == pytest.approx(10.0)
    # 10-10.5, 11.5-12.2, 16-17, 19.5-20
    assert s.busy_s == pytest.approx(0.5 + 0.7 + 1.0 + 0.5)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.73)


def test_kernel_time_by_name():
    s = reduce(*fixture())
    assert s.kernel_s(["pivot_select_blocks"]) == pytest.approx(0.5)
    assert s.kernel_s(["bm25_score_blocks", "bm25_score_probe_blocks"]) == pytest.approx(1.5)
    assert s.kernel_s(["decode_search_blocks"]) == pytest.approx(0.5)  # clipped
    assert s.kernel_s(["ef_search_blocks"]) == 0.0
    assert base_name("bm25_score_blocks.12") == "bm25_score_blocks"
    assert base_name("fusion_3.1") == "fusion"
    assert op_name("%decode_search_blocks.1 = s32[8,128]{1,0} custom-call(%a)") == (
        "decode_search_blocks.1")
    assert s.top_ops(2)[0][0] == "bm25_score_blocks.1"


def test_idle_gaps_named_by_host_span():
    s = reduce(*fixture())
    # gaps: 10.5-11.5 (mid 11.0: topk_batch starts at 11.0), 12.2-16
    # (mid 14.1: no span), 17-19.5 (mid 18.25: topk_batch)
    assert s.gap_s["bench.topk_batch"] == pytest.approx(1.0 + 2.5)
    assert s.gap_s[NO_SPAN] == pytest.approx(3.8)
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.top_gaps(1)[0][0] == NO_SPAN


def test_merge_and_missing_window():
    assert merge([I(0, 1, "a"), I(0.5, 2, "b"), I(3, 4, "c")], 0.5, 3.5) == [
        (0.5, 2), (3, 3.5)]
    with pytest.raises(ValueError):
        reduce([[]], [I(0, 1, "bench.topk_batch")])


def test_readers_on_the_fixture():
    from harness.cell import Run

    run = Run("topk", n_queries=4, stats={"candidates": 100})
    run.trace = reduce(*fixture())
    read = lambda name: spec.load_reader(ROOT, name)(run)  # noqa: E731
    assert read("device.idle_share.serve") == pytest.approx(73.0)
    assert read("device.idle_share.and") is None
    assert read("kernel.pivot_select.share.topk") == pytest.approx(5.0)
    assert read("kernel.bm25_score.share.topk") == pytest.approx(15.0)
    assert read("ranked.candidates_per_query") == pytest.approx(25.0)
    assert read("ranked.score_cache_hit_ratio") is None  # nothing counted
    run.counters = {"score_cache_hit": 3, "score_cache_miss": 1}
    assert read("ranked.score_cache_hit_ratio") == pytest.approx(75.0)
    run.operation = "and"
    (dev,), host = fixture()
    run.trace = reduce([dev + [I(14.0, 14.5, "ef_search_blocks.2")]], host)
    assert read("kernel.vbyte_decode.share.and") == pytest.approx(5.0)
    assert read("kernel.ef_search.share.and") == pytest.approx(5.0)
    assert read("kernel.pivot_select.share.topk") is None
    assert read("device.idle_share.serve") is None
    run.trace = reduce([[]], fixture()[1])  # no kernel ran: nothing to read
    assert read("kernel.vbyte_decode.share.and") is None
    assert read("kernel.ef_search.share.and") is None
