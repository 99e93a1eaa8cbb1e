"""Open-loop schedule and the due-time latency and lateness arithmetic."""

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell, traffic  # noqa: E402


def test_poisson_schedule_same_work_every_seed():
    a = traffic.poisson_due(np.random.default_rng(1), 50.0, 1000)
    b = traffic.poisson_due(np.random.default_rng(2), 50.0, 1000)
    for d in (a, b):
        assert len(d) == 1000 and d[0] >= 0 and np.all(np.diff(d) > 0)
    # the same gaps in another order: the schedules end together
    assert a[-1] == pytest.approx(b[-1])
    assert a[-1] == pytest.approx(20.0, rel=0.02)
    assert not np.allclose(a, b)
    gaps = np.diff(a)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.1)  # exponential


def test_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert cell.nearest_rank(v, 0.95) == 95.0
    assert cell.nearest_rank(v, 0.5) == 50.0
    assert cell.nearest_rank([3.0], 0.95) == 3.0
    assert cell.nearest_rank(np.r_[np.ones(19), 1e9], 0.95) == 1.0
    assert cell.nearest_rank(np.r_[np.ones(18), 1e9, 1e9], 0.95) == 1e9


class SlowEngine:
    """Answers after a fixed time, blocking the loop as a wave does."""

    WAVE_S = 0.05

    def __init__(self):
        self.waves = []

    def topk_batch(self, queries, k):
        self.waves.append(len(queries))
        time.sleep(self.WAVE_S)
        return [(np.array(q, np.int64), np.ones(len(q))) for q in queries]


def test_latency_is_timed_from_due_and_lag_from_admission():
    eng = SlowEngine()
    s = SimpleNamespace(engine=eng, cell=SimpleNamespace(traffic={
        "k": 10, "batch": 8, "linger_ms": 1.0}))
    due = np.array([0.0, 0.01, 0.02, 0.03, 0.2, 0.21])
    queries = [[i] for i in range(len(due))]
    admit, answer, wait_s, results, t_close = cell.open_loop(s, queries, due, 0.25)
    assert np.isfinite(answer).all()
    assert [r.docs.tolist() for r in results] == queries
    assert np.all(admit >= due - 1e-3)
    assert np.all(answer >= admit)
    # request 0 starts a wave at ~1 ms; 1-3 fall due inside it and are
    # admitted only when it ends: late by the rest of the wave
    lag = admit - due
    assert lag[0] < 0.01
    for i in (1, 2, 3):
        assert lag[i] == pytest.approx(0.051 - due[i], abs=0.02)
        assert answer[i] - due[i] >= 2 * eng.WAVE_S - due[i] - 0.005
    assert np.all(answer - due >= eng.WAVE_S - 1e-3)
    assert np.all(wait_s >= 0) and np.all(wait_s < 0.03)  # the server's own
    assert t_close >= answer.max()
    assert eng.waves[0] == 1 and sum(eng.waves) >= 6


def test_overload_sheds_nothing_and_rates_the_window():
    """Far more requests than the server's default queue, all due at once:
    every one is answered after the backlog drains, and the rate counts only
    the answers completed inside the window."""
    eng = SlowEngine()
    eng.WAVE_S = 0.01
    s = SimpleNamespace(engine=eng, cell=SimpleNamespace(traffic={
        "k": 10, "batch": 64, "linger_ms": 1.0}))
    n = 1500
    due = np.linspace(0.0, 0.05, n)
    admit, answer, _, results, t_close = cell.open_loop(
        s, [[i] for i in range(n)], due, 0.1)
    assert np.isfinite(answer).all() and all(r is not None for r in results)
    assert t_close > 0.2  # 24 waves of 10 ms drain after the window
    inside = int((answer <= 0.1).sum())
    assert 0 < inside < n
    assert cell.completed_rate(answer, 0.1) == pytest.approx(inside / 0.1)
    assert cell.completed_rate(np.array([0.05, np.nan, 0.3]), 0.1) == 10.0
