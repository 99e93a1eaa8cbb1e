"""The device path at a real key space, and no fallback that hides it.

* An index whose ``n_lists * stride`` passes 2^31 -- any real index does --
  is served by the device pipelines (``ref`` and Pallas interpret mode
  here) with answers identical to the scalar oracles; the device locate
  agrees with the host's int64 searchsorted cursor by cursor.
* Narrowing to the device's int32 raises instead of wrapping.
* A JAX that fails to initialise raises out of the backend policy, and
  ``chip_smoke.py`` refuses to run anywhere but on a TPU.
* The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, or at
  ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.api import EngineConfig, make_query_engine, make_topk_engine
from repro.core import build_partitioned_index
from repro.core.arena import MAX_DOCID, to_i32
from repro.core.engine_core import EngineCore, build_locate_dev, stage_cursors
from repro.ranked.bm25 import exhaustive_topk

ROOT = Path(__file__).resolve().parent.parent
N_LISTS = 640
UNIVERSE = 4_000_000  # stride ~4M x 640 lists ~ 2.6e9 > 2^31


@pytest.fixture(scope="module")
def wide_index():
    rng = np.random.default_rng(11)
    sizes = rng.integers(60, 400, N_LISTS)
    sizes[-4:] = 3_000  # a few lists span several blocks and partitions
    # lists share a pool of hot documents, so intersections are not empty
    pool = np.sort(rng.choice(UNIVERSE, 4_000, replace=False))
    lists = [np.sort(rng.choice(pool, int(n), replace=False)) for n in sizes]
    freqs = [rng.integers(1, 30, len(seq)) for seq in lists]
    idx = build_partitioned_index(lists, "optimal", freqs=freqs, codecs="auto")
    assert idx.n_lists * idx.arena.stride >= 2**31
    queries = [
        [int(t) for t in rng.choice(N_LISTS, ar, replace=False)]
        for ar in (2, 2, 3)
        for _ in range(4)
    ]
    # lists whose global keys pass 2^31, several blocks each
    queries += [[N_LISTS - 1, N_LISTS - 2], [N_LISTS - 3, N_LISTS - 4, 5]]
    return idx, lists, queries


def test_device_locate_matches_host_keys(wide_index):
    import jax.numpy as jnp

    idx, _, _ = wide_index
    a = idx.arena
    rng = np.random.default_rng(5)
    terms = rng.integers(0, N_LISTS, 4_096)
    probes = rng.integers(0, a.stride + 10, 4_096)
    probes[:8] = [0, a.stride - 1, a.stride, 2**31 - 1, 1, 2, 3, 4]
    tp, pp = stage_cursors(terms, probes, a.stride, len(terms))
    rows, pe, past = build_locate_dev(a)(
        vars(a.dev), jnp.asarray(tp), jnp.asarray(pp)
    )
    pc = np.clip(probes, 0, a.stride - 1)
    k = np.searchsorted(a.block_keys, pc + terms * a.stride, side="left")
    want_past = k >= a.list_blk_offsets[terms + 1]
    np.testing.assert_array_equal(np.asarray(past), want_past)
    np.testing.assert_array_equal(
        np.asarray(rows)[~want_past], k[~want_past]
    )
    np.testing.assert_array_equal(np.asarray(pe), np.where(want_past, 0, pc))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_and_past_2_31_keys_matches_scalar(wide_index, backend, monkeypatch):
    idx, lists, queries = wide_index
    engine = make_query_engine(idx, EngineConfig(backend=backend))
    assert engine.core.use_device

    def no_host(*a, **k):
        raise AssertionError("the host pipeline served a device backend")

    monkeypatch.setattr(EngineCore, "search_np", no_host)
    got = engine.intersect_batch(queries)
    rng = np.random.default_rng(3)
    terms = rng.integers(0, N_LISTS, 2_000)
    probes = rng.integers(0, UNIVERSE, 2_000)
    value, _ = engine.search_batch(terms, probes)
    monkeypatch.undo()
    assert sum(len(g) for g in got) > 0
    for q, g in zip(queries, got):
        np.testing.assert_array_equal(g, idx.intersect_scalar(q))
    for t, p, v in zip(terms, probes, value):
        k = np.searchsorted(lists[t], p)
        assert v == (lists[t][k] if k < len(lists[t]) else -1)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_topk_past_2_31_keys_matches_exhaustive(wide_index, backend):
    idx, lists, queries = wide_index
    engine = make_topk_engine(
        idx, EngineConfig(backend=backend, resident="kernel")
    )
    got = engine.topk_batch(queries, 10)
    for (gd, gs), (wd, ws) in zip(got, exhaustive_topk(idx, queries, 10)):
        np.testing.assert_array_equal(gd, wd)
        np.testing.assert_array_equal(gs, ws)
    # point lookups through the device locate, on the lists past 2^31
    tl = np.arange(N_LISTS - 8, N_LISTS)
    terms = np.repeat(tl, 50)
    docs = np.concatenate([lists[t][-50:] for t in tl])
    assert (engine.contributions(terms, docs) > 0).all()


def test_device_narrowing_raises_instead_of_wrapping():
    np.testing.assert_array_equal(to_i32(np.array([-5, 2**31 - 1]), "x"),
                                  [-5, 2**31 - 1])
    with pytest.raises(OverflowError, match="block_last"):
        to_i32(np.array([0, 2**31]), "block_last")


def test_arena_refuses_docids_past_int32():
    idx = build_partitioned_index([np.array([0, 7, MAX_DOCID + 1])], "single")
    with pytest.raises(ValueError, match="int32"):
        idx.arena


def test_default_backend_raises_when_jax_cannot_start(monkeypatch):
    import jax

    from repro.kernels.vbyte_decode import ops

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize"):
        ops.default_backend()
    with pytest.raises(RuntimeError, match="initialize"):
        ops.default_interpret()


def _smoke(cwd: Path, script: Path, tmp_path: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("REPRO_BACKEND", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_the_cpu(tmp_path):
    r = _smoke(ROOT, ROOT / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone)
    r = _smoke(alone, alone / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        got = enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
