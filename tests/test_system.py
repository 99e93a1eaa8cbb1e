"""End-to-end behaviour tests for the paper's system.

1. The full index lifecycle: clustered corpus -> optimal partitioning ->
   2x-smaller index -> correct AND queries (the paper's end-to-end claim).
2. A short LM training run through the production control flow
   (data pipeline + jit step + checkpoint/restart) reduces the loss.
3. Sharded-vs-unsharded numerical equivalence runs in a subprocess with 8
   placeholder devices (device count is process-global).
"""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def test_index_lifecycle_end_to_end():
    from repro.core import build_partitioned_index, build_unpartitioned_index
    from repro.data.postings import make_corpus, make_queries

    rng = np.random.default_rng(11)
    corpus = make_corpus(rng, n_lists=10, min_len=2_000, max_len=20_000,
                         mean_dense_gap=2.13, frac_dense=0.8)
    idx = build_partitioned_index(corpus, "optimal")
    base = build_unpartitioned_index(corpus)
    assert base.bits_per_int() / idx.bits_per_int() >= 1.8  # the 2x claim
    for q in make_queries(rng, len(corpus), 10, 2):
        got = idx.intersect([int(t) for t in q])
        want = np.intersect1d(corpus[q[0]], corpus[q[1]])
        assert np.array_equal(got, want)


def test_lm_training_reduces_loss(tmp_path):
    from repro.launch.train import build_training
    from repro.checkpoint import CheckpointManager
    from repro.distributed import FaultTolerantRunner, SimulatedFailure

    state, step, batches, cfg = build_training(
        "qwen1.5-0.5b", smoke=True, batch=8, seq_len=64
    )
    mgr = CheckpointManager(tmp_path, async_save=False)
    runner = FaultTolerantRunner(step, mgr, save_every=10)
    losses = []

    def wrapped(state, b):
        s, m = step(state, b)
        losses.append(float(m["loss"]))
        return s, m

    runner.step_fn = wrapped
    runner.run(state, batches, 30, failure=SimulatedFailure(at_steps=(12,)))
    assert runner.stats.restarts == 1
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first, (first, last)


@pytest.mark.slow
def test_sharded_equals_unsharded_subprocess():
    """DP x TP pjit step == single-device step, bit-for-bit-ish (f32)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, "src")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, AxisType
        from repro.models import transformer as T
        from repro.launch.cells import make_train_step
        from repro.optim import adamw_init

        cfg = T.TransformerConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                                  d_head=8, d_ff=64, vocab=128, attn_chunk=10**6,
                                  loss_chunk=10**6, compute_dtype=jnp.float32)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 128),
        }
        def loss(p, b, c):
            return T.lm_loss(p, b["tokens"], b["labels"], c)
        step = make_train_step(loss, cfg)
        opt = adamw_init(params)
        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, batch)
        # sharded (data=4, model=2)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        pspecs = T.param_specs(cfg, tp=2)
        ospecs = {"m": pspecs, "v": pspecs, "count": P()}
        bspec = {"tokens": P("data", None), "labels": P("data", None)}
        with jax.set_mesh(mesh):
            p2, o2, m2 = jax.jit(
                step, in_shardings=(pspecs, ospecs, bspec),
                out_shardings=(pspecs, ospecs, {"loss": P(), "grad_norm": P()}),
            )(params, opt, batch)
        d = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2))
        )
        print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                          "max_param_diff": d}))
    """)
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss1"] - res["loss2"]) < 1e-4, res
    assert res["max_param_diff"] < 1e-4, res
