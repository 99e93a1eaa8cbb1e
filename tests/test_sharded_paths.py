"""Numerical equivalence of the explicit-collective (shard_map) paths vs
their pjit/single-device references.  These are the §Perf optimizations --
each must be a pure performance change (subprocess: device count is
process-global)."""

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest


def _run(script: str) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json, dataclasses
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, AxisType
"""


@pytest.mark.slow
@pytest.mark.parametrize("case", ["ep", "tp"])
def test_shard_map_moe_equals_pjit(case):
    E, data, model = (4, 4, 2) if case == "ep" else (2, 2, 4)
    script = _PRELUDE + textwrap.dedent(f"""
        from repro.models import transformer as T
        data, model, E = {data}, {model}, {E}
        mesh = jax.make_mesh((data, model), ("data","model"),
                             axis_types=(AxisType.Auto,)*2)
        # the EP shard_map path shards tokens over `model` too, so the pjit
        # reference must use one capacity group per (data x model) shard;
        # the TP-in-expert path groups per data shard only
        groups = data * model if E % model == 0 else data
        cfg0 = T.TransformerConfig(n_layers=2, d_model=32, n_heads=4,
                                   n_kv_heads=2, d_head=8, d_ff=64, vocab=96,
                                   n_experts=E, top_k=2, attn_chunk=10**6,
                                   loss_chunk=10**6, compute_dtype=jnp.float32,
                                   moe_groups=groups)
        cfg_sm = dataclasses.replace(cfg0, moe_shard_map=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg0)
        tok = jax.random.randint(jax.random.PRNGKey(1), (data*2, 16), 0, 96)
        pspecs = T.param_specs(cfg0, tp=model)
        h_ref, _ = T.forward(params, tok, cfg0)
        with jax.set_mesh(mesh):
            h_sm, _ = jax.jit(lambda p: T.forward(p, tok, cfg_sm),
                              in_shardings=(pspecs,))(params)
        print(json.dumps({{"dh": float(jnp.max(jnp.abs(h_ref - h_sm)))}}))
    """)
    res = _run(script)
    assert res["dh"] < 1e-4, res


@pytest.mark.slow
def test_dst_sharded_gin_equals_plain():
    script = _PRELUDE + textwrap.dedent("""
        from repro.models import gnn as G
        rng = np.random.default_rng(0)
        N, E, S = 64, 300, 8
        cfg = G.GINConfig(n_layers=3, d_in=12, d_hidden=16, n_classes=5)
        params = G.init_params(jax.random.PRNGKey(0), cfg)
        edges = rng.integers(0, N, (2, E)).astype(np.int32)
        batch_ref = {"feats": jnp.asarray(rng.normal(size=(N,12)), jnp.float32),
                     "edges": jnp.asarray(edges), "edge_mask": jnp.ones(E, bool),
                     "labels": jnp.asarray(rng.integers(0,5,N), jnp.int32),
                     "label_mask": jnp.asarray(rng.random(N) < 0.5)}
        ge, gmask, _ = G.group_edges_by_dst_shard(edges, N, S)
        batch_sh = dict(batch_ref, edges=jnp.asarray(ge), edge_mask=jnp.asarray(gmask))
        mesh = jax.make_mesh((4, 2), ("data","model"), axis_types=(AxisType.Auto,)*2)
        l_ref, g_ref = jax.value_and_grad(lambda p: G.loss_fn(p, batch_ref, cfg))(params)
        with jax.set_mesh(mesh):
            l_sh, g_sh = jax.jit(jax.value_and_grad(
                lambda p: G.loss_fn_dst_sharded(p, batch_sh, cfg)))(params)
        dmax = max(float(jnp.max(jnp.abs(a-b))) for a,b in
                   zip(jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_sh)))
        print(json.dumps({"dl": abs(float(l_ref)-float(l_sh)), "dg": dmax}))
    """)
    res = _run(script)
    assert res["dl"] < 1e-5 and res["dg"] < 1e-4, res


@pytest.mark.slow
def test_routed_butterfly_equals_dense():
    script = _PRELUDE + textwrap.dedent("""
        from repro.launch.cells import routed_table_gather, routed_table_update
        mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,)*2)
        rng = np.random.default_rng(0)
        R, d, n = 1024, 16, 256
        table = jnp.asarray(rng.normal(size=(R, d)), jnp.float32)
        ids = jnp.asarray(rng.integers(0, R, n), jnp.int32)
        g = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        with jax.set_mesh(mesh):
            emb = routed_table_gather(table, ids, mesh, ("model","data"), ("data","model"))
            t2, a2, dropped = routed_table_update(table, jnp.zeros(R), ids, g, 0.1,
                                                  mesh, ("model","data"), ("data","model"))
        emb_ref = jnp.take(table, ids, axis=0)
        acc_ref = jnp.zeros(R).at[ids].add(jnp.sum(g*g, -1))
        t_ref = table.at[ids].add(-(0.1/jnp.sqrt(acc_ref[ids]+1e-8))[:,None]*g)
        print(json.dumps({
            "de": float(jnp.max(jnp.abs(emb - emb_ref))),
            "dt": float(jnp.max(jnp.abs(t2 - t_ref))),
            "da": float(jnp.max(jnp.abs(a2 - acc_ref))),
            "dropped": int(dropped)}))
    """)
    res = _run(script)
    assert res["de"] < 1e-6 and res["dt"] < 1e-5 and res["da"] < 1e-5, res
    assert res["dropped"] == 0, res
