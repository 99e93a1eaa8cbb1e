"""GIN (Graph Isomorphism Network, arXiv:1810.00826) in pure JAX.

Message passing is `jax.ops.segment_sum` over an edge-index -> node scatter
(JAX has no CSR SpMM; this IS the system, per the assignment notes).  The
`eps` parameters are learnable (GIN-eps).

Supported input regimes (all padded/masked to static shapes):
  * full-batch node classification (cora-like / ogbn-products-like),
  * sampled-subgraph mini-batch training (neighbor sampler in
    ``repro.data.graph_data``),
  * batched small graphs with segment-sum readout (molecule).

Normalization: the original model uses BatchNorm; we use LayerNorm to stay
functional/stateless (noted in DESIGN.md as an adaptation).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import dense_init, layer_norm


@dataclasses.dataclass(frozen=True)
class GINConfig:
    name: str = "gin"
    n_layers: int = 5
    d_in: int = 1433
    d_hidden: int = 64
    n_classes: int = 7
    graph_readout: bool = False  # True => graph classification (molecule)
    message_dtype: str = "float32"  # "bfloat16" halves the all_gather wire
    # bytes in the dst-sharded path (accumulation stays f32)


def init_params(key, cfg: GINConfig):
    ks = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    d_prev = cfg.d_in
    for l in range(cfg.n_layers):
        k1, k2 = jax.random.split(ks[l])
        layers.append(
            {
                "eps": jnp.zeros((), jnp.float32),
                "w1": dense_init(k1, (d_prev, cfg.d_hidden)),
                "b1": jnp.zeros((cfg.d_hidden,)),
                "w2": dense_init(k2, (cfg.d_hidden, cfg.d_hidden)),
                "b2": jnp.zeros((cfg.d_hidden,)),
                "ln_scale": jnp.ones((cfg.d_hidden,)),
                "ln_bias": jnp.zeros((cfg.d_hidden,)),
            }
        )
        d_prev = cfg.d_hidden
    head = dense_init(ks[-1], (cfg.d_hidden, cfg.n_classes))
    return {"layers": layers, "head": head, "head_b": jnp.zeros((cfg.n_classes,))}


def param_specs(cfg: GINConfig, model_axis: str = "model"):
    """GIN is tiny -> replicate everything."""
    return jax.tree_util.tree_map(lambda _: P(), init_params_shape_tree(cfg))


def init_params_shape_tree(cfg: GINConfig):
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))


def forward(params, feats, edges, edge_mask, cfg: GINConfig, graph_ids=None, n_graphs=0):
    """feats: [N, d_in]; edges: [2, E] (src, dst); edge_mask: [E] bool.

    Padded edges point at node 0 but are masked out of the aggregation.
    """
    n = feats.shape[0]
    h = feats
    src, dst = edges[0], edges[1]
    for lp in params["layers"]:
        msg = h[src] * edge_mask[:, None].astype(h.dtype)
        agg = jax.ops.segment_sum(msg, dst, num_segments=n)
        z = (1.0 + lp["eps"]) * h + agg
        z = jax.nn.relu(z @ lp["w1"] + lp["b1"])
        z = z @ lp["w2"] + lp["b2"]
        h = layer_norm(z, lp["ln_scale"], lp["ln_bias"])
    if cfg.graph_readout:
        assert graph_ids is not None
        g = jax.ops.segment_sum(h, graph_ids, num_segments=n_graphs)
        return g @ params["head"] + params["head_b"]
    return h @ params["head"] + params["head_b"]


def loss_fn(params, batch, cfg: GINConfig):
    """batch: feats, edges, edge_mask, labels, label_mask (+ graph_ids)."""
    if cfg.graph_readout:
        logits = forward(
            params,
            batch["feats"],
            batch["edges"],
            batch["edge_mask"],
            cfg,
            graph_ids=batch["graph_ids"],
            n_graphs=batch["labels"].shape[0],
        )
        labels = batch["labels"]
        mask = jnp.ones(labels.shape[0], jnp.float32)
    else:
        logits = forward(params, batch["feats"], batch["edges"], batch["edge_mask"], cfg)
        labels = batch["labels"]
        mask = batch["label_mask"].astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ==========================================================================
# dst-aligned sharded message passing (EXPERIMENTS.md Perf, gin-tu hillclimb)
# ==========================================================================
#
# Baseline full-batch GIN replicated the node features and all-reduced the
# [N, d] partial aggregations per layer (collective-bound, 256x redundant
# MLP compute).  This path shards nodes AND edges over every mesh axis:
#
#   * the pipeline delivers edges grouped by destination shard (CSR is
#     dst-sorted, so this is a layout contract, not extra work): shard s
#     holds only edges whose dst lies in [s*N/S, (s+1)*N/S), padded + masked;
#   * inside one shard_map over the whole forward: per layer, all_gather the
#     [N/S, d] node block (the ONLY collective), gather sources locally,
#     segment_sum into the LOCAL dst range (no all-reduce), run the MLP on
#     the local node block (no redundant compute);
#   * the loss is a local masked CE + psum.

def _all_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def forward_dst_sharded(params, feats_loc, edges_loc, edge_mask_loc, cfg: GINConfig,
                        axes: tuple, n_shards: int):
    """Body run per shard: feats_loc [N/S, d]; edges_loc [2, E/S] (dst local)."""
    n_loc = feats_loc.shape[0]
    shard = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        shard = shard * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    dst_off = shard * n_loc
    h_loc = feats_loc
    src, dst = edges_loc[0], edges_loc[1]
    mdt = jnp.bfloat16 if cfg.message_dtype == "bfloat16" else jnp.float32
    for lp in params["layers"]:
        # the ONLY collective: gather node blocks in message_dtype (bf16
        # halves the wire); segment accumulation stays f32
        h_full = jax.lax.all_gather(h_loc.astype(mdt), axes, tiled=True)
        msg = h_full[src].astype(jnp.float32) * edge_mask_loc[:, None]
        agg = jax.ops.segment_sum(msg, dst - dst_off, num_segments=n_loc)
        z = (1.0 + lp["eps"]) * h_loc + agg
        z = jax.nn.relu(z @ lp["w1"] + lp["b1"])
        z = z @ lp["w2"] + lp["b2"]
        h_loc = layer_norm(z, lp["ln_scale"], lp["ln_bias"])
    return h_loc @ params["head"] + params["head_b"]


def loss_fn_dst_sharded(params, batch, cfg: GINConfig, mesh=None):
    """batch: feats [N,d], edges [2,E] dst-grouped, edge_mask, labels,
    label_mask -- all sharded over every mesh axis (see batch_specs_sharded)."""
    mesh = mesh or jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return loss_fn(params, batch, cfg)
    axes = _all_axes(mesh)
    S = 1
    for a in axes:
        S *= mesh.shape[a]

    def body(feats, edges, emask, labels, lmask, params):
        logits = forward_dst_sharded(params, feats, edges, emask, cfg, axes, S)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        m = lmask.astype(jnp.float32)
        num = jax.lax.psum((nll * m).sum(), axes)
        den = jax.lax.psum(m.sum(), axes)
        return num / jnp.maximum(den, 1.0)

    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, axes), P(axes), P(axes), P(axes), pspec),
        out_specs=P(),
        check_vma=False,
    )(batch["feats"], batch["edges"], batch["edge_mask"], batch["labels"],
      batch["label_mask"], params)


def batch_specs_sharded(cfg: GINConfig, axes=("pod", "data", "model")):
    return {
        "feats": P(axes, None),
        "edges": P(None, axes),
        "edge_mask": P(axes),
        "labels": P(axes),
        "label_mask": P(axes),
    }


def group_edges_by_dst_shard(edges: "np.ndarray", n_nodes: int, n_shards: int):
    """Host-side layout pass: group (+pad) edges so slice s holds only edges
    with dst in shard s's node range.  Returns (edges [2, S*E_loc], mask)."""
    import numpy as np

    n_loc = n_nodes // n_shards
    owner = np.minimum(edges[1] // n_loc, n_shards - 1)
    counts = np.bincount(owner, minlength=n_shards)
    e_loc = int(counts.max()) if counts.size else 1
    out = np.zeros((2, n_shards * e_loc), edges.dtype)
    mask = np.zeros(n_shards * e_loc, bool)
    for s in range(n_shards):
        sel = np.flatnonzero(owner == s)
        out[:, s * e_loc : s * e_loc + sel.size] = edges[:, sel]
        # padding edges self-loop into the local range so indices stay local
        out[1, s * e_loc + sel.size : (s + 1) * e_loc] = s * n_loc
        mask[s * e_loc : s * e_loc + sel.size] = True
    return out, mask, e_loc


def input_specs(cfg: GINConfig, n_nodes: int, n_edges: int, n_graphs: int = 0):
    """ShapeDtypeStructs for the dry-run (shapes pre-padded by caller)."""
    spec = {
        "feats": jax.ShapeDtypeStruct((n_nodes, cfg.d_in), jnp.float32),
        "edges": jax.ShapeDtypeStruct((2, n_edges), jnp.int32),
        "edge_mask": jax.ShapeDtypeStruct((n_edges,), jnp.bool_),
    }
    if cfg.graph_readout:
        spec["graph_ids"] = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
        spec["labels"] = jax.ShapeDtypeStruct((n_graphs,), jnp.int32)
    else:
        spec["labels"] = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
        spec["label_mask"] = jax.ShapeDtypeStruct((n_nodes,), jnp.bool_)
    return spec


def batch_specs(cfg: GINConfig, data_axes=("pod", "data")):
    """PartitionSpecs: edges sharded over data axes, nodes replicated."""
    d = data_axes
    spec = {
        "feats": P(),
        "edges": P(None, d),
        "edge_mask": P(d),
    }
    if cfg.graph_readout:
        spec["graph_ids"] = P()
        spec["labels"] = P()
    else:
        spec["labels"] = P()
        spec["label_mask"] = P()
    return spec
