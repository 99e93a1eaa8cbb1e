"""Decoder-only transformer LM: dense and MoE, GQA, RoPE, SwiGLU, qk-norm,
QKV-bias, sliding-window attention, chunked (flash-style) attention,
scan-over-layers with remat.  Pure functional JAX; params are pytrees.

Supports the 5 assigned LM architectures (command-r-35b, qwen1.5-0.5b,
qwen3-0.6b, moonshot-v1-16b-a3b, mixtral-8x22b) through `TransformerConfig`.

Three entry points (all jit/pjit friendly):
  * ``train_step(params, opt_state, batch, cfg)``  -- loss + AdamW update
  * ``prefill_step(params, tokens, cfg)``          -- logits for a prompt +
                                                      freshly-built KV cache
  * ``serve_step(params, cache, token, cfg)``      -- one decode step
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax.sharding import get_abstract_mesh

from .common import dense_init, rms_norm, split_keys


def maybe_shard(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """with_sharding_constraint that degrades gracefully outside a mesh.

    Axis names absent from the ambient mesh are dropped from the spec, so the
    same model code runs under the single-pod mesh (no "pod" axis), the
    multi-pod mesh, and un-meshed CPU smoke tests.
    """
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in names)
            return kept if kept else None
        return entry if entry in names else None

    new_spec = P(*(keep(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, new_spec)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str = "lm"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 32
    d_ff: int = 512
    vocab: int = 1024
    qkv_bias: bool = False
    qk_norm: bool = False
    # MoE (n_experts == 0 -> dense FFN)
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_groups: int = 1  # GShard-style groups; set to the data-shard count so
    # dispatch positions (and the capacity buffer) are local per shard
    moe_shard_map: bool = False  # explicit-collective MoE (see moe_ffn_shard_map)
    # attention
    sliding_window: int = 0  # 0 => full causal attention
    rope_theta: float = 10_000.0
    attn_chunk: int = 1024  # flash-style chunking threshold / block
    loss_chunk: int = 512  # sequence chunking for the CE loss
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.d_head

    def param_count(self) -> int:
        c = self
        attn = c.d_model * (c.q_dim + 2 * c.kv_dim) + c.q_dim * c.d_model
        if c.qkv_bias:
            attn += c.q_dim + 2 * c.kv_dim
        if c.qk_norm:
            attn += 2 * c.d_head
        if c.is_moe:
            ffn = c.n_experts * 3 * c.d_model * c.d_ff + c.d_model * c.n_experts
        else:
            ffn = 3 * c.d_model * c.d_ff
        per_layer = attn + ffn + 2 * c.d_model
        return c.n_layers * per_layer + 2 * c.vocab * c.d_model + c.d_model

    def active_param_count(self) -> int:
        """Activated params per token (MoE counts top_k experts only)."""
        if not self.is_moe:
            return self.param_count()
        c = self
        attn = c.d_model * (c.q_dim + 2 * c.kv_dim) + c.q_dim * c.d_model
        ffn = c.top_k * 3 * c.d_model * c.d_ff + c.d_model * c.n_experts
        per_layer = attn + ffn + 2 * c.d_model
        return c.n_layers * per_layer + 2 * c.vocab * c.d_model + c.d_model


# ==========================================================================
# Parameter init (stacked [L, ...] leaves for scan-over-layers)
# ==========================================================================

def init_params(key, cfg: TransformerConfig):
    L, d, q, kv, ff, V = (
        cfg.n_layers,
        cfg.d_model,
        cfg.q_dim,
        cfg.kv_dim,
        cfg.d_ff,
        cfg.vocab,
    )
    ks = split_keys(key, ["embed", "head", "wq", "wk", "wv", "wo", "ffn1", "ffn2", "ffn3", "router"])
    pd = cfg.param_dtype
    layers: dict[str, Any] = {
        "wq": dense_init(ks["wq"], (L, d, q), dtype=pd),
        "wk": dense_init(ks["wk"], (L, d, kv), dtype=pd),
        "wv": dense_init(ks["wv"], (L, d, kv), dtype=pd),
        "wo": dense_init(ks["wo"], (L, q, d), dtype=pd),
        "ln1": jnp.ones((L, d), pd),
        "ln2": jnp.ones((L, d), pd),
    }
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, q), pd)
        layers["bk"] = jnp.zeros((L, kv), pd)
        layers["bv"] = jnp.zeros((L, kv), pd)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, cfg.d_head), pd)
        layers["k_norm"] = jnp.ones((L, cfg.d_head), pd)
    if cfg.is_moe:
        E = cfg.n_experts
        layers["router"] = dense_init(ks["router"], (L, d, E), dtype=pd)
        layers["w1"] = dense_init(ks["ffn1"], (L, E, d, ff), dtype=pd)
        layers["w3"] = dense_init(ks["ffn3"], (L, E, d, ff), dtype=pd)
        layers["w2"] = dense_init(ks["ffn2"], (L, E, ff, d), dtype=pd)
    else:
        layers["w1"] = dense_init(ks["ffn1"], (L, d, ff), dtype=pd)
        layers["w3"] = dense_init(ks["ffn3"], (L, d, ff), dtype=pd)
        layers["w2"] = dense_init(ks["ffn2"], (L, ff, d), dtype=pd)
    return {
        "embed": dense_init(ks["embed"], (V, d), scale=0.02, dtype=pd),
        "layers": layers,
        "final_ln": jnp.ones((d,), pd),
        "lm_head": dense_init(ks["head"], (d, V), dtype=pd),
    }


def param_specs(cfg: TransformerConfig, model_axis: str = "model", tp: int = 16):
    """PartitionSpec tree matching init_params (Megatron TP over `model`)."""
    m = model_axis
    kv_shardable = cfg.n_kv_heads % tp == 0
    layers: dict[str, Any] = {
        "wq": P(None, None, m),
        "wk": P(None, None, m) if kv_shardable else P(None, None, None),
        "wv": P(None, None, m) if kv_shardable else P(None, None, None),
        "wo": P(None, m, None),
        "ln1": P(None, None),
        "ln2": P(None, None),
    }
    if cfg.qkv_bias:
        layers["bq"] = P(None, m)
        layers["bk"] = P(None, m) if kv_shardable else P(None, None)
        layers["bv"] = P(None, m) if kv_shardable else P(None, None)
    if cfg.qk_norm:
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    if cfg.is_moe:
        if cfg.n_experts % tp == 0:  # expert parallelism over `model`
            layers["router"] = P(None, None, None)
            layers["w1"] = P(None, m, None, None)
            layers["w3"] = P(None, m, None, None)
            layers["w2"] = P(None, m, None, None)
        else:  # TP inside each expert
            layers["router"] = P(None, None, None)
            layers["w1"] = P(None, None, None, m)
            layers["w3"] = P(None, None, None, m)
            layers["w2"] = P(None, None, m, None)
    else:
        layers["w1"] = P(None, None, m)
        layers["w3"] = P(None, None, m)
        layers["w2"] = P(None, m, None)
    return {
        "embed": P(m, None),
        "layers": layers,
        "final_ln": P(None),
        "lm_head": P(None, m),
    }


# ==========================================================================
# RoPE
# ==========================================================================

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ==========================================================================
# Attention
# ==========================================================================

def _attn_scores_mask(q_pos, k_pos, window: int):
    """[Sq, Sk] bool mask: causal, optionally sliding-window."""
    m = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def full_attention(q, k, v, q_pos, k_pos, window: int):
    """q: [B,Sq,H,D]; k,v: [B,Sk,KV,D].  Materializes [Sq,Sk] scores."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    s *= 1.0 / math.sqrt(D)
    mask = _attn_scores_mask(q_pos, k_pos, window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def chunked_attention(q, k, v, q_pos, k_pos, window: int, chunk: int):
    """Flash-style online-softmax attention, O(chunk^2) live scores.

    Outer scan over q chunks, inner scan over kv chunks with running
    (max, denom, acc) carried in f32.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    C = chunk
    nq = S // C
    nk = k.shape[1] // C
    qg = q.reshape(B, nq, C, KV, G, D)
    kc = k.reshape(B, nk, C, KV, D)
    vc = v.reshape(B, nk, C, KV, D)
    qpc = q_pos.reshape(nq, C)
    kpc = k_pos.reshape(nk, C)
    scale = 1.0 / math.sqrt(D)

    def q_block(qi):
        qb = qg[:, qi].astype(jnp.float32) * scale  # [B,C,KV,G,D]
        qp = qpc[qi]

        def kv_step(carry, inputs):
            m, l, acc = carry
            kb, vb, kp = inputs
            s = jnp.einsum("bckgd,btkd->bkgct", qb, kb.astype(jnp.float32))
            mask = _attn_scores_mask(qp, kp, window)[None, None, None]
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mask
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgct,btkd->bkgcd", p, vb.astype(jnp.float32)
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, KV, G, C), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, C), jnp.float32)
        a0 = jnp.zeros((B, KV, G, C, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kpc),
        )
        o = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,KV,G,C,D]
        return o.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D)

    out = jax.lax.map(q_block, jnp.arange(nq))  # [nq,B,C,H,D]
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D).astype(q.dtype)


# ==========================================================================
# FFN (dense SwiGLU / MoE with sort-based dispatch)
# ==========================================================================

def dense_ffn(x, w1, w3, w2):
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def moe_ffn(x, router, w1, w3, w2, cfg: TransformerConfig):
    """Sort-free top-k dispatch: cumsum position assignment.

    x: [T, d].  Returns ([T, d], aux_loss).

    Perf note (EXPERIMENTS.md section Perf, mixtral hillclimb): the first
    implementation dispatched via a global ``argsort`` over T*k (token,
    expert) pairs and scatter-combined -- under pjit both the sharded sort
    and the replicated [E, cap, d] buffer exploded into hundreds of GB of
    all-gather traffic.  This version:
      * derives position-in-expert with an exclusive ``cumsum`` over the
        [T, E] assignment mask (sharding-friendly prefix sum, no sort);
      * combines by *gathering* y[e, pos] back per (token, slot) -- no
        scatter on the combine path;
      * constrains the dispatch buffer so the capacity dim follows the
        batch axes and (for EP) experts follow `model`.
    """
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    G = max(1, min(cfg.moe_groups, T))
    while T % G:
        G //= 2
    Tg = T // G
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # [T, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    cap = int(math.ceil(Tg * k / E * cfg.capacity_factor))
    cap = max(cap, 4)
    idx_g = idx.reshape(G, Tg, k)
    # assignment mask [G, Tg, E]; exclusive prefix WITHIN each group ->
    # every (expert, group) slice of the buffer is written only by its own
    # group's tokens, so dispatch + combine stay shard-local under pjit
    mask = jnp.zeros((G, Tg, E), jnp.int32)
    g_i = jax.lax.broadcasted_iota(jnp.int32, (G, Tg, k), 0)
    t_i = jax.lax.broadcasted_iota(jnp.int32, (G, Tg, k), 1)
    mask = mask.at[g_i, t_i, idx_g].add(1)
    pos_te = jnp.cumsum(mask, axis=1) - mask  # [G, Tg, E]
    pos = jnp.take_along_axis(pos_te, idx_g, axis=2)  # [G, Tg, k]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap - 1)

    ep = E % 16 == 0
    dsh = ("pod", "data")
    buf = jnp.zeros((G, E, cap, d), x.dtype)
    xk = jnp.where(keep[..., None], x.reshape(G, Tg, 1, d), 0)  # [G,Tg,k,d]
    buf = buf.at[
        g_i.reshape(G, Tg * k),
        idx_g.reshape(G, Tg * k),
        pos_c.reshape(G, Tg * k),
    ].add(xk.reshape(G, Tg * k, d))
    buf = maybe_shard(buf, P(dsh, "model" if ep else None, None, None))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, w1)) * jnp.einsum(
        "gecd,edf->gecf", buf, w3
    )
    y = jnp.einsum("gecf,efd->gecd", h, w2)  # [G, E, cap, d]
    # NO sharding constraint on y: with TP-in-expert the w2 contraction
    # leaves partial sums over `model`; the gate-weighted combine below is
    # linear, so XLA can defer the all-reduce until AFTER the combine --
    # reducing [T, d] token activations instead of the 2.5x-expanded
    # [G, E, cap, d] buffer (EXPERIMENTS.md Perf, mixtral iteration 3)
    # combine by GATHER within the group: out[g,t] = sum_j gate_j * y[g,e_j,pos_j]
    yk = y[
        g_i.reshape(G, Tg * k),
        idx_g.reshape(G, Tg * k),
        pos_c.reshape(G, Tg * k),
    ].reshape(G, Tg, k, d)
    out = jnp.einsum(
        "gtk,gtkd->gtd", (gates.reshape(G, Tg, k) * keep).astype(yk.dtype), yk
    ).reshape(T, d)
    # aux load-balancing loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.sum(mask, axis=(0, 1)).astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(me * ce)
    return out.astype(x.dtype), aux


def _moe_local(x, router, w1, w3, w2, cfg: TransformerConfig, n_local_experts: int,
               model_axis: str | None, data_axes_names: tuple = ()):
    """Per-shard MoE body used inside shard_map.

    x: [T_local, d] (this data shard's tokens).  Dispatch positions are
    computed locally (one GShard group per shard).  Two modes:
      * TP-in-expert (w1 local shape [E, d, ff/tp]): compute partial y,
        combine locally, ``psum`` the TOKEN-sized output over `model` --
        this is the whole point: the wire carries [T_local, d], not the
        2.5x-expanded capacity buffer (and never in f32).
      * EP (w1 local [E/tp, d, ff]): ``all_to_all`` the capacity buffer over
        `model` so each shard computes its resident experts, then a2a back.
    """
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)

    cap = max(4, int(math.ceil(T * k / E * cfg.capacity_factor)))
    mask = jnp.zeros((T, E), jnp.int32).at[jnp.arange(T)[:, None], idx].add(1)
    pos = jnp.take_along_axis(jnp.cumsum(mask, axis=0) - mask, idx, axis=1)
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap - 1)

    buf = jnp.zeros((E, cap, d), x.dtype)
    xk = jnp.where(keep[..., None], x[:, None, :], 0)
    buf = buf.at[idx.reshape(-1), pos_c.reshape(-1)].add(xk.reshape(T * k, d))

    ep = n_local_experts < E
    if ep and model_axis is not None:
        tp = E // n_local_experts
        # [E, cap, d] -> [tp, E/tp, cap, d]; a2a over model: shard m receives
        # every shard's rows for ITS resident experts (dim 0 becomes the
        # source-shard index) -> transpose to [E/tp, tp*cap, d]
        bufe = jax.lax.all_to_all(
            buf.reshape(tp, n_local_experts, cap, d), model_axis, 0, 0
        )
        bufe = bufe.transpose(1, 0, 2, 3).reshape(n_local_experts, tp * cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufe, w1)) * jnp.einsum(
            "ecd,edf->ecf", bufe, w3
        )
        y = jnp.einsum("ecf,efd->ecd", h, w2)
        y = y.reshape(n_local_experts, tp, cap, d).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(y, model_axis, 0, 0).reshape(E, cap, d)
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) * jnp.einsum(
            "ecd,edf->ecf", buf, w3
        )
        y = jnp.einsum("ecf,efd->ecd", h, w2)  # partial over model when TP

    yk = y[idx.reshape(-1), pos_c.reshape(-1)].reshape(T, k, d)
    out = jnp.einsum("tk,tkd->td", (gates * keep.astype(gates.dtype)), yk)
    if not ep and model_axis is not None:
        # keep the wire in bf16: the reduction operand must not be upcast
        out = jax.lax.psum(out.astype(x.dtype), model_axis)
    me = jnp.mean(probs, axis=0)
    ce = jnp.sum(mask, axis=0).astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(me * ce)
    for ax in data_axes_names:
        aux = jax.lax.pmean(aux, ax)
    if model_axis is not None:
        aux = jax.lax.pmean(aux, model_axis)
    return out.astype(x.dtype), aux


def moe_ffn_shard_map(x, router, w1, w3, w2, cfg: TransformerConfig):
    """Explicit-collective MoE via shard_map (EXPERIMENTS.md Perf).

    Falls back to the pjit ``moe_ffn`` when no mesh is active.
    """
    mesh = get_abstract_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn(x, router, w1, w3, w2, cfg)
    dsh = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]
    ds = 1
    for a in dsh:
        ds *= mesh.shape[a]
    E = cfg.n_experts
    T = x.shape[0]
    ep = E % tp == 0 and T % (ds * tp) == 0 and T >= 4 * ds * tp
    if (not ep and (T % ds != 0 or T < 4 * ds)) or not dsh:
        # decode-sized token counts cannot shard over the mesh: the pjit
        # path's tiny buffers are fine there
        return moe_ffn(x, router, w1, w3, w2, cfg)
    w_spec = P("model", None, None) if ep else P(None, None, "model")
    w2_spec = P("model", None, None) if ep else P(None, "model", None)
    n_local = E // tp if ep else E
    # EP: tokens are sharded over `model` as well (sequence-parallel entry),
    # so every device dispatches only ITS token slice -- no redundant expert
    # rows in the a2a.  TP-in-expert: tokens replicated over `model` (each
    # shard owns an ff slice of every token) + token-sized psum at the end.
    x_spec = P(dsh + ("model",), None) if ep else P(dsh, None)

    def body(xl, rl, w1l, w3l, w2l):
        return _moe_local(xl, rl, w1l, w3l, w2l, cfg, n_local, "model", dsh)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w2_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, router, w1, w3, w2)


# ==========================================================================
# Layer / forward
# ==========================================================================

def _layer(x, lp, positions, cfg: TransformerConfig, kv_cache=None, cache_pos=None):
    """One transformer block.  x: [B,S,d].  Returns (y, aux, new_kv)."""
    cd = cfg.compute_dtype
    B, S, d = x.shape
    h = rms_norm(x, lp["ln1"]).astype(cd)
    q = h @ lp["wq"].astype(cd)
    kk = h @ lp["wk"].astype(cd)
    vv = h @ lp["wv"].astype(cd)
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(cd)
        kk = kk + lp["bk"].astype(cd)
        vv = vv + lp["bv"].astype(cd)
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    kk = kk.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    vv = vv.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"])
        kk = rms_norm(kk, lp["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    kk = rope(kk, positions, cfg.rope_theta)

    new_kv = None
    if kv_cache is not None:
        ck, cv = kv_cache  # [B, S_cache, KV, D]
        if cache_pos is not None:  # decode: insert at cache_pos (ring for SWA)
            Sc = ck.shape[1]
            slot = cache_pos % Sc if cfg.sliding_window > 0 else cache_pos
            ck = jax.lax.dynamic_update_slice(ck, kk, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, vv, (0, slot, 0, 0))
            k_pos_abs = _cache_positions(Sc, cache_pos, cfg)
            o = full_attention(q, ck, cv, positions, k_pos_abs, cfg.sliding_window)
            new_kv = (ck, cv)
        else:
            raise ValueError("cache without cache_pos")
    else:
        if S > cfg.attn_chunk and S % cfg.attn_chunk == 0:
            o = chunked_attention(
                q, kk, vv, positions, positions, cfg.sliding_window, cfg.attn_chunk
            )
        else:
            o = full_attention(q, kk, vv, positions, positions, cfg.sliding_window)
        new_kv = (kk, vv)
    o = o.reshape(B, S, cfg.q_dim) @ lp["wo"].astype(cd)
    x = x + o.astype(x.dtype)

    h = rms_norm(x, lp["ln2"]).astype(cd)
    if cfg.is_moe:
        moe = moe_ffn_shard_map if cfg.moe_shard_map else moe_ffn
        y, aux = moe(
            h.reshape(B * S, d),
            lp["router"].astype(cd),
            lp["w1"].astype(cd),
            lp["w3"].astype(cd),
            lp["w2"].astype(cd),
            cfg,
        )
        y = y.reshape(B, S, d)
    else:
        y = dense_ffn(h, lp["w1"].astype(cd), lp["w3"].astype(cd), lp["w2"].astype(cd))
        aux = jnp.float32(0.0)
    return x + y.astype(x.dtype), aux, new_kv


def _cache_positions(Sc: int, cache_pos, cfg: TransformerConfig):
    """Absolute positions held by each cache slot at decode time."""
    slots = jnp.arange(Sc)
    if cfg.sliding_window > 0:
        # ring buffer: slot s holds the latest absolute position p <= cache_pos
        # with p % Sc == s; invalid (future) slots get a huge position.
        base = (cache_pos // Sc) * Sc
        pos = jnp.where(slots <= cache_pos % Sc, base + slots, base - Sc + slots)
        return jnp.where(pos >= 0, pos, jnp.iinfo(jnp.int32).max)
    return jnp.where(slots <= cache_pos, slots, jnp.iinfo(jnp.int32).max)


def forward(params, tokens, cfg: TransformerConfig, positions=None):
    """tokens: [B,S] -> final hidden states [B,S,d] (pre lm_head)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.arange(S)
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x = maybe_shard(x, P(("pod", "data"), None, None))

    def body(x, lp):
        y, aux, _ = _layer(x, lp, positions, cfg)
        return y, aux

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    x, auxs = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_ln"])
    return x, auxs.sum()


def lm_loss(params, tokens, labels, cfg: TransformerConfig):
    """Chunked cross-entropy over the vocab (avoids [B,S,V] materialization)."""
    x, aux = forward(params, tokens, cfg)
    B, S, d = x.shape
    C = min(cfg.loss_chunk, S)
    nc = S // C
    head = params["lm_head"].astype(cfg.compute_dtype)

    def chunk_loss(ci):
        xs = jax.lax.dynamic_slice(x, (0, ci * C, 0), (B, C, d))
        ls = jax.lax.dynamic_slice(labels, (0, ci * C), (B, C))
        logits = (xs @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return (lse - gold).sum()

    total = jax.lax.map(chunk_loss, jnp.arange(nc)).sum()
    rem = S - nc * C
    if rem:
        logits = (x[:, nc * C :] @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, nc * C :][..., None], -1)[..., 0]
        total = total + (lse - gold).sum()
    return total / (B * S) + 0.01 * aux


# ==========================================================================
# Serving
# ==========================================================================

def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    Sc = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    shape = (cfg.n_layers, 2, batch, Sc, cfg.n_kv_heads, cfg.d_head)
    return jnp.zeros(shape, cfg.compute_dtype)


def prefill_step(params, tokens, cfg: TransformerConfig):
    """Prompt forward: returns last-position logits + KV cache."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x = maybe_shard(x, P(("pod", "data"), None, None))

    def body(x, lp):
        y, _aux, kv = _layer(x, lp, positions, cfg)
        if cfg.sliding_window > 0 and kv[0].shape[1] > cfg.sliding_window:
            kv = tuple(z[:, -cfg.sliding_window :] for z in kv)
        return y, jnp.stack([kv[0], kv[1]])

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    x, cache = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x[:, -1:], params["final_ln"])
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    return logits[:, 0], cache


def serve_step(params, cache, token, cache_pos, cfg: TransformerConfig):
    """One decode step.  cache: [L,2,B,Sc,KV,D]; token: [B] int32."""
    positions = jnp.full((1,), cache_pos, jnp.int32)
    x = params["embed"][token[:, None]].astype(cfg.compute_dtype)

    def body(x, inputs):
        lp, kv = inputs
        y, _aux, new_kv = _layer(
            x, lp, positions, cfg, kv_cache=(kv[0], kv[1]), cache_pos=cache_pos
        )
        return y, jnp.stack([new_kv[0], new_kv[1]])

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = rms_norm(x, params["final_ln"])
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    return logits[:, 0], new_cache


# ==========================================================================
# Dry-run input specs
# ==========================================================================

def input_specs(cfg: TransformerConfig, shape_kind: str, seq_len: int, batch: int):
    """ShapeDtypeStructs + PartitionSpecs for each entry point."""
    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    if shape_kind == "train":
        return {"tokens": tok, "labels": tok}
    if shape_kind == "prefill":
        return {"tokens": tok}
    if shape_kind == "decode":
        Sc = min(seq_len, cfg.sliding_window) if cfg.sliding_window > 0 else seq_len
        cache = jax.ShapeDtypeStruct(
            (cfg.n_layers, 2, batch, Sc, cfg.n_kv_heads, cfg.d_head),
            cfg.compute_dtype,
        )
        return {
            "cache": cache,
            "token": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }
    raise ValueError(shape_kind)
