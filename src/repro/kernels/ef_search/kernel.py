"""Pallas TPU kernel: fused Elias-Fano NextGEQ over gathered block tiles.

The arena re-splits every EF partition into per-block tiles (128 values
each, rebased to the block's ``block_base``; see ``ops.ef_pack_blocks``):
uint16 low bits per lane plus a 384-bit unary high stream -- 128 one-bits
(one per lane) and up to 256 zero-bits (the block universe is capped so
``high < 256``).  The high stream ships as 24 x 16-bit words inside the
staged META tile, so every in-kernel shift stays in non-negative int32.

NextGEQ resolves with NO select-dictionary and NO per-lane control flow,
just lane-aligned counts over 16 bit planes of the high stream (VPU-shaped):

* ``rank`` -- split the rebased probe into (hp, lp).  The position of the
  b-th zero is ``Z(b) = #{j : zcumsum_j <= b}``, so the count of lanes
  with ``high < hp`` is ``Z(hp-1) - (hp-1)`` and the count with ``high <=
  hp`` is ``Z(hp) - hp``; lanes between the two counts with ``low < lp``
  complete the rank.  ``hp > 255`` (probe beyond the tile's high range)
  short-circuits to rank 128.
* ``value`` -- the rank-th one-bit sits at ``S(r) = #{j : ocumsum_j <=
  r}``, so ``high = S(r) - r`` and ``value = base + 1 + (high << l | low)``.

Outputs match ``vbyte_decode.decode_search_blocks`` lane-for-lane: lane 0
the smallest in-block value >= probe (2^31-1 if none), lane 1 the count
of in-block values < probe.  Integer contract -- bit-identical to the jnp
ref and the numpy mirror by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vbyte_decode.kernel import BLOCK_VALS, BM, lane_cumsum

EF_HI_WORDS = 24  # 16 high-stream bits per staged int32 word
EF_HI_BITS = EF_HI_WORDS * 16  # 384 = 128 one-bits + up to 256 zero-bits

# ef_search_blocks meta lanes: [:, 0:EF_HI_WORDS] = the 16-bit high-stream
# words, then per-row scalars; remaining lanes ignored (kept 128-wide)
EFMETA_LBITS = EF_HI_WORDS
EFMETA_BASE = EF_HI_WORDS + 1
EFMETA_PROBE = EF_HI_WORDS + 2
_I32_MAX = 2**31 - 1  # python int: jnp constants would be captured by pallas


def _ef_search_tile(lo, meta, lbits, base, probe):
    """[BM,128] i32 lows + the [BM,128] i32 meta tile (high words in lanes
    0..23) + [BM,1] i32 scalars -> [BM,1] (value, rank).

    The 384-bit high stream is held as 16 bit planes, plane s holding bit s
    of word w in lane w, so every count below is lane-aligned int32 VPU
    work plus one lane scan.  The inclusive one count at stream position j
    = 16w + s is ``before[w] + inner_s[w]``: the ones of the words before w
    plus the ones of bits 0..s of word w.  The zero counts are never
    materialized: zc_j = j+1 - oc_j, so ``zc_j <= b``  <=>  ``oc_j >=
    j+1-b``.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    word = lane < EF_HI_WORDS
    words = jnp.where(word, meta, 0)
    inner = []  # inner[s] = ones among bits 0..s of each word
    acc = jnp.zeros_like(words)
    for s in range(16):
        acc = acc + ((words >> s) & 1)
        inner.append(acc)
    before = lane_cumsum(acc) - acc  # ones in the words before w
    oc = [before + c for c in inner]  # inclusive one count at 16w + s
    pos1 = [lane * 16 + (s + 1) for s in range(16)]  # j + 1
    rp = jnp.clip(probe - base - 1, 0, None)  # rebased probe, >= 0
    hp = rp >> lbits
    lp = rp & ((1 << lbits) - 1)
    # hp clamps to 384: zc <= 256, so every b >= 256 already counts all 384
    # positions -- identical sums, and the hp > 255 rows are overridden by
    # ``big`` below anyway
    hpc = jnp.minimum(hp, EF_HI_BITS)

    def positions(pred):
        """#stream positions j (over the 24 real words) where pred holds."""
        n = jnp.zeros_like(words)
        for s in range(16):
            n = n + (pred(oc[s], pos1[s]) & word).astype(jnp.int32)
        return jnp.sum(n, axis=1, keepdims=True)

    # count_lt = #lanes with high < hp; count_le = #lanes with high <= hp
    z_lt = positions(lambda o, p: o >= p - (hpc - 1))
    z_le = positions(lambda o, p: o >= p - hpc)
    big = hp > 255  # beyond the tile's high range: every lane is below
    count_lt = jnp.where(hp <= 0, 0, z_lt - (hp - 1))
    count_lt = jnp.where(big, BLOCK_VALS, count_lt)
    count_le = jnp.where(big, BLOCK_VALS, z_le - hp)
    mid = jnp.sum(
        ((lane >= count_lt) & (lane < count_le) & (lo < lp)).astype(
            jnp.int32
        ),
        axis=1,
        keepdims=True,
    )
    rank = jnp.where(big, BLOCK_VALS, count_lt + mid)
    rc = jnp.minimum(rank, BLOCK_VALS - 1)
    sel = positions(lambda o, p: o <= rc)
    high_r = sel - rc
    low_r = jnp.sum(jnp.where(lane == rc, lo, 0), axis=1, keepdims=True)
    value = base + 1 + ((high_r << lbits) | low_r)
    value = jnp.where(rank >= BLOCK_VALS, _I32_MAX, value)
    return value, rank


def _ef_search_kernel(lo_ref, meta_ref, out_ref):
    lo = lo_ref[...]
    meta = meta_ref[...]
    value, rank = _ef_search_tile(
        lo,
        meta,
        meta[:, EFMETA_LBITS : EFMETA_LBITS + 1],
        meta[:, EFMETA_BASE : EFMETA_BASE + 1],
        meta[:, EFMETA_PROBE : EFMETA_PROBE + 1],
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, lo.shape, 1)
    out_ref[...] = jnp.where(lane == 0, value, jnp.where(lane == 1, rank, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ef_search_blocks(
    lo: jnp.ndarray, meta: jnp.ndarray, interpret: bool = True
):
    """Fused in-register Elias-Fano NextGEQ over gathered block tiles.

    lo: [nr, 128] int32 -- one GATHERED EF tile's low bits per cursor.
    meta: [nr, 128] int32 carrying per row: lanes 0..23 the 16-bit high
    words, lane EFMETA_LBITS = l, lane EFMETA_BASE = block_base, lane
    EFMETA_PROBE = probe.

    Returns [nr, 128] int32: lane 0 = smallest value >= probe within the
    block (2^31-1 if none), lane 1 = count of block values < probe
    (0..128) -- the ``decode_search_blocks`` output contract exactly.
    """
    nr = lo.shape[0]
    assert nr % BM == 0, f"rows must be a multiple of {BM}"
    grid = (nr // BM,)
    return pl.pallas_call(
        _ef_search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
            pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BM, BLOCK_VALS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr, BLOCK_VALS), jnp.int32),
        interpret=interpret,
    )(lo, meta)
