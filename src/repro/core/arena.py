"""Block-aligned device arena over a ``PartitionedIndex`` (DESIGN.md §2).

The on-disk/paper layout of the index (plain-VByte or bit-vector payloads,
byte offsets) is great for space but hostile to a device hot path: payloads
are variable-length, partitions start mid-byte-stream, and bit-vectors need a
different decoder.  The arena is the *query-time* representation: every
partition -- VByte AND bit-vector -- is transcoded ONCE at build into the
fixed-block Stream-VByte layout consumed by ``repro.kernels.vbyte_decode``:

  * 128 values / 512 data bytes per block (``BLOCK_VALS`` / ``BLOCK_BYTES``),
  * each partition padded to WHOLE blocks (pad gap-1 = 0, so padded lanes
    keep ascending past the partition endpoint -- they can never win a
    NextGEQ whose probe is <= the endpoint),
  * blocks of one partition are consecutive rows, partitions of one list are
    consecutive runs, lists are laid out in id order.

Per-block sidecars make every block self-decoding and directly searchable:

  * ``block_base[b]``  -- absolute docID preceding the block's first value,
    so ``values = block_base + cumsum(gaps + 1)`` needs no cross-block scan;
  * ``block_keys[b]``  -- ``last_real_value + list_of_block * stride`` with
    ``stride > max docID + 1``: globally non-decreasing, so ONE searchsorted
    over all blocks locates the unique block holding NextGEQ(term, probe)
    for every cursor of a batch at once (the partition-level trick of PR 1,
    pushed down to block granularity);
  * ``lane_valid[b, i]`` -- mask of real (non-padding) lanes.

``dev`` uploads the arrays to the default jax device once, narrowed to
int32 by ``to_i32``, which raises instead of wrapping.  ``block_keys``
outgrow 31 bits on any real index (``n_lists * stride``), so they stay on
the host: the device locate (``engine_core.locate_graph``) searches each
list's own block range of ``block_last`` -- the last real docID per block,
< 2^31 because ``build_arena`` refuses docIDs above ``MAX_DOCID``.

MULTI-CODEC arenas (DESIGN.md §14): under ``codec_policy="auto"`` blocks of
Elias-Fano-tagged partitions (and under ``"ef"`` every eligible block) are
stored as fixed-width EF tiles (``ef_lo`` / ``ef_hi`` / ``ef_lbits``, 308
bytes per block) instead of Stream-VByte rows, served by
``repro.kernels.ef_search``.  ``block_codec[b]`` tags each block (0 = SVB,
1 = EF) and ``codec_row[b]`` gives its row WITHIN its codec's arrays --
``lens`` / ``data`` then hold only the SVB rows, so the arena actually
shrinks.  The locate sidecars (``block_base`` / ``block_keys`` /
``lane_valid``) and the ranked sidecar stay per-BLOCK and codec-agnostic:
one searchsorted still locates every cursor, only the decode is dispatched
per codec.  Single-codec arenas keep ``block_codec = None`` and the exact
row-identity layout of PR 1 -- every existing path is byte-for-byte
unchanged.

When the index carries a freq stream (``index.has_freqs``), the transcode
also builds the RANKED sidecar (DESIGN.md §5): the per-posting term
frequencies re-encoded into PARALLEL Stream-VByte blocks (``freq_lens`` /
``freq_data``, lane-aligned with the docID blocks), an 8-bit quantized
length-norm code per lane (``norm_q``), and the block-max structure of the
BM25 literature: ``block_max_q[b]``, an upper-bound-safe u8 quantization of
the true maximum contract score inside block b, plus per-list upper bounds
and idf.  Quantization rounds UP (and is then verified lane-exactly), so no
block's true max ever exceeds its dequantized bound -- the admissibility
invariant Block-Max WAND/MaxScore pruning rests on.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.kernels.vbyte_decode.kernel import BLOCK_VALS

TAG_VBYTE = 0
TAG_EF = 2  # mirrors repro.core.index (which imports this module)

CODEC_SVB = 0  # block_codec values
CODEC_EF = 1
CODEC_POLICIES = ("svb", "auto", "ef")

# largest docID the device layout holds: padding lanes run up to 128 past a
# block's last real docID, and ``stride`` = max docID + 2, all in int32
MAX_DOCID = 2**31 - BLOCK_VALS - 2


def to_i32(a, name: str) -> np.ndarray:
    """``a`` narrowed to int32 for the device; raises rather than wraps."""
    a = np.asarray(a)
    info = np.iinfo(np.int32)
    if a.size and (a.min() < info.min or a.max() > info.max):
        raise OverflowError(
            f"{name} spans [{a.min()}, {a.max()}], outside int32"
        )
    return a.astype(np.int32)


@dataclass
class RankedSidecar:
    """Freq blocks + BM25 block-max structure riding the arena (§5)."""

    freq_lens: np.ndarray    # [nb_padded, 128] int32  (VByte of tf - 1)
    freq_data: np.ndarray    # [nb_padded, 512] uint8
    norm_q: np.ndarray       # [n_blocks, 128] uint8  quantized doc-norm code
    block_max_q: np.ndarray  # [n_blocks] uint8  quantized score upper bound
    bound_scale: np.float32  # dequant: bound(b) = block_max_q[b] * bound_scale
    idf: np.ndarray          # [n_lists] float32
    list_ub: np.ndarray      # [n_lists] float32  max block bound per list
    kmin: np.float32         # norm dequant grid (repro.ranked.bm25)
    kstep: np.float32
    norm_table: np.ndarray   # [256] float32  gathered (never recomputed)
    params: object           # BM25Params the sidecar was built with
    _dev: object = field(default=None, repr=False, compare=False)

    def block_bounds(self) -> np.ndarray:
        """Dequantized per-block score upper bounds, float32 (admissible)."""
        return (
            self.block_max_q.astype(np.float32) * np.float32(self.bound_scale)
        )

    @property
    def dev(self):
        if self._dev is None:
            import jax.numpy as jnp
            from types import SimpleNamespace

            self._dev = SimpleNamespace(
                freq_lens=jnp.asarray(self.freq_lens),
                freq_data=jnp.asarray(self.freq_data),
                norm_q=jnp.asarray(self.norm_q),
                idf=jnp.asarray(self.idf),
                norm_table=jnp.asarray(self.norm_table),
            )
        return self._dev

    def nbytes(self) -> int:
        return int(
            self.freq_lens.nbytes + self.freq_data.nbytes + self.norm_q.nbytes
            + self.block_max_q.nbytes
        )


@dataclass
class DeviceArena:
    # per block (lens/data are padded by pack_blocks to a multiple of BM rows;
    # the sidecars below cover only the n_blocks real rows)
    lens: np.ndarray          # [nb_padded, 128] int32  control lengths
    data: np.ndarray          # [nb_padded, 512] uint8  data bytes
    block_base: np.ndarray    # [n_blocks] int64  docID before the block
    block_keys: np.ndarray    # [n_blocks] int64  last real value + list*stride
    lane_valid: np.ndarray    # [n_blocks, 128] bool  real-lane mask
    part_of_block: np.ndarray  # [n_blocks] int64
    # per partition
    first_blk: np.ndarray     # [n_parts] int64
    n_blk: np.ndarray         # [n_parts] int64
    sizes: np.ndarray         # [n_parts] int64  (values per partition)
    bases: np.ndarray         # [n_parts] int64  docID before the partition
    part_list: np.ndarray     # [n_parts] int64  owning list
    # per list
    list_blk_offsets: np.ndarray  # [n_lists + 1] int64
    stride: int = 0
    n_blocks: int = 0
    ranked: RankedSidecar | None = None
    # multi-codec layout (None on single-codec arenas: lens/data rows are
    # then block rows, the PR 1 identity layout)
    block_codec: np.ndarray | None = None  # [n_blocks] uint8  0=SVB 1=EF
    codec_row: np.ndarray | None = None    # [n_blocks] int64  row in codec
    ef_lo: np.ndarray | None = None        # [n_ef, 128] uint16 low bits
    ef_hi: np.ndarray | None = None        # [n_ef, 24] uint16  high words
    ef_lbits: np.ndarray | None = None     # [n_ef] uint8  l per tile
    _dev: object = field(default=None, repr=False, compare=False)

    @property
    def multi(self) -> bool:
        """True when blocks mix codecs (lens/data hold SVB rows only)."""
        return self.block_codec is not None

    def block_last(self) -> np.ndarray:
        """[n_blocks] int64 last real docID of each block."""
        return self.block_keys - self.part_list[self.part_of_block] * self.stride

    @property
    def locate_iters(self) -> int:
        """Binary-search steps that cover the longest list's block range."""
        counts = np.diff(self.list_blk_offsets)
        return int(counts.max()).bit_length() if counts.size else 0

    @property
    def dev(self):
        """jnp copies of the arena, uploaded once (checked int32 narrowing)."""
        if self._dev is None:
            import jax.numpy as jnp
            from types import SimpleNamespace

            def up(name, arr):
                return jnp.asarray(to_i32(arr, name))

            self._dev = SimpleNamespace(
                lens=jnp.asarray(self.lens),
                data=jnp.asarray(self.data),
                block_base=up("block_base", self.block_base),
                block_last=up("block_last", self.block_last()),
                part_of_block=up("part_of_block", self.part_of_block),
                first_blk=up("first_blk", self.first_blk),
                list_blk_offsets=up("list_blk_offsets", self.list_blk_offsets),
            )
            if self.block_codec is not None:
                self._dev.block_codec = up("block_codec", self.block_codec)
                self._dev.codec_row = up("codec_row", self.codec_row)
                self._dev.ef_lo = up("ef_lo", self.ef_lo)
                self._dev.ef_hi = up("ef_hi", self.ef_hi)
                self._dev.ef_lbits = up("ef_lbits", self.ef_lbits)
        return self._dev

    def nbytes(self) -> int:
        total = int(
            self.lens.nbytes + self.data.nbytes + self.block_base.nbytes
            + self.block_keys.nbytes + self.lane_valid.nbytes
        ) + (self.ranked.nbytes() if self.ranked is not None else 0)
        if self.block_codec is not None:
            total += int(
                self.block_codec.nbytes + self.codec_row.nbytes
                + self.ef_lo.nbytes + self.ef_hi.nbytes
                + self.ef_lbits.nbytes
            )
        return total


def build_arena(index, codec_policy: str = "auto") -> DeviceArena:
    """Transcode every partition of ``index`` into the block arena.

    ``codec_policy`` picks the per-BLOCK storage codec: ``"svb"`` forces
    the all-Stream-VByte layout of PR 1; ``"auto"`` stores the blocks of
    Elias-Fano-TAGGED partitions as EF tiles where block-eligible;
    ``"ef"`` stores EVERY eligible block as an EF tile regardless of the
    partition's serialized tag.  When no block ends up EF (e.g. ``"auto"``
    over an index built with ``codecs="svb"``), the arena is returned in
    the single-codec identity layout (``block_codec is None``).
    """
    from repro.core.bitvector import bitvector_decode
    from repro.core.eliasfano import ef_decode
    from repro.core.vbyte import vbyte_decode
    from repro.kernels.vbyte_decode.ops import pack_blocks

    if codec_policy not in CODEC_POLICIES:
        raise ValueError(
            f"codec_policy must be one of {CODEC_POLICIES}, got "
            f"{codec_policy!r}"
        )

    n_parts = len(index.endpoints)
    if n_parts and int(index.endpoints.max()) > MAX_DOCID:
        raise ValueError(
            f"docID {int(index.endpoints.max())} exceeds {MAX_DOCID}: the "
            "device arena holds docIDs in int32 lanes"
        )
    sizes = index.sizes.astype(np.int64)
    part_counts = np.diff(index.list_part_offsets)
    part_list = np.repeat(np.arange(index.n_lists, dtype=np.int64), part_counts)
    # base docID per partition: endpoint of the previous partition of the
    # SAME list, -1 for the first partition of each list
    bases = np.empty(n_parts, np.int64)
    if n_parts:
        bases[0] = -1
        bases[1:] = index.endpoints[:-1]
        bases[index.list_part_offsets[:-1][part_counts > 0]] = -1

    n_blk = (sizes + BLOCK_VALS - 1) // BLOCK_VALS
    first_blk = np.zeros(n_parts, np.int64)
    if n_parts:
        first_blk[1:] = np.cumsum(n_blk)[:-1]
    nb = int(n_blk.sum())

    ranked_on = bool(getattr(index, "has_freqs", False))
    gaps_m1 = np.zeros(nb * BLOCK_VALS, np.uint32)
    block_base = np.zeros(nb, np.int64)
    block_last = np.zeros(nb, np.int64)
    lane_valid = np.zeros((nb, BLOCK_VALS), bool)
    tf_m1 = np.zeros(nb * BLOCK_VALS, np.uint32) if ranked_on else None
    norm_q = np.zeros(nb * BLOCK_VALS, np.uint8) if ranked_on else None
    if ranked_on:
        from repro.ranked.bm25 import DEFAULT_BM25, quantize_norms

        q_norms, kmin, kstep = quantize_norms(
            index.doc_lens, index.avg_dl, DEFAULT_BM25
        )
    payload_end = index.offsets[1:].tolist() + [index.payload.size]
    for p in range(n_parts):
        off, end = int(index.offsets[p]), int(payload_end[p])
        size, base = int(sizes[p]), int(bases[p])
        if index.tags[p] == TAG_VBYTE:
            g = vbyte_decode(index.payload[off:end], size).astype(np.int64)
            vals = base + np.cumsum(g + 1)
        elif index.tags[p] == TAG_EF:
            vals = ef_decode(index.payload[off:end], size) + base + 1
            g = np.diff(vals, prepend=base) - 1
        else:
            universe = int(index.endpoints[p]) - base
            vals = bitvector_decode(index.payload[off:end], universe) + base + 1
            g = np.diff(vals, prepend=base) - 1
        b0, k = int(first_blk[p]), int(n_blk[p])
        s = b0 * BLOCK_VALS
        gaps_m1[s : s + size] = g
        block_base[b0] = base
        block_base[b0 + 1 : b0 + k] = vals[BLOCK_VALS - 1 :: BLOCK_VALS][: k - 1]
        block_last[b0 : b0 + k] = vals[
            np.minimum(np.arange(1, k + 1) * BLOCK_VALS, size) - 1
        ]
        lv = lane_valid[b0 : b0 + k].reshape(-1)
        lv[:size] = True
        if ranked_on:
            tf_m1[s : s + size] = index._decode_partition_freqs(p) - 1
            norm_q[s : s + size] = q_norms[vals]

    # per-BLOCK codec split (§14): EF tiles where the policy + per-block
    # eligibility allow, Stream-VByte rows (compacted) for the rest
    block_codec = codec_row = ef_lo = ef_hi = ef_lbits = None
    svb_gaps = gaps_m1
    if codec_policy != "svb" and nb:
        from repro.kernels.ef_search.ops import (
            ef_block_eligible,
            ef_pack_blocks,
        )

        blk_vals = block_base[:, None] + np.cumsum(
            gaps_m1.reshape(nb, BLOCK_VALS).astype(np.int64) + 1, axis=1
        )
        want = (
            np.repeat(np.asarray(index.tags) == TAG_EF, n_blk)
            if codec_policy == "auto"
            else np.ones(nb, bool)
        )
        ef_mask = want & ef_block_eligible(blk_vals, block_base)
        if ef_mask.any():
            block_codec = np.where(ef_mask, CODEC_EF, CODEC_SVB).astype(
                np.uint8
            )
            # row of each block WITHIN its codec's arrays (rows stay in
            # block order per codec, so gathered rows remain ascending)
            codec_row = np.zeros(nb, np.int64)
            codec_row[~ef_mask] = np.arange(int((~ef_mask).sum()))
            codec_row[ef_mask] = np.arange(int(ef_mask.sum()))
            ef_lo, ef_hi, ef_lbits = ef_pack_blocks(
                blk_vals[ef_mask], block_base[ef_mask]
            )
            svb_gaps = gaps_m1.reshape(nb, BLOCK_VALS)[~ef_mask].reshape(-1)
    lens, data, _ = pack_blocks(svb_gaps)

    stride = int(index.endpoints.max()) + 2 if n_parts else 2
    block_keys = block_last + part_list[
        np.repeat(np.arange(n_parts, dtype=np.int64), n_blk)
    ] * stride
    part_of_block = np.repeat(np.arange(n_parts, dtype=np.int64), n_blk)
    list_blk_offsets = np.zeros(index.n_lists + 1, np.int64)
    if n_parts:
        list_blk_offsets[:] = np.concatenate(
            [first_blk, [nb]]
        )[index.list_part_offsets]

    ranked = None
    if ranked_on:
        ranked = _build_ranked_sidecar(
            index, tf_m1, norm_q, lane_valid, part_list, n_blk, nb,
            kmin, kstep,
        )

    return DeviceArena(
        lens=lens,
        data=data,
        block_base=block_base,
        block_keys=block_keys,
        lane_valid=lane_valid,
        part_of_block=part_of_block,
        first_blk=first_blk,
        n_blk=n_blk,
        sizes=sizes,
        bases=bases,
        part_list=part_list,
        list_blk_offsets=list_blk_offsets,
        stride=stride,
        n_blocks=nb,
        ranked=ranked,
        block_codec=block_codec,
        codec_row=codec_row,
        ef_lo=ef_lo,
        ef_hi=ef_hi,
        ef_lbits=ef_lbits,
    )


def _build_ranked_sidecar(
    index, tf_m1, norm_q, lane_valid, part_list, n_blk, nb, kmin, kstep
) -> RankedSidecar:
    """Freq blocks + admissible block-max bounds (see module docstring)."""
    from repro.kernels.vbyte_decode.ops import pack_blocks
    from repro.ranked.bm25 import (
        DEFAULT_BM25,
        dequant_norm,
        idf,
        norm_table,
        score_tf,
    )

    freq_lens, freq_data, _ = pack_blocks(tf_m1)
    idf_list = idf(index.n_docs_real, np.maximum(index.list_sizes, 1)).astype(
        np.float32
    )
    # true per-lane contract scores (build-time only; never materialized at
    # query time on device)
    list_of_block = part_list[np.repeat(np.arange(len(n_blk)), n_blk)] \
        if len(n_blk) else np.zeros(0, np.int64)
    lane_idf = np.repeat(idf_list[list_of_block], BLOCK_VALS) \
        if nb else np.zeros(0, np.float32)
    k_hat = dequant_norm(norm_q, kmin, kstep)
    sc = score_tf(tf_m1.astype(np.int64) + 1, k_hat, lane_idf, DEFAULT_BM25)
    sc = np.where(lane_valid.reshape(-1), sc, np.float32(0.0))
    block_true_max = sc.reshape(nb, BLOCK_VALS).max(axis=1) if nb \
        else np.zeros(0, np.float32)
    # upper-bound-safe u8 quantization: ceil onto a 255-level grid, then
    # verify in the contract's float32 and bump where rounding undershot
    scale = float(block_true_max.max()) if nb else 0.0
    bound_scale = np.float32(scale / 255.0) if scale > 0 else np.float32(0.0)
    # f32(255) * bound_scale can round BELOW scale, leaving the q=255 block
    # inadmissible with no room to bump: nudge the scale up until it covers
    while scale > 0 and np.float32(255.0) * bound_scale < np.float32(scale):
        bound_scale = np.nextafter(bound_scale, np.float32(np.inf),
                                   dtype=np.float32)
    if scale > 0:
        q = np.ceil(
            block_true_max.astype(np.float64) / float(bound_scale) - 1e-9
        ).astype(np.int64)
        q = np.clip(q, 0, 255)
        for _ in range(3):  # f32 dequant may still round below the true max
            low = (q.astype(np.float32) * bound_scale) < block_true_max
            if not low.any():
                break
            q[low] = np.minimum(q[low] + 1, 255)
        q = q.astype(np.uint8)
        assert np.all(q.astype(np.float32) * bound_scale >= block_true_max)
    else:
        q = np.zeros(nb, np.uint8)
    bounds = q.astype(np.float32) * bound_scale
    list_ub = np.zeros(index.n_lists, np.float32)
    if nb:
        np.maximum.at(list_ub, list_of_block, bounds)
    return RankedSidecar(
        freq_lens=freq_lens,
        freq_data=freq_data,
        norm_q=norm_q.reshape(nb, BLOCK_VALS),
        block_max_q=q,
        bound_scale=bound_scale,
        idf=idf_list,
        list_ub=list_ub,
        kmin=kmin,
        kstep=kstep,
        norm_table=norm_table(kmin, kstep),
        params=DEFAULT_BM25,
    )
