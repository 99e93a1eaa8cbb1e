"""Shared flat-mirror / locate machinery of the batched engines (one place).

``QueryEngine`` (boolean AND / NextGEQ) and ``TopKEngine`` (BM25 top-k) both
serve batches the same way: locate each (term, probe) cursor's arena row --
on the host with ONE searchsorted over globally monotone int64 keys, on the
device with a binary search of the cursor's own list -- then resolve the
cursor inside the located row.  The machinery behind that -- the flat host
mirror, the lane-key construction with its padding clamp, the pow2 cursor
bucketing, and the int32 probe clip -- lives here, once: its subtleties
are exactly the kind that drift apart silently between two copies.

The subtleties, for the record:

* **padding clamp** (``flat_init``): the flat lane keys extend the arena's
  block keys to lane granularity as ``min(value, block_last) + owning_list *
  stride``.  Padding lanes keep ascending past the partition endpoint (the
  arena pads gap-1 = 0), so WITHOUT the ``min`` they would overtake the next
  partition's keys and break global monotonicity; clamped, they tie with
  their block's last real value and a ``side="left"`` searchsorted can never
  land on a padding lane before the real hit.

* **int32 probe clip** (``stage_cursors``): the device pipeline stages
  cursors as int32.  Probes are clipped to ``[0, stride - 1]`` BEFORE the
  cast -- an int64 probe >= 2^31 must resolve as past-the-end (clip to the
  maximum key, which locates past every real block of the list), not wrap
  negative and clip to probe 0.

* **sentinel lane** (``flat_init``): one extra lane (value -1, key int64
  max, score 0) keeps a past-the-end searchsorted result a valid gather
  index; callers mask with ``lane_end`` afterwards.

* **pow2 buckets** (``pow2_bucket`` / ``search_jax``): device cursor counts
  are padded to power-of-two buckets so jit traces are reused across
  batches; padding cursors probe list 0 at docID 0 and are sliced away.

One ``EngineCore`` serves ONE ``DeviceArena`` -- the sharded engines hold a
core per shard (see ``repro.core.shard``) and route cursors between them.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.kernels.vbyte_decode.kernel import BLOCK_VALS, BM
from repro.kernels.vbyte_decode.ops import (
    decode_block_rows,
    default_backend,
    default_interpret,
)

INT64_MAX = np.iinfo(np.int64).max


def pow2_bucket(n: int, floor: int = BM) -> int:
    """Power-of-two jit bucket holding ``n`` cursors (floor keeps the pallas
    grid shape legal and bounds the number of distinct traces)."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def stage_cursors(terms, probes, stride: int, bucket: int):
    """Stage cursors into int32 device buffers of size ``bucket``.

    Padding cursors probe list 0 at docID 0.  The probe clip happens BEFORE
    the int32 cast -- see the module docstring (a probe >= 2^31 must clip to
    the maximum key and resolve past-the-end, not wrap negative).
    """
    n = len(terms)
    tp = np.zeros(bucket, np.int32)
    pp = np.zeros(bucket, np.int32)
    tp[:n] = terms
    pp[:n] = np.clip(probes, 0, stride - 1)
    return tp, pp


def group_cursors(terms, probes, stride: int):
    """Group duplicate (term, probe) cursors before a device dispatch.

    Returns ``(idx, inv)`` with ``terms[idx]`` the unique cursors and
    ``inv`` scattering results back, or ``None`` when every cursor is
    already unique.  The clip matches ``stage_cursors``, so grouped and
    ungrouped dispatches see identical staged cursors.
    """
    key = np.clip(probes, 0, stride - 1) + terms * stride
    uk, idx, inv = np.unique(key, return_index=True, return_inverse=True)
    if len(uk) == len(terms):
        return None
    return idx, inv


def locate_graph(block_last, list_blk_offsets, stride, iters, terms, probes):
    """Jitted-graph locate over resident per-block last docIDs.

    Traces int32 cursor arrays into ``(rows, pe, past)``: ``rows`` the
    arena row holding each cursor's answer (clamped in-range), ``pe`` the
    effective probe (0 where past the end), ``past`` the past-the-end
    mask.  Each cursor binary-searches ITS list's block range
    ``[list_blk_offsets[t], list_blk_offsets[t + 1])`` of ``block_last``
    for the first block whose last real docID is >= the probe: the block
    the host's one searchsorted over ``block_keys`` finds, with no key
    that grows with the list count (``n_lists * stride`` passes 2^31 on a
    real index).  ``iters`` (static) is the bit length of the longest
    list's block count.  Every device pipeline -- both engines' jitted
    fns AND the shard_map bodies of ``core.shard`` -- opens with exactly
    this graph; it exists ONCE, here.
    """
    import jax.numpy as jnp

    nb = block_last.shape[0]
    pc = jnp.clip(probes, 0, stride - 1)
    lo = list_blk_offsets[terms]
    end = list_blk_offsets[terms + 1]
    hi = end
    for _ in range(iters):
        mid = (lo + hi) >> 1
        right = (lo < hi) & (block_last[jnp.minimum(mid, nb - 1)] < pc)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    past = lo >= end
    rows = jnp.minimum(lo, nb - 1)
    pe = jnp.where(past, 0, pc)
    return rows, pe, past


def jit_resident(fn, resident: dict, **jit_kw):
    """``jax.jit(fn)``, called as ``fn(resident, *args)``.

    The resident device arrays travel as an argument on every call.  Closed
    over instead, jit would bake them into each compiled program as
    constants: one copy of the arena per cursor bucket, in device and host
    memory alike.
    """
    import functools

    import jax

    return functools.partial(jax.jit(fn, **jit_kw), resident)


def build_locate_dev(arena):
    """``locate_graph`` over one arena, as ``locate(dev, terms, probes)``
    with ``dev`` the arena's resident arrays (``vars(arena.dev)``)."""
    stride, iters = arena.stride, arena.locate_iters

    def locate(dev, terms, probes):
        return locate_graph(
            dev["block_last"], dev["list_blk_offsets"], stride, iters,
            terms, probes,
        )

    return locate


def pivot_graph(qb_g, qmins, nblk_g, backend, interpret):
    """Block-Max pivot selection over GATHERED bound-chunk rows.

    The third single-source jit-graph half, alongside ``locate_graph`` and
    ``bm25_score.ops.score_probe_graph``: the jitted engine pipelines AND
    the ``ShardMapPivot`` body of ``core.shard`` both open their pruning
    dispatch with exactly this graph.  Traces int32 (chunk bound tiles,
    per-lane qmin tiles, valid-lane counts) into ``(compact, count,
    pivot, maxq)`` -- see ``kernels.blockmax_pivot``.  Integer contract,
    so the pallas kernel and the jnp ref are bit-identical.
    """
    import jax.numpy as jnp

    from repro.kernels.blockmax_pivot.kernel import (
        AUX_COUNT,
        AUX_MAXQ,
        AUX_PIVOT,
        PMETA_NBLK,
        pivot_select_blocks,
    )
    from repro.kernels.blockmax_pivot.ref import pivot_select_ref

    if backend == "pallas":
        meta = jnp.zeros((qb_g.shape[0], BLOCK_VALS), jnp.int32)
        meta = meta.at[:, PMETA_NBLK].set(nblk_g)
        out, aux = pivot_select_blocks(qb_g, qmins, meta, interpret=interpret)
        return out, aux[:, AUX_COUNT], aux[:, AUX_PIVOT], aux[:, AUX_MAXQ]
    return pivot_select_ref(qb_g, qmins, nblk_g)


def pivot_score_graph(
    qb_g, qmins, nblk_g, base_g, flens, fdata, norms, idf_rows, table,
    k1p1, slots, backend, interpret,
):
    """Fused pivot + kept-slot scoring over GATHERED bound-chunk rows.

    The fully-resident WAND round (DESIGN.md §13): ``pivot_graph`` plus
    the in-graph gather-and-score of the first ``slots`` surviving blocks
    per chunk, so keep-test, compaction, pivot AND the survivors' scores
    come back from ONE dispatch.  flens/fdata/norms/idf_rows are the FULL
    resident freq arena (gathered in-graph at ``base + compact``); slots
    is a static python int.  Returns ``(compact, count, pivot, maxq,
    sscores)`` -- see ``kernels.pivot_score``.  f32-bit-exact: the pivot
    half is integer and the scoring half is the ``bm25_score`` contract.
    """
    import jax.numpy as jnp

    from repro.kernels.pivot_score.kernel import (
        PS_META_BASE,
        PS_META_NBLK,
        pivot_score_blocks,
    )
    from repro.kernels.pivot_score.ref import pivot_score_ref

    if backend == "pallas":
        from repro.kernels.blockmax_pivot.kernel import (
            AUX_COUNT,
            AUX_MAXQ,
            AUX_PIVOT,
        )

        meta = jnp.zeros((qb_g.shape[0], BLOCK_VALS), jnp.int32)
        meta = meta.at[:, PS_META_NBLK].set(nblk_g)
        meta = meta.at[:, PS_META_BASE].set(base_g)
        out, aux, sscores = pivot_score_blocks(
            qb_g, qmins, meta, flens, fdata, norms, idf_rows, table, k1p1,
            interpret=interpret, slots=slots,
        )
        return (
            out, aux[:, AUX_COUNT], aux[:, AUX_PIVOT], aux[:, AUX_MAXQ],
            sscores,
        )
    return pivot_score_ref(
        qb_g, qmins, nblk_g, base_g, flens, fdata, norms, idf_rows, table,
        k1p1, slots,
    )


@dataclass
class PivotChunks:
    """``block_max_q`` re-tiled into per-list 128-lane chunks (§9).

    The pivot kernel consumes bound CHUNKS -- up to 128 consecutive blocks
    of one list per row -- so the ranked sidecar's flat [n_blocks] u8
    array is re-tiled once per arena into a [n_chunks, 128] int32 table
    plus per-chunk metadata.  Chunks never span lists; a list with b
    blocks owns ceil(b / 128) consecutive chunk rows.
    """

    qb: np.ndarray  # [nc, 128] int32  block_max_q per lane (0 past nblk)
    nblk: np.ndarray  # [nc] int32  valid lanes in the chunk
    base: np.ndarray  # [nc] int64  arena row of lane 0
    offsets: np.ndarray  # [n_lists + 1] int64  chunk range per list
    _dev: object = field(default=None, repr=False, compare=False)

    @property
    def dev(self):
        """jnp copies of the gatherable halves, uploaded once."""
        if self._dev is None:
            import jax.numpy as jnp
            from types import SimpleNamespace

            self._dev = SimpleNamespace(
                qb=jnp.asarray(self.qb), nblk=jnp.asarray(self.nblk)
            )
        return self._dev


def build_pivot_chunks(arena) -> PivotChunks:
    """Re-tile one arena's ``block_max_q`` into ``PivotChunks``."""
    r = arena.ranked
    if r is None:
        raise ValueError("pivot chunks need a ranked arena")
    counts = np.diff(arena.list_blk_offsets)
    nch = -(-counts // BLOCK_VALS)  # ceil: chunks per list
    offsets = np.zeros(len(counts) + 1, np.int64)
    np.cumsum(nch, out=offsets[1:])
    nc = int(offsets[-1])
    if nc == 0:
        return PivotChunks(
            qb=np.zeros((0, BLOCK_VALS), np.int32),
            nblk=np.zeros(0, np.int32),
            base=np.zeros(0, np.int64),
            offsets=offsets,
        )
    list_of_chunk = np.repeat(np.arange(len(counts), dtype=np.int64), nch)
    k_in = np.arange(nc, dtype=np.int64) - offsets[list_of_chunk]
    base = arena.list_blk_offsets[list_of_chunk] + k_in * BLOCK_VALS
    nblk = np.minimum(
        counts[list_of_chunk] - k_in * BLOCK_VALS, BLOCK_VALS
    ).astype(np.int32)
    lane = np.arange(BLOCK_VALS, dtype=np.int64)
    rows = np.minimum(base[:, None] + lane[None, :], arena.n_blocks - 1)
    qb = np.where(
        lane[None, :] < nblk[:, None], r.block_max_q[rows], 0
    ).astype(np.int32)
    return PivotChunks(qb=qb, nblk=nblk, base=base, offsets=offsets)


def decode_rows_values(arena, rows, backend, interpret):
    """[len(rows), 128] absolute docIDs of arena block rows, codec-aware.

    THE host row-decode of the stack: every flat-mirror build, row-cache
    miss, and list decode funnels through here.  Single-codec arenas keep
    the PR 1 path (rows index ``lens``/``data`` directly); multi-codec
    arenas (§14) bucket the rows by ``block_codec`` and decode each
    codec's tiles with its own decoder -- Stream-VByte rows via
    ``decode_block_rows`` + cumsum, EF tiles via ``ef_decode_rows_np`` --
    then scatter back in row order.
    """
    a = arena
    rows = np.asarray(rows, dtype=np.int64)
    if a.block_codec is None:
        gaps = decode_block_rows(
            a.lens[rows], a.data[rows], backend=backend, interpret=interpret
        )
        return a.block_base[rows][:, None] + np.cumsum(gaps + 1, axis=1)
    from repro.core.arena import CODEC_EF
    from repro.kernels.ef_search.ops import ef_decode_rows_np

    out = np.empty((len(rows), BLOCK_VALS), np.int64)
    cr = a.codec_row[rows]
    ef_j = np.nonzero(a.block_codec[rows] == CODEC_EF)[0]
    svb_j = np.nonzero(a.block_codec[rows] != CODEC_EF)[0]
    if len(svb_j):
        r = cr[svb_j]
        gaps = decode_block_rows(
            a.lens[r], a.data[r], backend=backend, interpret=interpret
        )
        out[svb_j] = a.block_base[rows[svb_j]][:, None] + np.cumsum(
            gaps + 1, axis=1
        )
    if len(ef_j):
        r = cr[ef_j]
        out[ef_j] = ef_decode_rows_np(
            a.ef_lo[r], a.ef_hi[r], a.ef_lbits[r], a.block_base[rows[ef_j]]
        )
    return out


def decode_search_graph(lens_g, data_g, base_g, pe, backend, interpret):
    """Fused decode+NextGEQ over GATHERED rows -> (value, rank_in).

    The kernel-dispatch epilogue shared by the jitted engine pipelines and
    the shard_map bodies: pallas stages (base, probe) into the META lanes,
    ref calls the jnp oracle.  Bit-identical across backends.
    """
    import jax.numpy as jnp

    from repro.kernels.vbyte_decode.kernel import (
        META_BASE,
        META_PROBE,
        decode_search_blocks,
    )
    from repro.kernels.vbyte_decode.ref import decode_search_ref

    if backend == "pallas":
        meta = jnp.zeros((pe.shape[0], BLOCK_VALS), jnp.int32)
        meta = meta.at[:, META_BASE].set(base_g)
        meta = meta.at[:, META_PROBE].set(pe)
        out = decode_search_blocks(lens_g, data_g, meta, interpret=interpret)
        return out[:, 0], out[:, 1]
    return decode_search_ref(lens_g, data_g, base_g, pe)


def ef_search_graph(lo_g, hi_g, lbits_g, base_g, pe, backend, interpret):
    """Fused Elias-Fano NextGEQ over GATHERED EF tiles -> (value, rank_in).

    ``decode_search_graph``'s twin for the EF half of a multi-codec arena
    (§14): same (value, rank) output contract, same staging discipline --
    pallas packs the high words + per-row scalars into the META tile, ref
    calls the jnp oracle.  Integer contract, bit-identical across
    backends.
    """
    import jax.numpy as jnp

    from repro.kernels.ef_search.kernel import (
        EF_HI_WORDS,
        EFMETA_BASE,
        EFMETA_LBITS,
        EFMETA_PROBE,
        ef_search_blocks,
    )
    from repro.kernels.ef_search.ref import ef_search_ref

    if backend == "pallas":
        meta = jnp.zeros((pe.shape[0], BLOCK_VALS), jnp.int32)
        meta = meta.at[:, :EF_HI_WORDS].set(hi_g)
        meta = meta.at[:, EFMETA_LBITS].set(lbits_g)
        meta = meta.at[:, EFMETA_BASE].set(base_g)
        meta = meta.at[:, EFMETA_PROBE].set(pe)
        out = ef_search_blocks(lo_g, meta, interpret=interpret)
        return out[:, 0], out[:, 1]
    return ef_search_ref(lo_g, hi_g, lbits_g, base_g, pe)


# Identity registry of the single-source jit-graph halves, checked by the
# HLO sanitizer (repro.analyze.hlo_check; DESIGN.md §10).  "integer" graphs
# must lower to float-free optimized HLO; "f32-bit-exact" graphs may use f32
# but no contracted multiply-add (FMA reassociates the op order the triple
# contract pins) and no dot contractions beyond the allow-list (the one-hot
# norm-dequant matmul over the 256-entry table -- see bm25.norm_table).
GRAPH_CONTRACTS = {
    "locate_graph": {
        "module": "repro.core.engine_core",
        "identity": "integer",
    },
    "decode_search_graph": {
        "module": "repro.core.engine_core",
        "identity": "integer",
    },
    "ef_search_graph": {
        "module": "repro.core.engine_core",
        "identity": "integer",
    },
    "pivot_graph": {
        "module": "repro.core.engine_core",
        "identity": "integer",
    },
    "score_probe_graph": {
        "module": "repro.kernels.bm25_score.ops",
        "identity": "f32-bit-exact",
        "allow_dot_contractions": [256],
    },
    "score_rows_graph": {
        "module": "repro.kernels.bm25_score.ops",
        "identity": "f32-bit-exact",
        "allow_dot_contractions": [256],
    },
    "pivot_score_graph": {
        "module": "repro.core.engine_core",
        "identity": "f32-bit-exact",
        "allow_dot_contractions": [256],
    },
}


class EngineCore:
    """Flat-mirror / locate / dispatch machinery over ONE ``DeviceArena``.

    Parameters
    ----------
    arena: the ``DeviceArena`` to serve (global, or one shard's sub-arena).
    backend: "auto" | "numpy" | "ref" | "pallas" -- decode path.
    cache_parts / cache_bytes: bounds of the decoded-row LRU; cache_bytes
        also gates the flat mirror (None = unbudgeted, always build it).
    mirror_backend: backend used to DECODE the flat mirror (None = same as
        ``backend``; TopKEngine passes "numpy" -- values are exact ints and
        the mirror is a host structure whatever the scoring backend).
    lane_scores_fn: optional ``() -> [n_blocks, 128] float32`` scoring every
        arena lane; when given, ``flat_init`` masks padding lanes to 0 and
        keeps the flat per-lane score mirror (TopKEngine's impact mirror).
    stats: optional dict to count into (an engine shares its stats dict so
        existing counters keep working); missing keys are created.
    """

    def __init__(
        self,
        arena,
        backend: str = "auto",
        cache_parts: int = 32_768,
        cache_bytes: int | None = None,
        mirror_backend: str | None = None,
        lane_scores_fn=None,
        stats: dict | None = None,
        shard_id: int | None = None,
        injector=None,
    ):
        self.arena = arena
        # host-loop shard-dispatch fault boundary (ISSUE-7): when this core
        # serves one shard of a ShardedArena, a ShardFaultInjector is
        # consulted at every fused dispatch -- the host-loop mirror of the
        # shard_map dispatchers' check
        self.shard_id = shard_id
        self.injector = injector
        self.backend = default_backend() if backend == "auto" else backend
        # interpret mode only off-accelerator: on TPU/GPU the pallas backend
        # must COMPILE the kernel, not emulate it
        self.interpret = default_interpret()
        self.cache_parts = int(cache_parts)
        self.cache_bytes = None if cache_bytes is None else int(cache_bytes)
        self.mirror_backend = mirror_backend or self.backend
        self.lane_scores_fn = lane_scores_fn
        # stats stays a plain-dict interface for callers/tests; the
        # CounterDict default mirrors increments onto obs counters when the
        # observability layer is armed (compat shim, DESIGN.md §12)
        self.stats = stats if stats is not None else obs.CounterDict("engine")
        for key in ("decoded_rows", "kernel_calls", "cache_hits", "evictions",
                    "device_round_trips"):
            self.stats.setdefault(key, 0)
        self.cache: OrderedDict = OrderedDict()
        self.cache_nbytes = 0
        # flat mirror: decoded lane values + global lane keys (+ scores)
        self.flat_vals: np.ndarray | None = None
        self.flat_keys: np.ndarray | None = None
        self.flat_scores: np.ndarray | None = None
        self.lane_end: np.ndarray | None = None
        self.flat_ok = None  # None = undecided, False = budget refused
        self._jax_fn = None
        self._ef_jax_fn = None

    # ------------------------------------------------------------------
    # LRU cache (decoded rows / partitions / lists), byte- and count-bounded
    # ------------------------------------------------------------------
    def cache_get(self, key):
        """Cached array for ``key`` (LRU-touched, hit-counted) or None."""
        got = self.cache.get(key)
        if got is not None:
            self.cache.move_to_end(key)
            self.stats["cache_hits"] += 1
        return got

    def cache_put(self, key, arr: np.ndarray) -> None:
        old = self.cache.pop(key, None)
        if old is not None:
            self.cache_nbytes -= old.nbytes
        self.cache[key] = arr
        self.cache_nbytes += arr.nbytes
        limit = np.inf if self.cache_bytes is None else self.cache_bytes
        while self.cache and (
            len(self.cache) > self.cache_parts or self.cache_nbytes > limit
        ):
            _, ev = self.cache.popitem(last=False)
            self.cache_nbytes -= ev.nbytes
            self.stats["evictions"] += 1

    # ------------------------------------------------------------------
    # host flat mirror: decoded lane docIDs + lane keys (+ lane scores)
    # ------------------------------------------------------------------
    def flat_init(self) -> bool:
        """Decode the arena once into flat (values, lane keys[, scores]).

        Lane keys extend the arena's block keys to lane granularity with the
        padding clamp described in the module docstring; one searchsorted
        over them subsumes BOTH locate steps.  Gated on ``cache_bytes``
        (2 x 1 KiB per block) when a budget is set.
        """
        if self.flat_keys is None and self.flat_ok is None:
            a = self.arena
            if (
                self.cache_bytes is not None
                and 2 * a.n_blocks * BLOCK_VALS * 8 > self.cache_bytes
            ):
                self.flat_ok = False  # budget refused: per-call decode
                return False
            with obs.span("flat_init", backend=self.mirror_backend):
                vals = decode_rows_values(
                    a,
                    np.arange(a.n_blocks, dtype=np.int64),
                    backend=self.mirror_backend,
                    interpret=self.interpret,
                )
            self.stats["kernel_calls"] += 1
            self.stats["decoded_rows"] += a.n_blocks
            # one sentinel lane so a past-the-end searchsorted result is
            # still a valid gather index (masked via lane_end afterwards)
            self.flat_vals = np.append(vals.reshape(-1), -1)
            list_of_block = a.part_list[a.part_of_block]
            self.flat_keys = np.append(
                np.minimum(
                    vals + (list_of_block * a.stride)[:, None],
                    a.block_keys[:, None],
                ).reshape(-1),
                INT64_MAX,
            )
            self.lane_end = a.list_blk_offsets * BLOCK_VALS
            if self.lane_scores_fn is not None and a.n_blocks:
                scores = np.where(a.lane_valid, self.lane_scores_fn(), np.float32(0.0))
                self.flat_scores = np.append(
                    scores.reshape(-1).astype(np.float32), np.float32(0.0)
                )
            if self.cache_bytes is not None:
                # the flat arrays spend part of the decoded-bytes budget:
                # LRU entries (decoded rows / lists) only get the remainder
                self.cache_nbytes += self.flat_vals.nbytes + self.flat_keys.nbytes
            self.flat_ok = True
        return bool(self.flat_ok)

    def rows_values(self, rows: np.ndarray) -> np.ndarray:
        """[len(rows), 128] absolute docIDs of the given (unique) rows.

        With the flat arena refused (over ``cache_bytes``), decoded rows go
        through the byte-budgeted LRU under ``("row", r)`` keys -- the
        dense row cache of the fused CPU path.  Rows the budget cannot hold
        are decoded, served, and dropped, with every drop counted in
        ``stats["evictions"]`` like any other cache eviction.
        """
        a = self.arena
        if self.flat_init():
            return self.flat_vals[:-1].reshape(-1, BLOCK_VALS)[rows]
        rows = np.asarray(rows, dtype=np.int64)
        out = np.empty((len(rows), BLOCK_VALS), np.int64)
        miss_j: list[int] = []
        for j, rr in enumerate(rows):
            got = self.cache_get(("row", int(rr)))
            if got is None:
                miss_j.append(j)
            else:
                out[j] = got
        if miss_j:
            miss_rows = rows[miss_j]
            vals = decode_rows_values(
                a, miss_rows, backend=self.backend, interpret=self.interpret
            )
            self.stats["kernel_calls"] += 1
            self.stats["decoded_rows"] += len(miss_rows)
            out[miss_j] = vals
            # cache at most a budget's worth of this batch's rows (the
            # most recently decoded): caching a miss set larger than the
            # budget would evict every entry before it could ever be
            # re-hit -- pure churn.  copy(): a view would pin the whole
            # batch's vals base array and void the byte accounting.
            bb = self.cache_bytes if self.cache_bytes is not None else 0
            cap = max(int(bb // (BLOCK_VALS * 8)), 1)
            for j in range(max(len(miss_rows) - cap, 0), len(miss_rows)):
                self.cache_put(("row", int(miss_rows[j])), vals[j].copy())
        return out

    def decode_list(self, t: int) -> np.ndarray:
        """All real docIDs of (local) list ``t``, via the LRU cache."""
        key = ("list", int(t))
        got = self.cache_get(key)
        if got is not None:
            return got
        a = self.arena
        r0 = int(a.list_blk_offsets[t])
        r1 = int(a.list_blk_offsets[t + 1])
        if r0 == r1:
            return np.zeros(0, np.int64)
        rows = np.arange(r0, r1, dtype=np.int64)
        vals = self.rows_values(rows)
        out = vals.reshape(-1)[a.lane_valid[r0:r1].reshape(-1)]
        self.cache_put(key, out)
        return out

    # ------------------------------------------------------------------
    # fused locate -> resolve, host (numpy) path
    # ------------------------------------------------------------------
    def search_np(self, terms, probes, with_rank: bool = True, trusted: bool = False):
        """Host (numpy) fused pipeline: one searchsorted per batch.

        Returns UNMASKED (value, rank, past): callers apply their own mask
        (-1 fill for NextGEQ, ``& ~past`` for membership) so the membership
        hot loop skips the rank arithmetic entirely (``with_rank=False``).
        ``trusted`` skips the probe clip for probes that are known decoded
        docIDs (the AND filter feeds candidates straight back in).

        With the flat lane keys resident, locate AND in-partition resolve
        collapse into a single searchsorted plus O(1) gathers per cursor.
        Without them (arena over the byte budget), a two-level variant
        locates blocks first and decodes only the unique touched rows.
        """
        a = self.arena
        pc = probes if trusted else np.clip(probes, 0, a.stride - 1)
        pk = pc + terms * a.stride
        if self.flat_init():
            self.stats["cache_hits"] += len(terms)
            pos = np.searchsorted(self.flat_keys, pk, side="left")
            past = pos >= self.lane_end[terms + 1]
            value = self.flat_vals[pos]  # sentinel lane keeps pos in range
            rank = None
            if with_rank:
                rows = np.minimum(pos, len(self.flat_keys) - 2) >> 7
                rank = pos - (a.first_blk[a.part_of_block[rows]] << 7)
            return value, rank, past
        k = np.searchsorted(a.block_keys, pk, side="left")
        past = k >= a.list_blk_offsets[terms + 1]
        rows = np.minimum(k, a.n_blocks - 1)
        pe = np.where(past, 0, pc)
        urows, inv = np.unique(rows, return_inverse=True)
        vals_u = self.rows_values(urows)  # [U, 128]
        base_u = a.block_base[urows]
        # rebased lane values are in [1, stride + 127]; stride2 clears them
        stride2 = a.stride + BLOCK_VALS + 2
        lane_keys = (
            vals_u - base_u[:, None]
            + np.arange(len(urows), dtype=np.int64)[:, None] * stride2
        ).reshape(-1)
        probe_keys = np.maximum(pe - base_u[inv], 1) + inv * stride2
        pos = np.searchsorted(lane_keys, probe_keys, side="left")
        value = vals_u.reshape(-1)[pos]
        rank = None
        if with_rank:
            rank_in = pos - inv * BLOCK_VALS
            part = a.part_of_block[rows]
            rank = (rows - a.first_blk[part]) * BLOCK_VALS + rank_in
        return value, rank, past

    # ------------------------------------------------------------------
    # fused locate -> decode_search, jitted device path
    # ------------------------------------------------------------------
    def _build_jax_fn(self):
        import jax.numpy as jnp

        multi = self.arena.block_codec is not None
        locate = build_locate_dev(self.arena)
        backend, interpret = self.backend, self.interpret

        def fn(dev, terms, probes):
            rows, pe, past = locate(dev, terms, probes)
            # multi-codec arenas store SVB tiles compacted: the gather goes
            # through codec_row (EF blocks alias row 0, but every cursor
            # reaching this fn was bucketed onto an SVB block by the host)
            sr = dev["codec_row"][rows] if multi else rows
            value, rank_in = decode_search_graph(
                dev["lens"][sr],
                dev["data"][sr],
                dev["block_base"][rows],
                pe,
                backend,
                interpret,
            )
            part = dev["part_of_block"][rows]
            rank = (rows - dev["first_blk"][part]) * BLOCK_VALS + rank_in
            return jnp.where(past, -1, value), jnp.where(past, -1, rank)

        return jit_resident(fn, vars(self.arena.dev))

    def _build_ef_jax_fn(self):
        """Jitted locate -> EF-NextGEQ pipeline (multi-codec arenas, §14).

        The EF twin of ``_build_jax_fn``: same locate graph, same rank
        arithmetic, ``ef_search_graph`` in place of ``decode_search_graph``
        with the tile gather routed through ``codec_row``.
        """
        import jax.numpy as jnp

        locate = build_locate_dev(self.arena)
        backend, interpret = self.backend, self.interpret

        def fn(dev, terms, probes):
            rows, pe, past = locate(dev, terms, probes)
            er = dev["codec_row"][rows]
            value, rank_in = ef_search_graph(
                dev["ef_lo"][er],
                dev["ef_hi"][er],
                dev["ef_lbits"][er],
                dev["block_base"][rows],
                pe,
                backend,
                interpret,
            )
            part = dev["part_of_block"][rows]
            rank = (rows - dev["first_blk"][part]) * BLOCK_VALS + rank_in
            return jnp.where(past, -1, value), jnp.where(past, -1, rank)

        return jit_resident(fn, vars(self.arena.dev))

    def _dispatch_jax(self, fn, terms, probes):
        """Stage one cursor bucket and run one jitted pipeline over it."""
        import jax.numpy as jnp

        n = len(terms)
        with obs.span("stage"):
            tp, pp = stage_cursors(
                terms, probes, self.arena.stride, pow2_bucket(n)
            )
        value, rank = fn(jnp.asarray(tp), jnp.asarray(pp))
        self.stats["device_round_trips"] += 1
        with obs.span("fetch"):
            return (
                np.asarray(value)[:n].astype(np.int64),
                np.asarray(rank)[:n].astype(np.int64),
            )

    def search_jax(self, terms, probes):
        """Device fused pipeline, jitted end-to-end over the resident arena.

        Cursor counts are padded to power-of-two buckets so jit traces are
        reused across batches; padding cursors probe list 0 at docID 0 and
        are sliced away.  One host sync at the end (the result fetch).

        Multi-codec arenas add a HOST pre-pass: the same searchsorted that
        the device pipeline opens with, run once on the host purely to read
        each located block's ``block_codec`` tag, buckets the cursors per
        codec; then ONE fused dispatch per codec per wave resolves its
        bucket (each jitted fn re-locates on device -- the graphs stay
        single-source and the HLO contracts unchanged).  The scatter back
        into batch order is pure indexing, so results are independent of
        the codec split -- bit-identical to the single-codec arena.
        """
        a = self.arena
        if self._jax_fn is None:
            self._jax_fn = self._build_jax_fn()
        if a.block_codec is None:
            return self._dispatch_jax(self._jax_fn, terms, probes)
        from repro.core.arena import CODEC_EF

        with obs.span("codec_split"):
            terms = np.asarray(terms, dtype=np.int64)
            probes = np.asarray(probes, dtype=np.int64)
            pc = np.clip(probes, 0, a.stride - 1)
            k = np.searchsorted(
                a.block_keys, pc + terms * a.stride, side="left"
            )
            codec = a.block_codec[np.minimum(k, a.n_blocks - 1)]
            ef_j = np.nonzero(codec == CODEC_EF)[0]
        n = len(terms)
        if not len(ef_j):
            return self._dispatch_jax(self._jax_fn, terms, probes)
        if self._ef_jax_fn is None:
            self._ef_jax_fn = self._build_ef_jax_fn()
        if len(ef_j) == n:
            return self._dispatch_jax(self._ef_jax_fn, terms, probes)
        svb_j = np.nonzero(codec != CODEC_EF)[0]
        value = np.empty(n, np.int64)
        rank = np.empty(n, np.int64)
        value[svb_j], rank[svb_j] = self._dispatch_jax(
            self._jax_fn, terms[svb_j], probes[svb_j]
        )
        value[ef_j], rank[ef_j] = self._dispatch_jax(
            self._ef_jax_fn, terms[ef_j], probes[ef_j]
        )
        return value, rank

    @property
    def use_device(self) -> bool:
        return self.backend in ("ref", "pallas")

    def fused_search(
        self, terms, probes, with_rank: bool = True, trusted: bool = False
    ):
        """One fused dispatch over THIS arena: (value, rank, past).

        value/rank are meaningful only where ``~past`` (the device pipeline
        pre-masks them to -1, which is equivalent for every caller).
        """
        if self.injector is not None and self.shard_id is not None:
            self.injector.check(self.shard_id)
        if self.shard_id is not None:
            obs.count("shard_dispatch", shard=str(self.shard_id), path="host_loop")
        if self.use_device:
            with obs.span("decode_search", backend=self.backend):
                value, rank = self.search_jax(terms, probes)
            return value, rank, value < 0
        with obs.span("decode_search", backend="numpy"):
            return self.search_np(terms, probes, with_rank, trusted)
