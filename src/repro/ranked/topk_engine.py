"""Batched Block-Max BM25 top-k engine over the ranked arena (DESIGN.md §5).

Serves MANY disjunctive top-k queries per call with Block-Max WAND/MaxScore
pruning over the arena's quantized per-block score upper bounds, while
guaranteeing results IDENTICAL to the exhaustive-scoring oracle
(``repro.ranked.bm25.exhaustive_topk``): same docIDs, same scores, ties
broken by ascending docID.

Phases per batch (all bound arithmetic in float64 over the f32 contract
values, so it is exact):

1. **Seed.**  Per query, the docs of each term's ``seed_blocks``
   highest-bounded blocks are scored fully; theta = their k-th best true
   score.  Any valid lower bound works; covering every term catches the
   multi-term docs that dominate disjunctive top-k.

2. **Generate** (the block-max pivot, batched).  For every block b of
   every query term t, an ALIGNED upper bound: own bound plus, per other
   term, the max bound of its blocks overlapping b's docID span (an O(1)
   sparse-table range-max).  Surviving blocks emit candidates, lane-exactly
   filtered where the impact mirror is resident (aligned-bound and
   proportional-share tests on the lane's true contribution).  Every doc
   with score >= theta provably survives through each block containing it.

3. **Rescore + select** (threshold+compact, two rounds).  ONE membership
   pass (per query term, a searchsorted of the candidates in that term's
   own lane of the flat lane keys) resolves every (term, candidate) pair
   and yields doc-aligned upper bounds from the block-max sidecar.  Round
   A exact-scores the highest-UB docs and raises theta to their k-th true
   score; round B scores only the remaining docs whose UB clears the
   raised theta.  Member-pair contributions come from the impact mirror
   (``resident="mirror"``) or the fused decode+score kernel over the
   unique touched rows (``resident="kernel"``, the HBM-resident
   accelerator path).  Per-doc sums accumulate in float64 --
   exact and order-free, because the f32 contributions span far less than
   f64's 29 bits of headroom -- then (score desc, docID asc) cuts to k.

The per-doc reduction and final selection stay on the host ON PURPOSE: jax
accumulates f32 by default, and an order-dependent 1-ulp drift there could
flip near-tied docs -- breaking the "identical top-k" contract that makes
the exhaustive oracle a usable correctness harness.  The fused
``bm25_score_probe`` pipeline (jitted locate -> gather -> decode+score+match
over the resident arena) serves the point-lookup ``contributions()`` API.

The flat lane mirror, the lane-key padding clamp, the pow2 staging, and the
int32 probe clip all come from the shared ``core.engine_core.EngineCore``
(the same machinery ``QueryEngine`` runs on).  With ``shards=N`` the
contributions hot path routes (term, doc) cursors to per-shard sub-arenas
(``core.shard.ShardedArena``) and runs the fused bm25 kernel per shard --
under one ``shard_map`` dispatch when a mesh with one device per shard
exists -- while the merge stays a pure scatter: only f32 contributions
cross the boundary, so the sharded engine is bit-identical to the
unsharded one.

Residency decides WHERE phase 2 runs (DESIGN.md §9).  ``"mirror"`` keeps
the host impact mirror and prunes with the range-aligned RMQ bounds plus
lane-exact filters above.  ``"kernel"`` -- the HBM-resident accelerator
configuration -- runs the pruning itself through the third kernel family
(``kernels/blockmax_pivot``): theta and the per-term upper bounds reduce
to ONE integer per (query, term) on the host (the minimal admissible
bound code, float64-exact), and the device keeps/compacts the candidate
blocks of every term of every query in one dispatch over the resident
``block_max_q`` chunk tiles -- sharded, the qmins broadcast to every
shard's cursors and the kept blocks scatter back through
``ShardedArena.rows_of``, so per-round pruning never syncs the mesh.  The
kept sets are identical across backends and shard counts (the integer
test is exactly the float test), and the final top-k is identical to the
oracle in every mode because rescoring is exact wherever candidate
generation is admissible.
"""

from __future__ import annotations

import numpy as np

from repro import obs
from repro.api import UNSET, coerce_config
from repro.core.engine_core import (
    EngineCore,
    build_locate_dev,
    build_pivot_chunks,
    group_cursors,
    jit_resident,
    pow2_bucket,
    stage_cursors,
)
from repro.kernels.blockmax_pivot.kernel import QMIN_NONE
from repro.kernels.blockmax_pivot.ops import (
    dequant_table,
    pivot_select,
    qmin_for,
)
from repro.kernels.bm25_score.ops import bm25_score_rows
from repro.kernels.pivot_score.kernel import SCORE_SLOTS
from repro.kernels.vbyte_decode.kernel import BLOCK_VALS
from repro.kernels.vbyte_decode.ops import default_interpret
from repro.ranked.bm25 import topk_select


def default_resident() -> str:
    """What ``resident="auto"`` resolves to: "kernel" on an accelerator,
    "mirror" where Pallas only interprets."""
    return "mirror" if default_interpret() else "kernel"


class TopKEngine:
    """Batched BM25 top-k over one freq-carrying ``PartitionedIndex``.

    Parameters
    ----------
    index: a ``PartitionedIndex`` built with ``freqs=`` (the arena must
        carry the ranked sidecar).
    backend: "auto" | "numpy" | "ref" | "pallas" -- scoring path; "auto"
        resolves via the shared ``default_backend()``.
    seed_blocks: how many highest-bounded blocks of each query term seed
        the pruning threshold (more = tighter theta, costlier seed).
    resident: "mirror" | "kernel" | "auto".  "mirror" scores the arena ONCE
        into a host per-lane impact mirror (through the chosen backend's
        kernel -- all backends are bit-identical) and serves batches from
        it, which also enables lane-exact candidate filtering; "kernel"
        keeps only compressed blocks + bound tiles resident, runs the
        Block-Max pruning itself through the ``blockmax_pivot`` kernel
        (DESIGN.md §9) and re-scores the touched rows through the fused
        bm25 kernel every batch -- the HBM-resident accelerator
        configuration.  "auto" picks "kernel" on a real accelerator,
        "mirror" elsewhere.  Both return the oracle's exact top-k.
    shards: list-hash-partition the arena and route the device
        contributions dispatch per shard (see module docstring).  None =
        unsharded.
    shard_mesh: "auto" | None | a Mesh with a "shard" axis, as in
        ``QueryEngine``.
    replicas: copies of each list across shards (``core.shard``); routing
        prefers the primary, so R > 1 is invisible until shards die and
        their lists fail over -- bit-identically (pure-scatter merge).
    fault_injector: optional ``ShardFaultInjector`` consulted at every
        shard dispatch, normally wired by ``ResilientEngine``.
    """

    def __init__(self, index, backend=UNSET, seed_blocks: int = 4,
                 resident=UNSET, shards=UNSET, shard_mesh=UNSET,
                 replicas=UNSET, fault_injector=UNSET, codec_policy=UNSET,
                 config=None, **kwargs):
        # one coercion point for config= + legacy keywords (repro.api);
        # unknown keywords now raise instead of being silently ignored
        cfg = coerce_config(
            "TopKEngine",
            config,
            dict(
                backend=backend, resident=resident, shards=shards,
                shard_mesh=shard_mesh, replicas=replicas,
                fault_injector=fault_injector, codec_policy=codec_policy,
            ),
            kwargs,
        )
        self.config = cfg
        backend, resident = cfg.backend, cfg.resident
        shards, shard_mesh = cfg.shards, cfg.shard_mesh
        replicas, fault_injector = cfg.replicas, cfg.fault_injector
        self.index = index
        self.arena = (
            index.arena_for(cfg.codec_policy)
            if hasattr(index, "arena_for")
            else index.arena
        )
        if self.arena.ranked is None:
            raise ValueError(
                "index has no ranked sidecar: build with freqs= "
                "(build_partitioned_index(lists, freqs=...))"
            )
        self.ranked = self.arena.ranked
        # CounterDict: plain-dict reads for callers/tests, and every numeric
        # increment mirrors onto an obs counter when the layer is armed
        # (EngineCore shares this dict, so its cache/kernel counters land
        # under the same ``ranked_*`` prefix)
        self.stats = obs.CounterDict(
            "ranked",
            {
                "batches": 0,
                "seed_pairs": 0,
                "scored_pairs": 0,
                "candidates": 0,
                "ub_filtered": 0,
                "scored_rows": 0,
                "blocks_kept": 0,
                "blocks_total": 0,
                "pivot_chunks": 0,
                "score_evictions": 0,  # hot-block score cache flushes (rows)
                "fused_pivot_chunks": 0,  # cursors through pivot_score (§13)
                "theta_device_rounds": 0,  # device-carried theta rounds
                "device_round_trips": 0,  # blocking device->host fetches
                "membership_pairs": 0,  # (term, candidate) pairs searched
            },
            engine="topk",
        )
        a, r = self.arena, self.ranked
        self.k1p1 = np.float32(r.params.k1 + 1.0)
        self.lob = a.part_list[a.part_of_block]  # owning list per block
        self.bounds = r.block_bounds().astype(np.float64)  # [nb]
        self.list_ub = r.list_ub.astype(np.float64)        # [n_lists]
        if resident == "auto":
            resident = default_resident()
        if resident not in ("mirror", "kernel"):
            raise ValueError(f"unknown resident mode {resident!r}")
        self.resident = resident
        self.seed_blocks = int(seed_blocks)
        # shared flat-mirror/locate machinery: the doc/key mirror is a HOST
        # structure, decoded with the numpy mirror whatever the scoring
        # backend (values are exact ints); the per-lane impact mirror rides
        # along under resident="mirror"
        self.core = EngineCore(
            a, backend=backend, cache_bytes=None, mirror_backend="numpy",
            lane_scores_fn=(
                self._lane_scores if resident == "mirror" else None
            ),
            stats=self.stats,
        )
        self.backend = self.core.backend
        self.interpret = self.core.interpret
        # per-codec jitted contrib fns of the global arena ("svb" always,
        # "ef" filled on the first EF-bucketed wave of a multi-codec arena)
        self._jax_fns: dict = {}
        self.sharded = None
        self._shard_fns: list = []  # per shard: per-codec fn dict (or None)
        self._smap_fn = None
        # device-pivot state (resident="kernel"): bound-chunk tiles + the
        # f64 dequant table behind the exact theta -> qmin reduction
        self._deq64 = dequant_table(r.bound_scale)
        self._pchunks = None
        self._pivot_fn = None
        self._shard_pivot_fns: list = []
        self._smap_pivot = None
        # fully-resident round state (DESIGN.md §13): the fused pivot+score
        # dispatch, the resident row scorer, and the device theta round
        self._pivot_score_fn = None
        self._rowscore_fn = None
        self._idf_blk = None
        self._theta_fn = None
        self._scache_rows = np.zeros(0, np.int64)  # sorted hot rows
        self._scache = np.zeros((0, BLOCK_VALS), np.float32)
        self.fault_injector = fault_injector
        if shards is not None:
            from repro.core.shard import ShardedArena

            self.sharded = ShardedArena.build(
                self.arena, int(shards), mesh=shard_mesh,
                replicas=int(replicas),
            )
            self._shard_fns = [None] * self.sharded.n_shards
            self._shard_pivot_fns = [None] * self.sharded.n_shards

    def _check_shard(self, s: int) -> None:
        """Host-loop shard-dispatch fault boundary (the shard_map
        dispatchers and per-shard EngineCores carry their own check)."""
        if self.fault_injector is not None:
            self.fault_injector.check(s)
        obs.count("shard_dispatch", shard=str(s), path="ranked")

    @staticmethod
    def _note_theta(theta) -> None:
        """Theta-trajectory gauge: the batch's max raised threshold (the
        tightest pruning bound the two-round rescore reached)."""
        if theta is None or not obs.enabled():
            return
        finite = theta[np.isfinite(theta)]
        if len(finite):
            obs.set_gauge("ranked_theta_max", float(finite.max()))

    def _lane_scores(self) -> np.ndarray:
        """The impact mirror: every lane scored ONCE through the chosen
        backend's kernel (bit-identical across backends)."""
        a, r = self.arena, self.ranked
        return bm25_score_rows(
            r.freq_lens, r.freq_data, r.norm_q,
            np.arange(a.n_blocks, dtype=np.int64), r.idf[self.lob],
            r.norm_table, self.k1p1,
            backend=self.backend, interpret=self.interpret,
        )

    # ------------------------------------------------------------------
    # host flat mirror (shared EngineCore): decoded docIDs + lane scores
    # ------------------------------------------------------------------
    def _flat_init(self) -> None:
        self.core.flat_init()

    def _block_docs(self, rows: np.ndarray) -> np.ndarray:
        """Real docIDs of the given arena rows (flat mirror)."""
        self._flat_init()
        vals = self.core.flat_vals[:-1].reshape(-1, BLOCK_VALS)[rows]
        return vals[self.arena.lane_valid[rows]]

    def _block_docs_filtered(
        self, rows: np.ndarray, rest: np.ndarray, mult_t: float,
        theta: float, share: float,
    ) -> np.ndarray:
        """docIDs of the rows that can still reach theta, lane-exactly.

        With the impact mirror resident, the generating term's contribution
        per lane is KNOWN, and a candidate only materializes when BOTH
        admissible tests pass on its true contribution c = mult_t * score:

        * aligned-bound test: ``c + rest(row) >= theta`` with rest the
          co-located block-max bound of the other terms;
        * proportional-share test: ``c >= share`` where share =
          theta * ub_t / total_ub -- a doc with score >= theta must beat
          its proportional share in SOME term (else summing the per-term
          shortfalls contradicts score >= theta), and this generator runs
          once per term, so the doc materializes where it does.

        This keeps candidate sets near the per-doc truth instead of
        128 x surviving blocks.
        """
        self._flat_init()
        if len(rows) == 0:
            return np.zeros(0, np.int64)
        vals = self.core.flat_vals[:-1].reshape(-1, BLOCK_VALS)[rows]
        lv = self.arena.lane_valid[rows]
        scores = self.core.flat_scores
        if scores is None or not np.isfinite(theta):
            return vals[lv]
        c = mult_t * scores[:-1].reshape(-1, BLOCK_VALS)[rows]
        ok = lv & (c + rest[:, None] >= theta) & (c >= share)
        return vals[ok]

    # ------------------------------------------------------------------
    # range-max over block bounds (sparse table; built once per engine)
    # ------------------------------------------------------------------
    def _rmq_init(self) -> None:
        """st[l][i] = max(bounds[i : i + 2^l]) -- O(nb log nb) once, O(1)
        per range query; the structure behind the aligned pivot test."""
        if getattr(self, "_rmq", None) is not None:
            return
        nb = max(self.arena.n_blocks, 1)
        levels = max(int(nb - 1).bit_length(), 1)
        st = np.full((levels, nb), 0.0)
        st[0, : self.arena.n_blocks] = self.bounds
        for l in range(1, levels):
            half = 1 << (l - 1)
            st[l, : nb - (1 << l) + 1] = np.maximum(
                st[l - 1, : nb - (1 << l) + 1],
                st[l - 1, half : nb - (1 << l) + 1 + half],
            )
        self._rmq = st

    def _rmq_max(self, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
        """max(bounds[lo:hi]) per element; 0.0 for empty ranges."""
        self._rmq_init()
        nb = self._rmq.shape[1]
        length = hi - lo
        ok = length > 0
        ln = np.maximum(length, 1)
        lvl = np.frexp(ln.astype(np.float64))[1] - 1  # floor(log2(len))
        lo_s = np.clip(lo, 0, nb - 1)
        hi_s = np.clip(np.maximum(hi - (1 << lvl), lo), 0, nb - 1)
        m = np.maximum(self._rmq[lvl, lo_s], self._rmq[lvl, hi_s])
        return np.where(ok, m, 0.0)

    def _aligned_rest(self, terms, mult):
        """Per term j: (rows, rest) over every block of list terms[j].

        ``rest[b] = sum_{j2 != j} mult[j2] * max bound of the terms[j2]-
        blocks overlapping b's docID span`` (an O(1) sparse-table
        range-max per pair) -- the range-aligned co-candidate bound behind
        BOTH residencies' pruning: the mirror path tests ``mult[j] *
        bound(b) + rest(b) >= theta`` directly, the kernel path reduces
        the identical test to per-block integer codes (``qmin_for``).
        The own-term bound is deliberately NOT folded in; every term of
        the sum is an exact float64 over f32 contract values, so the sum
        is exact and the two residencies prune bit-identically.
        """
        a = self.arena
        out = []
        for j, t in enumerate(terms):
            t = int(t)
            r0 = int(a.list_blk_offsets[t])
            r1 = int(a.list_blk_offsets[t + 1])
            rows = np.arange(r0, r1, dtype=np.int64)
            lo = a.block_base[rows] + 1  # first docID a block can hold
            hi = a.block_keys[rows] - t * a.stride  # last real docID
            rest = np.zeros(len(rows), np.float64)
            for j2, t2 in enumerate(terms):
                if j2 == j:
                    continue
                t2 = int(t2)
                s1 = int(a.list_blk_offsets[t2 + 1])
                ks = np.searchsorted(
                    a.block_keys, lo + t2 * a.stride, side="left"
                )
                ke = np.searchsorted(
                    a.block_keys, hi + t2 * a.stride, side="left"
                )
                rest += mult[j2] * self._rmq_max(ks, np.minimum(ke + 1, s1))
            out.append((rows, rest))
        return out

    # ------------------------------------------------------------------
    # device Block-Max pivot (resident="kernel"): candidate blocks via
    # the blockmax_pivot kernel over resident bound-chunk tiles
    # ------------------------------------------------------------------
    def _pivot_chunks_init(self):
        if self._pchunks is None:
            self._pchunks = build_pivot_chunks(self.arena)
        return self._pchunks

    # hot-block score cache bound (rows): 2^17 rows x 512 B = 64 MB max
    SCORE_CACHE_ROWS = 1 << 17

    def _fetch(self, *arrays) -> list:
        """THE device->host materialization point of the ranked engine.

        Every fetch on the ranked hot path funnels through this one
        function -- a plain loop, deliberately not a comprehension, so
        the sync auditor (``repro.analyze.sync_audit``) attributes every
        materialization to ONE stable ``(file, fn)`` site and the
        ``ranked_topk`` ratchet measures residency, not call-site
        shuffles.  Each round fetches here exactly once per MAX_BUCKET
        chunk, after the whole round's graph has been dispatched.
        """
        self.stats["device_round_trips"] += 1
        out = []
        with obs.span("fetch", path="ranked"):
            for a in arrays:
                out.append(np.asarray(a))
        return out

    def _cache_lookup(self, urows: np.ndarray):
        """Hot-block score cache lookup for UNIQUE SORTED arena rows.

        resident="kernel" holds no arena-wide impact mirror -- that is
        the point -- but hot blocks recur across batches (and within one:
        the pivot's lane-exact candidate filter and the rescore's member
        scoring touch heavily overlapping row sets), so scored rows live
        in a sorted-array hot-block cache with fully vectorized lookups
        (one searchsorted per call; a python dict walk here costs more
        than the scoring).  Returns ``(out [n, 128] f32, hit mask)`` with
        only the hit rows of ``out`` filled."""
        out = np.empty((len(urows), BLOCK_VALS), np.float32)
        n = len(self._scache_rows)
        if n:
            pos = np.minimum(
                np.searchsorted(self._scache_rows, urows), n - 1
            )
            hit = self._scache_rows[pos] == urows
            if hit.any():
                out[hit] = self._scache[pos[hit]]
        else:
            hit = np.zeros(len(urows), bool)
        if obs.enabled():
            nh = int(hit.sum())
            obs.count("ranked_score_cache_rows", nh, kind="hit")
            obs.count("ranked_score_cache_rows", len(urows) - nh, kind="miss")
        return out, hit

    def _cache_merge(self, mrows: np.ndarray, scored: np.ndarray) -> int:
        """Insert (SORTED UNIQUE rows, [n, 128] f32 scores) into the
        hot-block cache; rows already present are skipped (a re-score is
        bit-identical, so dropping the duplicate is exact).  Returns the
        number of rows actually inserted.

        The cache is row-BOUNDED, not an unconditional mirror: past
        ``SCORE_CACHE_ROWS`` it is flushed (counted in
        ``stats["score_evictions"]``), and an over-budget insert set is
        truncated so the row bound holds even for one giant batch (mrows
        is sorted, so the kept prefix keeps the cache sorted too)."""
        n = len(self._scache_rows)
        if n:
            pos = np.minimum(np.searchsorted(self._scache_rows, mrows), n - 1)
            new = self._scache_rows[pos] != mrows
            if not new.all():
                mrows, scored = mrows[new], scored[new]
        if not len(mrows):
            return 0
        if n + len(mrows) > self.SCORE_CACHE_ROWS:
            self.stats["score_evictions"] += n
            keep = min(len(mrows), self.SCORE_CACHE_ROWS)
            self._scache_rows = mrows[:keep].copy()
            self._scache = scored[:keep].copy()
        else:
            rows2 = np.concatenate([self._scache_rows, mrows])
            order = np.argsort(rows2, kind="stable")
            self._scache_rows = rows2[order]
            self._scache = np.concatenate([self._scache, scored])[order]
        return len(mrows)

    def _build_rowscore_fn(self):
        """Jitted gather -> score_rows_graph over the RESIDENT freq arena.

        The legacy ``bm25_score_rows`` wrapper gathers rows on the host
        (one upload of the gathered tiles per call); this keeps the whole
        sidecar resident and gathers ON DEVICE, so a row-scoring round is
        one dispatch whose only host traffic is the fetched scores."""
        import jax.numpy as jnp

        from repro.kernels.bm25_score.ops import score_rows_graph

        res = dict(vars(self.ranked.dev), idf_blk=self._idf_blk_dev())
        backend, interpret = self.backend, self.interpret
        k1p1 = float(self.k1p1)

        def fn(r, rows):
            return score_rows_graph(
                r["freq_lens"][rows], r["freq_data"][rows],
                r["norm_q"][rows].astype(jnp.int32), r["idf_blk"][rows],
                r["norm_table"], k1p1, backend, interpret,
            )

        return jit_resident(fn, res)

    def _idf_blk_dev(self):
        """[n_blocks] f32 idf of each block's list, on the device once."""
        if self._idf_blk is None:
            import jax.numpy as jnp

            self._idf_blk = jnp.asarray(self.ranked.idf[self.lob])
        return self._idf_blk

    def _rowscore_dev(self, mrows: np.ndarray):
        """ONE resident row-scoring dispatch (pow2 row bucket, padding
        rows gather row 0 and are sliced off by the caller).  Returns the
        DEVICE score array -- callers fetch via ``_fetch`` so follow-up
        graphs (the device theta round) can consume it without a sync."""
        import jax.numpy as jnp

        if self._rowscore_fn is None:
            self._rowscore_fn = self._build_rowscore_fn()
        b = pow2_bucket(len(mrows))
        rp = np.zeros(b, np.int32)
        rp[: len(mrows)] = mrows
        return self._rowscore_fn(jnp.asarray(rp))

    def _score_miss_rows(self, mrows: np.ndarray) -> np.ndarray:
        """Score UNIQUE SORTED cache-miss rows: resident dispatch on an
        unsharded device backend, host-gather kernel wrapper otherwise."""
        if self.sharded is None and self.core.use_device:
            n = len(mrows)
            out = np.empty((n, BLOCK_VALS), np.float32)
            for s in range(0, n, self.MAX_BUCKET):
                e = min(s + self.MAX_BUCKET, n)
                res, = self._fetch(self._rowscore_dev(mrows[s:e]))
                out[s:e] = res[: e - s]
            return out
        return bm25_score_rows(
            self.ranked.freq_lens, self.ranked.freq_data,
            self.ranked.norm_q, mrows,
            self.ranked.idf[self.lob[mrows]],
            self.ranked.norm_table, self.k1p1,
            backend=self.backend, interpret=self.interpret,
        )

    def _score_rows_batch(self, urows: np.ndarray) -> np.ndarray:
        """[len(urows), 128] f32 lane scores of UNIQUE SORTED arena rows,
        cached across batches (see ``_cache_lookup`` / ``_cache_merge``)."""
        out, hit = self._cache_lookup(urows)
        miss = ~hit
        if miss.any():
            mrows = urows[miss]
            self.stats["scored_rows"] += len(mrows)
            scored = self._score_miss_rows(mrows)
            out[miss] = scored
            self._cache_merge(mrows, scored)
        return out

    def _build_pivot_fn(self, pc):
        """Jitted gather -> pivot_graph over ONE arena's resident chunk
        tiles (the global ones, or a shard's)."""
        from repro.core.engine_core import pivot_graph

        backend, interpret = self.backend, self.interpret

        def fn(c, rows, qmins):
            return pivot_graph(
                c["qb"][rows], qmins, c["nblk"][rows], backend, interpret
            )

        return jit_resident(fn, vars(pc.dev))

    def _pivot_dev_on(self, fn, rows, qmins):
        """Device dispatch of one arena's jitted pivot fn: pow2 cursor
        buckets (padding cursors stage qmin = QMIN_NONE and keep nothing),
        chunked at MAX_BUCKET.  Returns (kept lanes [n, 128], counts)."""
        import jax.numpy as jnp

        n = len(rows)
        kept = np.empty((n, BLOCK_VALS), np.int64)
        cnt = np.empty(n, np.int64)
        for s in range(0, n, self.MAX_BUCKET):
            e = min(s + self.MAX_BUCKET, n)
            b = pow2_bucket(e - s)
            rp = np.zeros(b, np.int32)
            qp = np.full((b, BLOCK_VALS), QMIN_NONE, np.int32)
            rp[: e - s] = rows[s:e]
            qp[: e - s] = qmins[s:e]
            out, c, _, _ = fn(jnp.asarray(rp), jnp.asarray(qp))
            out_h, c_h = self._fetch(out, c)
            kept[s:e] = out_h[: e - s]
            cnt[s:e] = c_h[: e - s]
        return kept, cnt

    # fused pivot+score dispatches gather SCORE_SLOTS freq/norm tiles per
    # cursor (~32 KB each), so they chunk smaller than MAX_BUCKET
    PIVOT_SCORE_BUCKET = 1_024

    def _build_pivot_score_fn(self, pc):
        """Jitted gather -> pivot_score_graph: the FUSED round (§13).

        One dispatch selects the kept blocks (bit-identical to
        ``pivot_graph``: the selection half IS ``pivot_select_blocks``)
        and decodes + BM25-scores the first ``SCORE_SLOTS`` kept blocks
        of every cursor in-graph, so the lane-exact candidate filter that
        used to need a second kernel round-trip rides back with the
        pivot fetch."""
        import jax.numpy as jnp

        from repro.core.arena import to_i32
        from repro.core.engine_core import pivot_score_graph

        res = dict(
            vars(self.ranked.dev), **vars(pc.dev),
            base=jnp.asarray(to_i32(pc.base, "chunk base")),
            idf_blk=self._idf_blk_dev(),
        )
        backend, interpret = self.backend, self.interpret
        k1p1 = float(self.k1p1)

        def fn(r, rows, qmins):
            return pivot_score_graph(
                r["qb"][rows], qmins, r["nblk"][rows], r["base"][rows],
                r["freq_lens"], r["freq_data"], r["norm_q"], r["idf_blk"],
                r["norm_table"], k1p1, SCORE_SLOTS, backend, interpret,
            )

        return jit_resident(fn, res)

    def _fusable_cursors(self, rows, cur_ij, theta, pc) -> np.ndarray:
        """FUSED-dispatch routing mask, per pivot cursor (§13).

        A cursor takes the fused pivot+score path when its query's theta
        is finite (only finite-theta segments get lane-filtered, so only
        their slot scores will be read) AND its chunk still has blocks
        missing from the hot-block score cache.  A fully-cached chunk
        takes the plain pivot -- its lane scores come out of the cache
        for free -- so the warm steady state pays ZERO fused-gather
        overhead and the fused path fires exactly where a second
        row-scoring dispatch would otherwise have been needed."""
        fin = np.fromiter(
            (bool(np.isfinite(theta[i])) for i, _ in cur_ij),
            bool, len(cur_ij),
        )
        if not fin.any():
            return fin
        base = pc.base[rows]
        nblk = pc.nblk[rows].astype(np.int64)
        lo = np.searchsorted(self._scache_rows, base)
        hi = np.searchsorted(self._scache_rows, base + nblk)
        return fin & ((hi - lo) < nblk)

    def _pivot_score_dev_on(self, rows, qmins, pc):
        """Fused dispatch of ``_build_pivot_score_fn``: same bucketing
        contract as ``_pivot_dev_on`` (pow2 cursor buckets, padding
        cursors keep nothing), but each fetch also carries the slot
        scores, which are folded into the hot-block cache here so the
        candidate filter's ``_score_rows_batch`` finds them already
        resident.  Returns (kept lanes [n, 128], counts)."""
        import jax.numpy as jnp

        if self._pivot_score_fn is None:
            self._pivot_score_fn = self._build_pivot_score_fn(pc)
        n = len(rows)
        kept = np.empty((n, BLOCK_VALS), np.int64)
        cnt = np.empty(n, np.int64)
        for s in range(0, n, self.PIVOT_SCORE_BUCKET):
            e = min(s + self.PIVOT_SCORE_BUCKET, n)
            b = pow2_bucket(e - s)
            rp = np.zeros(b, np.int32)
            qp = np.full((b, BLOCK_VALS), QMIN_NONE, np.int32)
            rp[: e - s] = rows[s:e]
            qp[: e - s] = qmins[s:e]
            out, c, _, _, ss = self._pivot_score_fn(
                jnp.asarray(rp), jnp.asarray(qp)
            )
            out_h, c_h, ss_h = self._fetch(out, c, ss)
            kept[s:e] = out_h[: e - s]
            cnt[s:e] = c_h[: e - s]
            ke = out_h[: e - s, :SCORE_SLOTS]
            valid = ke >= 0
            if valid.any():
                grows = (pc.base[rows[s:e]][:, None] + ke)[valid]
                sc = ss_h[: e - s].reshape(-1, BLOCK_VALS)[valid.reshape(-1)]
                u, first = np.unique(grows, return_index=True)
                self.stats["scored_rows"] += self._cache_merge(u, sc[first])
        self.stats["fused_pivot_chunks"] += n
        return kept, cnt

    def _pivot_select(self, specs, theta, want_scores: bool = False):
        """Emission + ONE device pivot dispatch for a whole batch.

        The host reduces the float admissibility envelope to u8 codes in
        float64 -- per block b of term t,

          ``mult_t * bound(b) + rest(b) >= theta``   (aligned bound) and
          ``mult_t * bound(b) >= theta * ub_t / total_ub``  (share floor)

        <=> ``block_max_q[b] >= qmin[b]`` exactly, with rest the range-
        aligned co-candidate bound of ``_aligned_rest``.  The share floor
        is admissible at block level for the same reason the mirror's
        lane-exact version is: a doc with score >= theta must beat its
        proportional share in SOME term, and the generator runs once per
        term, so the doc materializes where it does -- a block whose
        BOUND misses the share cannot contain a lane that beats it.

        Every chunk of every surviving term then goes through ONE pivot
        dispatch over the resident bound tiles (per shard under
        ``shards=``, qmin tiles broadcast to each shard's cursor runs,
        kept blocks scattered back to global rows via ``rows_of``).
        Admissible by construction: a block whose bound clears the
        envelope always comes back, on every backend and shard count.

        Returns ``(segments, params)``: ``segments[(i, j)] = (kept global
        rows, aligned rest of those rows)`` per query i / term slot j;
        ``params[(i, j)] = (mult_j, share_j)``.
        """
        use_dev = self._use_device
        routed = self.sharded is not None and use_dev
        pc = None if routed else self._pivot_chunks_init()
        pcs = self.sharded.pivot_chunks if routed else None
        segments: dict = {}
        params: dict = {}
        rests: dict = {}
        # ---- collect every (query, term) pair, then ONE batched qmin
        # reduction over all their blocks (the theta "broadcast" of the
        # round is this single float64 -> u8 fold)
        pair_meta, rest_l, mult_l, theta_l, share_l = [], [], [], [], []
        for i, (terms, mult) in enumerate(specs):
            if len(terms) == 0:
                continue
            ub = mult * self.list_ub[terms]
            total_ub = float(ub.sum())
            aligned = self._aligned_rest(terms, mult)
            for j, (rows_t, rest) in enumerate(aligned):
                nb_t = len(rows_t)
                self.stats["blocks_total"] += nb_t
                if nb_t == 0:
                    continue
                share = (
                    float(theta[i]) * float(ub[j]) / total_ub
                    if total_ub > 0 and np.isfinite(theta[i])
                    else -np.inf
                )
                pair_meta.append((i, j, int(terms[j]), nb_t))
                rest_l.append(rest)
                mult_l.append(float(mult[j]))
                theta_l.append(float(theta[i]))
                share_l.append(share)
                params[(i, j)] = (float(mult[j]), share)
                rests[(i, j)] = (int(rows_t[0]), rest)
        if not pair_meta:
            return segments, params
        sizes = np.array([m[3] for m in pair_meta])
        qmin_all = qmin_for(
            np.repeat(mult_l, sizes),
            np.concatenate(rest_l),
            np.repeat(theta_l, sizes),
            self._deq64,
        )
        # the proportional-share floor, one bisection over the pairs
        q_share = qmin_for(
            np.asarray(mult_l), np.zeros(len(pair_meta)),
            np.asarray(share_l), self._deq64,
        )
        qmin_all = np.maximum(qmin_all, np.repeat(q_share, sizes))

        rows_l, qmin_l, shard_l, cur_ij = [], [], [], []
        pair_cuts = np.zeros(len(pair_meta) + 1, np.int64)
        np.cumsum(sizes, out=pair_cuts[1:])
        for p, (i, j, t, nb_t) in enumerate(pair_meta):
            qmin_b = qmin_all[pair_cuts[p] : pair_cuts[p + 1]]
            if qmin_b.min() >= QMIN_NONE:
                del params[(i, j)], rests[(i, j)]
                continue  # no block of this term can reach theta
            if routed:
                s, lt = self.sharded.route_one(t)
                offs = pcs[s].offsets
                c0, c1 = int(offs[lt]), int(offs[lt + 1])
                shard_l.append(np.full(c1 - c0, s, np.int64))
            else:
                c0, c1 = int(pc.offsets[t]), int(pc.offsets[t + 1])
            tile = np.full(((c1 - c0) * BLOCK_VALS,), QMIN_NONE, np.int64)
            tile[:nb_t] = qmin_b
            rows_l.append(np.arange(c0, c1, dtype=np.int64))
            qmin_l.append(tile.reshape(c1 - c0, BLOCK_VALS))
            cur_ij.extend([(i, j)] * (c1 - c0))
        if not rows_l:
            return segments, params
        rows = np.concatenate(rows_l)
        qmins_c = np.concatenate(qmin_l)
        self.stats["pivot_chunks"] += len(rows)

        # ---- one pivot dispatch (per shard when routed)
        if not use_dev:
            kept, cnt, _, _ = pivot_select(
                pc.qb[rows], qmins_c, pc.nblk[rows],
                backend=self.backend, interpret=self.interpret,
            )
            grows = (pc.base[rows][:, None] + kept)[kept >= 0]
        elif not routed:
            if self._pivot_fn is None:
                self._pivot_fn = self._build_pivot_fn(pc)
            # §13: cursors whose slot scores will be read AND whose chunk
            # is not already hot take the fused pivot+score dispatch; the
            # rest take the plain pivot (same kept blocks either way)
            fuse = (
                self._fusable_cursors(rows, cur_ij, theta, pc)
                if want_scores
                else np.zeros(len(rows), bool)
            )
            kept = np.empty((len(rows), BLOCK_VALS), np.int64)
            cnt = np.empty(len(rows), np.int64)
            plain = ~fuse
            if plain.any():
                kept[plain], cnt[plain] = self._pivot_dev_on(
                    self._pivot_fn, rows[plain], qmins_c[plain]
                )
            if fuse.any():
                kept[fuse], cnt[fuse] = self._pivot_score_dev_on(
                    rows[fuse], qmins_c[fuse], pc
                )
            grows = (pc.base[rows][:, None] + kept)[kept >= 0]
        else:
            sa = self.sharded
            shards = np.concatenate(shard_l)
            order = np.argsort(shards, kind="stable")
            cuts = np.searchsorted(shards[order], np.arange(sa.n_shards + 1))
            rows_o, qmins_o = rows[order], qmins_c[order]
            cur_ij = [cur_ij[c] for c in order]
            kept = np.empty((len(rows), BLOCK_VALS), np.int64)
            cnt = np.empty(len(rows), np.int64)
            if sa.mesh is not None:
                if self._smap_pivot is None:
                    from repro.core.shard import ShardMapPivot

                    self._smap_pivot = ShardMapPivot(
                        sa, backend=self.backend, interpret=self.interpret,
                        max_bucket=self.MAX_BUCKET,
                        injector=self.fault_injector,
                    )
                kept, cnt, _, _ = self._smap_pivot(rows_o, qmins_o, cuts)
            else:
                for s in range(sa.n_shards):
                    sl = slice(int(cuts[s]), int(cuts[s + 1]))
                    if sl.start == sl.stop:
                        continue
                    self._check_shard(s)
                    if self._shard_pivot_fns[s] is None:
                        self._shard_pivot_fns[s] = self._build_pivot_fn(
                            pcs[s]
                        )
                    kept[sl], cnt[sl] = self._pivot_dev_on(
                        self._shard_pivot_fns[s], rows_o[sl], qmins_o[sl]
                    )
        self.stats["blocks_kept"] += int(cnt.sum())
        # per-cursor output cuts: shared by the routed scatter below and
        # the segment grouping (one cumsum, one source of truth)
        gcuts = np.zeros(len(rows) + 1, np.int64)
        np.cumsum(cnt, out=gcuts[1:])
        if routed:
            # shard-local lanes -> local rows -> GLOBAL rows (pure scatter)
            sa = self.sharded
            grows = np.empty(int(cnt.sum()), np.int64)
            for s in range(sa.n_shards):
                sl = slice(int(cuts[s]), int(cuts[s + 1]))
                if sl.start == sl.stop:
                    continue
                k_s = kept[sl]
                local = (pcs[s].base[rows_o[sl]][:, None] + k_s)[k_s >= 0]
                grows[gcuts[sl.start] : gcuts[sl.stop]] = sa.rows_of[s][
                    local
                ]

        # ---- group surviving rows into per-(query, term) segments with
        # their aligned rest values (cursors of one term are contiguous)
        acc: dict = {}
        for c, ij in enumerate(cur_ij):
            sl = slice(int(gcuts[c]), int(gcuts[c + 1]))
            if sl.start != sl.stop:
                acc.setdefault(ij, []).append(grows[sl])
        for ij, chunks in acc.items():
            rows_k = np.concatenate(chunks)
            r0, rest = rests[ij]
            segments[ij] = (rows_k, rest[rows_k - r0])
        return segments, params

    def _pivot_rows(self, specs, theta) -> list[np.ndarray]:
        """Per query: ALL arena rows (blocks) surviving the device pivot
        at the query's theta (the block-level keep-set; property-tested
        for admissibility in tests/test_pivot_kernel.py)."""
        segments, _ = self._pivot_select(specs, theta)
        out = [np.zeros(0, np.int64) for _ in specs]
        by_q: dict = {}
        for (i, _), (rows_k, _) in sorted(segments.items()):
            by_q.setdefault(i, []).append(rows_k)
        for i, chunks in by_q.items():
            out[i] = np.concatenate(chunks)
        return out

    def _pivot_candidates(self, specs, theta) -> list[np.ndarray]:
        """Per query: candidate docIDs from the surviving blocks, lane-
        exactly filtered through the fused scoring kernel.

        The kept blocks' lane scores come from ``_score_rows_batch`` (the
        row-bounded hot-block score cache shared with the rescore phase,
        so a hot row is scored once however many phases or batches touch
        it), and the same two admissible tests as the mirror path's
        ``_block_docs_filtered`` run on the true contributions:
        ``c + rest >= theta`` and ``c >= share``.  Scores are
        bit-identical across backends and residencies, so the candidate
        sets are too.
        """
        segments, params = self._pivot_select(specs, theta, want_scores=True)
        self._flat_init()
        a = self.arena
        out: list[list[np.ndarray]] = [[] for _ in specs]
        # only finite-theta segments get lane-filtered, so only THEIR rows
        # are worth scoring: a theta = -inf query (under-filled seed) keeps
        # whole posting lists, and scoring them would just flush hot rows
        # out of the bounded cache to produce scores nobody reads
        fin = [
            rows_k
            for (i, _), (rows_k, _) in segments.items()
            if np.isfinite(theta[i])
        ]
        scores_u = None
        if fin:
            urows = np.unique(np.concatenate(fin))
            scores_u = self._score_rows_batch(urows)
        for (i, j), (rows_k, rest_k) in sorted(segments.items()):
            vals = self.core.flat_vals[:-1].reshape(-1, BLOCK_VALS)[rows_k]
            lv = a.lane_valid[rows_k]
            if scores_u is None or not np.isfinite(theta[i]):
                out[i].append(vals[lv])
                continue
            mult_t, share = params[(i, j)]
            pos = np.searchsorted(urows, rows_k)
            c = mult_t * scores_u[pos]
            ok = lv & (c + rest_k[:, None] >= theta[i]) & (c >= share)
            out[i].append(vals[ok])
        return [
            np.concatenate(chunks) if chunks else np.zeros(0, np.int64)
            for chunks in out
        ]

    # ------------------------------------------------------------------
    # batched per-(term, doc) contributions
    # ------------------------------------------------------------------
    def _contrib_np(self, terms: np.ndarray, docs: np.ndarray) -> np.ndarray:
        """Host path: one searchsorted over the flat keys per batch."""
        self._flat_init()
        a, core = self.arena, self.core
        key = np.clip(docs, 0, a.stride - 1) + terms * a.stride
        pos = np.searchsorted(core.flat_keys, key, "left")
        past = pos >= core.lane_end[terms + 1]
        hit = (core.flat_vals[pos] == docs) & ~past
        if core.flat_scores is None:  # resident="kernel": no score mirror
            rows_n = np.minimum(pos, a.n_blocks * BLOCK_VALS - 1) >> 7
            urows, inv = np.unique(rows_n[hit], return_inverse=True)
            row_scores = bm25_score_rows(
                self.ranked.freq_lens, self.ranked.freq_data,
                self.ranked.norm_q, urows, self.ranked.idf[self.lob[urows]],
                self.ranked.norm_table, self.k1p1,
                backend=self.backend, interpret=self.interpret,
            )
            out = np.zeros(len(terms), np.float32)
            out[hit] = row_scores[inv, (pos[hit] & (BLOCK_VALS - 1))]
            return out
        return np.where(hit, core.flat_scores[pos], np.float32(0.0))

    def _build_jax_fn(self, arena, ranked):
        """Jitted locate -> gather -> decode+score+match over ONE arena
        (the global one, or a shard's sub-arena).  Both graph halves come
        from the shared single-source helpers (``locate_graph`` via
        ``build_locate_dev``, ``score_probe_graph``)."""
        import jax.numpy as jnp

        from repro.kernels.bm25_score.ops import score_probe_graph

        res = self._contrib_resident(arena, ranked)
        locate = build_locate_dev(arena)
        backend, interpret = self.backend, self.interpret
        k1p1 = float(self.k1p1)

        multi = arena.block_codec is not None

        def fn(r, terms, probes):
            rows, pe, past = locate(r, terms, probes)
            # multi-codec arenas compact the SVB doc tiles: gather through
            # codec_row (the host bucketing only sends SVB-block cursors)
            sr = r["codec_row"][rows] if multi else rows
            contrib = score_probe_graph(
                r["lens"][sr], r["data"][sr], r["freq_lens"][rows],
                r["freq_data"][rows], r["norm_q"][rows].astype(jnp.int32),
                r["block_base"][rows], pe, r["idf"][r["lob"][rows]],
                r["norm_table"], k1p1, backend, interpret,
            )
            return jnp.where(past, jnp.float32(0.0), contrib)

        return jit_resident(fn, res)

    @staticmethod
    def _contrib_resident(arena, ranked) -> dict:
        """The resident arrays of one arena's contribution fns."""
        import jax.numpy as jnp

        from repro.core.arena import to_i32

        lob = arena.part_list[arena.part_of_block]
        return dict(
            vars(arena.dev), **vars(ranked.dev),
            lob=jnp.asarray(to_i32(lob, "lob")),
        )

    def _build_ef_jax_fn(self, arena, ranked):
        """Jitted locate -> EF-NextGEQ -> score-row -> lane-select over one
        multi-codec arena (§14): the EF twin of ``_build_jax_fn``.

        The freq sidecar stays per-BLOCK whatever the docID codec, so the
        scoring half is ``score_rows_graph`` over the SAME freq row the SVB
        fn would read, and the matched lane's score is selected at the EF
        rank -- per-posting arithmetic identical to ``score_probe_graph``,
        hence bit-identical contributions.
        """
        import jax.numpy as jnp

        from repro.core.engine_core import ef_search_graph
        from repro.kernels.bm25_score.ops import score_rows_graph

        res = self._contrib_resident(arena, ranked)
        locate = build_locate_dev(arena)
        backend, interpret = self.backend, self.interpret
        k1p1 = float(self.k1p1)

        def fn(r, terms, probes):
            rows, pe, past = locate(r, terms, probes)
            er = r["codec_row"][rows]
            value, rank_in = ef_search_graph(
                r["ef_lo"][er], r["ef_hi"][er], r["ef_lbits"][er],
                r["block_base"][rows], pe, backend, interpret,
            )
            row_scores = score_rows_graph(
                r["freq_lens"][rows], r["freq_data"][rows],
                r["norm_q"][rows].astype(jnp.int32),
                r["idf"][r["lob"][rows]], r["norm_table"], k1p1, backend,
                interpret,
            )
            rc = jnp.minimum(rank_in, BLOCK_VALS - 1)
            contrib = jnp.take_along_axis(row_scores, rc[:, None], axis=1)[
                :, 0
            ]
            hit = (value == pe) & ~past
            return jnp.where(hit, contrib, jnp.float32(0.0))

        return jit_resident(fn, res)

    # largest single device dispatch: bigger batches are chunked to this
    # fixed bucket so every chunk reuses ONE jit trace and the gathered
    # tiles (~2.3 KB/cursor) stay bounded
    MAX_BUCKET = 16_384

    def _contrib_dev_on(self, fn, stride, terms, docs) -> np.ndarray:
        """Device dispatch of one arena's jitted fn: pow2 cursor buckets
        (padding cursors probe list 0 / doc 0), chunked at MAX_BUCKET."""
        import jax.numpy as jnp

        n = len(terms)
        out = np.empty(n, np.float32)
        for s in range(0, n, self.MAX_BUCKET):
            e = min(s + self.MAX_BUCKET, n)
            tp, pp = stage_cursors(
                terms[s:e], docs[s:e], stride, pow2_bucket(e - s)
            )
            res = fn(jnp.asarray(tp), jnp.asarray(pp))
            res_h, = self._fetch(res)
            out[s:e] = res_h[: e - s]
        return out

    def _contrib_dev_arena(self, arena, ranked, fns, terms, docs):
        """One arena's device contributions, bucketed per codec (§14).

        ``fns`` is the arena's per-codec jitted-fn dict, filled lazily.
        Single-codec arenas go straight to the SVB pipeline; multi-codec
        arenas run the host codec pre-pass (the same searchsorted the
        device re-runs, read only for ``block_codec``) and dispatch ONE
        fused wave per codec, scattering back in batch order.
        """
        if fns.get("svb") is None:
            fns["svb"] = self._build_jax_fn(arena, ranked)
        if arena.block_codec is None:
            return self._contrib_dev_on(fns["svb"], arena.stride, terms, docs)
        from repro.core.arena import CODEC_EF

        pc = np.clip(docs, 0, arena.stride - 1)
        k = np.searchsorted(
            arena.block_keys, pc + terms * arena.stride, side="left"
        )
        codec = arena.block_codec[np.minimum(k, arena.n_blocks - 1)]
        ef_j = np.nonzero(codec == CODEC_EF)[0]
        if not len(ef_j):
            return self._contrib_dev_on(fns["svb"], arena.stride, terms, docs)
        if fns.get("ef") is None:
            fns["ef"] = self._build_ef_jax_fn(arena, ranked)
        if len(ef_j) == len(terms):
            return self._contrib_dev_on(fns["ef"], arena.stride, terms, docs)
        svb_j = np.nonzero(codec != CODEC_EF)[0]
        out = np.empty(len(terms), np.float32)
        out[svb_j] = self._contrib_dev_on(
            fns["svb"], arena.stride, terms[svb_j], docs[svb_j]
        )
        out[ef_j] = self._contrib_dev_on(
            fns["ef"], arena.stride, terms[ef_j], docs[ef_j]
        )
        return out

    def _contrib_dev(self, terms: np.ndarray, docs: np.ndarray) -> np.ndarray:
        """Device path; with ``shards=`` cursors route to their owning
        shard's sub-arena and merge back by pure scatter (contributions are
        scalars -- nothing to rebase)."""
        if self.sharded is None:
            return self._contrib_dev_arena(
                self.arena, self.ranked, self._jax_fns, terms, docs
            )
        sa = self.sharded
        owner, local, served = sa.route(terms)
        if not served.all():
            from repro.core.shard import ShardsUnavailable

            raise ShardsUnavailable(np.unique(np.asarray(terms)[~served]))
        order = np.argsort(owner, kind="stable")
        cuts = np.searchsorted(owner[order], np.arange(sa.n_shards + 1))
        out = np.zeros(len(terms), np.float32)
        if sa.mesh is not None:
            if self._smap_fn is None:
                from repro.core.shard import ShardMapBM25

                self._smap_fn = ShardMapBM25(
                    sa, backend=self.backend, interpret=self.interpret,
                    k1p1=float(self.k1p1), max_bucket=self.MAX_BUCKET,
                    injector=self.fault_injector,
                )
            out[order] = self._smap_fn(local[order], docs[order], cuts)
            return out
        for s in range(sa.n_shards):
            idx = order[cuts[s] : cuts[s + 1]]
            if len(idx) == 0:
                continue
            self._check_shard(s)
            if self._shard_fns[s] is None:
                self._shard_fns[s] = {}
            sub = sa.shards[s]
            out[idx] = self._contrib_dev_arena(
                sub, sub.ranked, self._shard_fns[s], local[idx], docs[idx]
            )
        return out

    @property
    def _use_device(self) -> bool:
        return self.core.use_device

    def contributions(self, terms, docs) -> np.ndarray:
        """f32 BM25 contribution of doc in list(term), 0.0 when absent.

        On the device path, duplicate (term, doc) cursors -- rampant across
        a batch of queries sharing hot terms and candidate docs -- are
        grouped first so each one costs a single gather + kernel row (the
        same move as ``QueryEngine``'s grouped ``_fused_raw``).
        """
        terms = np.asarray(terms, dtype=np.int64)
        docs = np.asarray(docs, dtype=np.int64)
        if len(terms) == 0:
            return np.zeros(0, np.float32)
        if self._use_device:
            g = group_cursors(terms, docs, self.arena.stride)
            if g is not None:
                idx, inv = g
                out = self._contrib_dev(terms[idx], docs[idx])[inv]
            else:
                out = self._contrib_dev(terms, docs)
            # the device staging clip maps out-of-range docs onto real
            # probes (e.g. -1 -> docID 0); they can never be members
            out[(docs < 0) | (docs >= self.arena.stride)] = 0.0
            return out
        return self._contrib_np(terms, docs)

    # ------------------------------------------------------------------
    # device-carried theta (§13): the round-A theta raise + round-B UB
    # filter ride in the round-A scoring dispatch
    # ------------------------------------------------------------------
    def _build_theta_fn(self):
        """Jitted round-A tail: pair scatter-add -> f32 LOWER BOUNDS of
        the exact per-doc scores -> k-th lower bound per query -> round-B
        UB mask.

        Float contract: the exact score of doc slot s is a float64 sum of
        f32 contributions; the device computes the same sum in f32 plus
        an abs-sum slack ``asums * eps`` covering every f32 rounding on
        the path (products, scatter-add order, the f64->f32 base cast --
        each step is <= 1/2 ulp of a partial bounded by the abs-sum, and
        eps budgets 4x the op count), so ``lb <= exact`` always.  With
        theta rounded DOWN and the round-B UBs rounded UP by the caller,
        the emitted mask is a provable superset of the exact round-B
        selection {UB >= exact theta2} -- never a subset, so no top-k
        candidate is ever dropped."""
        import jax
        import jax.numpy as jnp

        def fn(
            scores, dinv, lanes, w, seg, base, ndocs, theta_lo, eps,
            ub_hi, qid_b, k, cap,
        ):
            nqp = ndocs.shape[0]
            contrib = scores[dinv, lanes] * w
            sums = base.at[seg].add(contrib)
            asums = jnp.abs(base).at[seg].add(jnp.abs(contrib))
            lb = (sums - asums * eps)[:-1].reshape(nqp, cap)
            slot = jax.lax.broadcasted_iota(jnp.int32, (nqp, cap), 1)
            lb = jnp.where(slot < ndocs[:, None], lb, -jnp.inf)
            kth = jax.lax.top_k(lb, k)[0][:, k - 1]
            theta2 = jnp.where(
                ndocs >= k, jnp.maximum(theta_lo, kth), theta_lo
            )
            return ub_hi >= theta2[qid_b]

        return jax.jit(fn, static_argnames=("k", "cap"))

    def _theta_round_dev(
        self, specs, sel_a, cap, k, theta, ubs,
        idx_l, col_l, w_l, out_u, hit, inv, lanes, miss, mrows,
    ) -> np.ndarray:
        """Round A as ONE dispatch: score the cache-miss rows resident,
        scatter the pair contributions into per-(query, doc-slot) f32
        lower bounds, raise theta on device, and emit the round-B UB
        mask -- all fetched together (a single ``_fetch``), so the theta
        broadcast costs no extra host round-trip.

        Fills the miss rows of ``out_u`` (and the hot-block cache) with
        the fetched scores; returns the mask over the concatenated
        not-round-A doc slots of every query."""
        import jax.numpy as jnp

        self.stats["theta_device_rounds"] += 1
        self.stats["scored_rows"] += len(mrows)
        nq = len(specs)
        counts = np.array([int(s.sum()) for s in sel_a], np.int64)
        capm = int(pow2_bucket(max(int(counts.max()), k)))
        nqp = int(pow2_bucket(nq, 1))
        nslot = nqp * capm + 1  # +1: dump slot for padding pairs

        # pair segments: slot = query * capm + compacted doc column
        qid = np.repeat(
            np.arange(nq, dtype=np.int64), [len(ix) for ix in idx_l]
        )
        col = np.concatenate(col_l) if len(qid) else np.zeros(0, np.int64)
        w = np.concatenate(w_l) if len(qid) else np.zeros(0, np.float64)
        seg = qid * capm + col
        # pairs over CACHED rows accumulate on the host in exact f64 and
        # enter the device sum as one f32 base term per slot
        pair_hit = hit[inv]
        bs64 = np.zeros(nslot, np.float64)
        if pair_hit.any():
            hp = np.flatnonzero(pair_hit)
            np.add.at(
                bs64, seg[hp],
                w[hp] * out_u[inv[hp], lanes[hp]].astype(np.float64),
            )
        # pairs over rows being scored THIS round stay on device
        dp = np.flatnonzero(~pair_hit)
        miss_pos = np.cumsum(miss) - 1  # urows index -> mrows index
        P = int(pow2_bucket(max(len(dp), 1)))
        dinv = np.zeros(P, np.int32)
        dlan = np.zeros(P, np.int32)
        dw = np.zeros(P, np.float32)
        dseg = np.full(P, nslot - 1, np.int32)
        dinv[: len(dp)] = miss_pos[inv[dp]]
        dlan[: len(dp)] = lanes[dp]
        dw[: len(dp)] = w[dp].astype(np.float32)
        dseg[: len(dp)] = seg[dp].astype(np.int32)

        # f32 envelope: theta rounded DOWN, round-B UBs rounded UP
        ndocs = np.zeros(nqp, np.int32)
        ndocs[:nq] = np.minimum(counts, capm)
        theta32 = np.full(nqp, -np.inf, np.float32)
        theta32[:nq] = np.nextafter(
            theta.astype(np.float32), np.float32(-np.inf)
        )
        ub_l, qid_l = [], []
        for i in range(nq):
            nb_i = ~sel_a[i]
            u = ubs[i][nb_i].astype(np.float32)
            ub_l.append(np.nextafter(u, np.float32(np.inf)))
            qid_l.append(np.full(int(nb_i.sum()), i, np.int32))
        ub_b = np.concatenate(ub_l)
        n_b = len(ub_b)
        Bn = int(pow2_bucket(max(n_b, 1)))
        ubp = np.full(Bn, -np.inf, np.float32)
        ubp[:n_b] = ub_b
        qbp = np.zeros(Bn, np.int32)
        qbp[:n_b] = np.concatenate(qid_l)
        # abs-sum slack: <= tmax pair adds + products + base cast per
        # slot, each <= 1 ulp of a partial bounded by the abs-sum; 4x op
        # count in f32 ulps covers any evaluation order
        tmax = max((len(t) for t, _, _ in specs), default=1)
        eps = np.float32(4.0 * (tmax + 4.0) * 2.0 ** -23)

        scores_dev = self._rowscore_dev(mrows)
        if self._theta_fn is None:
            self._theta_fn = self._build_theta_fn()
        mask_dev = self._theta_fn(
            scores_dev, jnp.asarray(dinv), jnp.asarray(dlan),
            jnp.asarray(dw), jnp.asarray(dseg),
            jnp.asarray(bs64.astype(np.float32)), jnp.asarray(ndocs),
            jnp.asarray(theta32), jnp.asarray(eps), jnp.asarray(ubp),
            jnp.asarray(qbp), k=k, cap=capm,
        )
        miss_sc, mask_h = self._fetch(scores_dev, mask_dev)
        miss_sc = miss_sc[: len(mrows)]
        out_u[miss] = miss_sc
        self._cache_merge(mrows, miss_sc)
        return mask_h[:n_b]

    # ------------------------------------------------------------------
    # batched bound-filter + exact scoring of per-query candidate sets
    # ------------------------------------------------------------------
    def _membership(self, specs, need_ub: bool):
        """The membership pass of ``_score_specs`` over the flat lane mirror.

        A key ``doc + t * stride`` can only land in term t's own lane,
        ``flat_keys[lane_end[t] : lane_end[t + 1]]``, so each (query, term)
        searches its candidates there and never the whole mirror.  Pairs
        are laid out query-major, term-major, doc order.  Returns (pos: the
        flat slot of every pair, cuts: each query's first pair, mems: per
        query the [T, D] member mask, ubs: per query the doc-aligned
        block-max upper bound -- None without ``need_ub``).  The UB sums
        term by term from 0.0, which keeps it bit-identical to a row-wise
        ``sum(axis=0)`` of the [T, D] table.
        """
        a, core = self.arena, self.core
        cuts = [0]
        for terms, _, docs in specs:
            cuts.append(cuts[-1] + len(terms) * len(docs))
        self.stats["membership_pairs"] += cuts[-1]
        last_slot = a.n_blocks * BLOCK_VALS - 1
        pos = np.empty(cuts[-1], np.int64)
        mems, ubs = [], []
        for i, (terms, mult, docs) in enumerate(specs):
            T, D = len(terms), len(docs)
            pos_i = pos[cuts[i] : cuts[i + 1]].reshape(T, D)
            mem = np.zeros((T, D), bool)
            ub = np.zeros(D, np.float64) if need_ub else None
            for j, t in enumerate(terms):
                lo, hi = core.lane_end[t], core.lane_end[t + 1]
                p = lo + np.searchsorted(
                    core.flat_keys[lo:hi], docs + t * a.stride, "left"
                )
                pos_i[j] = p
                mem[j] = (core.flat_vals[p] == docs) & (p < hi)
                if need_ub:
                    ub += mult[j] * np.where(
                        mem[j], self.bounds[np.minimum(p, last_slot) >> 7], 0.0
                    )
            mems.append(mem)
            ubs.append(ub)
        return pos, cuts, mems, ubs

    def _score_specs(
        self,
        specs: list[tuple[np.ndarray, np.ndarray, np.ndarray]],
        theta: np.ndarray | None = None,
        k: int | None = None,
    ) -> tuple[list[tuple[np.ndarray, np.ndarray]], np.ndarray | None]:
        """specs: per query (unique terms, multiplicities, candidate docs).
        Returns (per query (surviving docs, exact f64 scores), the raised
        per-query theta -- None when no threshold pass ran).  The raised
        theta is monotone: never below the theta passed in (property-
        tested in tests/test_pivot_kernel.py).

        One membership pass over the flat lane mirror resolves EVERY
        (term, doc) pair of the batch (``_membership``: per query term, one
        searchsorted of the candidates in the term's own lane; no decode,
        no scoring).  It yields, per pair, membership and the
        owning arena block, from which the Block-Max WAND pivot test runs
        doc-aligned: UB(doc) = sum over member pairs of mult * block bound
        >= score(doc).  Only MEMBER pairs of surviving docs are ever scored
        -- on the numpy backend that is a free gather from the flat lane
        scores already in hand; on device backends it is the fused
        decode+score+match kernel over the resident arena (duplicate pairs
        grouped).  Scores accumulate per doc in float64 (exact, order-free).

        With ``theta``/``k`` set, scoring is TWO-ROUND threshold+compact:
        round A exact-scores the max(4k, 64) highest-UB docs per query and
        raises theta to their k-th true score; round B scores only the
        remaining docs whose UB clears the raised theta.  Dropped docs are
        provably outside the top-k (score <= UB < theta <= final k-th).
        """
        self._flat_init()
        core = self.core
        nq = len(specs)
        with obs.span("membership", path="ranked"):
            pos, cuts, mems, ubs = self._membership(specs, theta is not None)
            if cuts[-1] == 0:
                return [
                    (np.zeros(0, np.int64), np.zeros(0, np.float64))
                    for _ in specs
                ], (None if theta is None else theta.copy())

        def pairs_for(sels: list[np.ndarray]):
            """Member-pair segments of the selected doc slots: per query
            (flat pair index, compacted doc column, multiplicity)."""
            idx_l, col_l, w_l = [], [], []
            for i, (terms, mult, docs) in enumerate(specs):
                sel = sels[i]
                D = len(docs)
                if D == 0 or len(terms) == 0 or not sel.any():
                    idx_l.append(np.zeros(0, np.int64))
                    col_l.append(np.zeros(0, np.int64))
                    w_l.append(np.zeros(0, np.float64))
                    continue
                colmap = np.cumsum(sel) - 1
                pr, pc = np.nonzero(mems[i] & sel[None, :])
                idx_l.append(cuts[i] + pr * D + pc)
                col_l.append(colmap[pc])
                w_l.append(mult[pr])
            return idx_l, col_l, w_l, np.concatenate(idx_l)

        def accumulate(idx_l, col_l, w_l, sels, contrib):
            """Per-doc exact scores: float64 scatter-add (order-free)."""
            out, start = [], 0
            for i in range(nq):
                n_i = len(idx_l[i])
                sc = np.zeros(int(sels[i].sum()), np.float64)
                np.add.at(
                    sc, col_l[i],
                    w_l[i] * contrib[start : start + n_i].astype(np.float64),
                )
                out.append(sc)
                start += n_i
            return out

        def score_subset(sels: list[np.ndarray]):
            """Exact f64 scores of the selected doc slots of every query,
            via ONE batched contribution dispatch over the member pairs."""
            idx_l, col_l, w_l, g_idx = pairs_for(sels)
            self.stats["scored_pairs"] += len(g_idx)
            with obs.span("score_rows", path="ranked"):
                if self.resident == "kernel":
                    # member pairs pin exact (row, lane) coordinates, so
                    # the batch's contributions cost ONE all-lane kernel
                    # pass over the UNIQUE touched rows -- not one gathered
                    # cursor per pair: many candidates share a hot block,
                    # and the block is decoded+scored once however many
                    # pairs land in it
                    g_pos = pos[g_idx]
                    rows_n, lanes = g_pos >> 7, g_pos & (BLOCK_VALS - 1)
                    urows, inv = np.unique(rows_n, return_inverse=True)
                    row_scores = self._score_rows_batch(urows)
                    contrib = row_scores[inv, lanes]
                else:
                    contrib = core.flat_scores[pos[g_idx]]
            with obs.span("accumulate", path="ranked"):
                return accumulate(idx_l, col_l, w_l, sels, contrib)

        if theta is None or k is None:
            sels = [np.ones(len(docs), bool) for _, _, docs in specs]
            scores = score_subset(sels)
            return [
                (docs, sc) for (_, _, docs), sc in zip(specs, scores)
            ], None

        # ---- round A: the max(4k, 64) highest-UB docs, scored exactly
        # (argpartition: ANY k-superset works here, order does not matter)
        cap = max(4 * k, 64)
        sel_a = []
        for i, (_, _, docs) in enumerate(specs):
            sel = np.zeros(len(docs), bool)
            if len(docs) > cap:
                sel[np.argpartition(-ubs[i], cap - 1)[:cap]] = True
            elif len(docs):
                sel[:] = True
            sel_a.append(sel)

        # ---- round A dispatch; on an unsharded resident backend the
        # theta raise rides in the SAME dispatch as the round-A scoring
        # (device-carried theta, §13): an f32 lower-bound top-k on device
        # emits the round-B UB mask, so round B needs no second
        # theta-broadcast round-trip.  The authoritative theta2 is still
        # the exact f64 host value below -- the device mask is only a
        # provable SUPERSET filter of the exact round-B selection.
        idx_l, col_l, w_l, g_idx = pairs_for(sel_a)
        self.stats["scored_pairs"] += len(g_idx)
        mask_b = None
        with obs.span("score_rows", path="ranked"):
            if self.resident == "kernel":
                g_pos = pos[g_idx]
                rows_n, lanes = g_pos >> 7, g_pos & (BLOCK_VALS - 1)
                urows, inv = np.unique(rows_n, return_inverse=True)
                out_u, hit = self._cache_lookup(urows)
                miss = ~hit
                mrows = urows[miss]
                if (
                    self.sharded is None
                    and self.core.use_device
                    and 0 < len(mrows) <= self.MAX_BUCKET
                ):
                    mask_b = self._theta_round_dev(
                        specs, sel_a, cap, k, theta, ubs, idx_l, col_l,
                        w_l, out_u, hit, inv, lanes, miss, mrows,
                    )
                elif miss.any():
                    self.stats["scored_rows"] += len(mrows)
                    scored = self._score_miss_rows(mrows)
                    out_u[miss] = scored
                    self._cache_merge(mrows, scored)
                contrib = out_u[inv, lanes]
            else:
                contrib = core.flat_scores[pos[g_idx]]
        with obs.span("accumulate", path="ranked"):
            scores_a = accumulate(idx_l, col_l, w_l, sel_a, contrib)
            # ---- raise theta to the k-th true score of round A (exact
            # f64: the returned theta2 is bit-identical on every path)
            theta2 = theta.copy()
            for i, sc in enumerate(scores_a):
                if len(sc) >= k:
                    kth = np.partition(sc, len(sc) - k)[len(sc) - k]
                    theta2[i] = max(theta2[i], kth)

        # ---- round B: remaining docs whose UB clears the raised theta.
        # The device mask keeps a superset of {UB >= exact theta2} (its
        # theta is rounded DOWN, the UBs rounded UP), and every kept doc
        # is scored exactly below -- top-k identity is untouched.
        sel_b = []
        if mask_b is not None:
            off = 0
            for i, (_, _, docs) in enumerate(specs):
                nb_i = np.flatnonzero(~sel_a[i])
                m = mask_b[off : off + len(nb_i)]
                off += len(nb_i)
                sel = np.zeros(len(docs), bool)
                sel[nb_i[m]] = True
                self.stats["ub_filtered"] += int(len(nb_i) - sel.sum())
                sel_b.append(sel)
        else:
            for i, (_, _, docs) in enumerate(specs):
                sel = ~sel_a[i] & (ubs[i] >= theta2[i])
                self.stats["ub_filtered"] += int(
                    (~sel_a[i]).sum() - sel.sum()
                )
                sel_b.append(sel)
        scores_b = score_subset(sel_b)

        out = []
        for i, (_, _, docs) in enumerate(specs):
            docs_i = np.concatenate([docs[sel_a[i]], docs[sel_b[i]]])
            sc_i = np.concatenate([scores_a[i], scores_b[i]])
            out.append((docs_i, sc_i))
        return out, theta2

    # ------------------------------------------------------------------
    # the Block-Max MaxScore batch loop
    # ------------------------------------------------------------------
    def _query_spec(self, q) -> tuple[np.ndarray, np.ndarray]:
        """(unique terms with non-empty lists, multiplicities as f64)."""
        terms, mult = np.unique(np.asarray(q, dtype=np.int64), return_counts=True)
        keep = self.index.list_sizes[terms] > 0
        return terms[keep], mult[keep].astype(np.float64)

    def topk_batch(
        self, queries: list[list[int]], k: int
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Exact BM25 top-k of each query; (docIDs, f64 scores) per query,
        sorted by (score desc, docID asc) -- identical to the exhaustive
        oracle, including the tie-break."""
        with obs.span("topk_batch", path="ranked"):
            return self._topk_batch(queries, k)

    def _topk_batch(self, queries, k):
        a = self.arena
        self.stats["batches"] += 1
        specs = [self._query_spec(q) for q in queries]

        # ---- phase 1: seed theta from every term's best-bounded blocks
        # (covering each term catches the multi-term docs that dominate
        # disjunctive top-k, so theta starts close to the true k-th score;
        # whole blocks beat per-lane top-m picks here because saturation
        # ties many lanes and the joint-hot docs hide among them)
        with obs.span("seed", path="ranked"):
            self._flat_init()
            seed_specs, seed_qids = [], []
            for i, (terms, mult) in enumerate(specs):
                if len(terms) == 0:
                    continue
                chunks = []
                for t in terms:
                    r0 = int(a.list_blk_offsets[int(t)])
                    r1 = int(a.list_blk_offsets[int(t) + 1])
                    rows = np.arange(r0, r1, dtype=np.int64)
                    top = rows[np.argsort(-self.bounds[rows], kind="stable")]
                    chunks.append(self._block_docs(top[: self.seed_blocks]))
                docs = np.unique(np.concatenate(chunks))
                seed_specs.append((terms, mult, docs))
                seed_qids.append(i)
            seed_scored, _ = self._score_specs(seed_specs)
            self.stats["seed_pairs"] += sum(
                len(t) * len(d) for t, _, d in seed_specs
            )
            theta = np.full(len(queries), -np.inf)
            seeds: dict[int, np.ndarray] = {}
            for (terms, mult, docs), (_, sc), i in zip(
                seed_specs, seed_scored, seed_qids
            ):
                seeds[i] = docs
                if len(docs) >= k:
                    theta[i] = np.partition(sc, len(sc) - k)[len(sc) - k]

        # ---- phase 2, resident="kernel": the device Block-Max pivot.
        # Theta reduces to one qmin per (query, term) on the host; the
        # blockmax_pivot kernel keeps/compacts candidate blocks over the
        # resident bound tiles in ONE dispatch (per shard when sharded,
        # qmins broadcast to every shard) -- no host work per block, no
        # sync per pruning round.  Admissible, so phase 3's exact rescore
        # still reproduces the oracle bit for bit.
        if self.resident == "kernel":
            with obs.span("pivot", path="ranked", resident="kernel"):
                cand_docs = self._pivot_candidates(specs, theta)
                final_specs = []
                with obs.span("union", path="ranked"):
                    for i, (terms, mult) in enumerate(specs):
                        if len(terms) == 0:
                            final_specs.append(
                                (terms, mult, np.zeros(0, np.int64))
                            )
                            continue
                        cand_chunks = [seeds[i]] if i in seeds else []
                        if len(cand_docs[i]):
                            cand_chunks.append(cand_docs[i])
                        cand = (
                            np.unique(np.concatenate(cand_chunks))
                            if cand_chunks
                            else np.zeros(0, np.int64)
                        )
                        self.stats["candidates"] += len(cand)
                        final_specs.append((terms, mult, cand))
            with obs.span("rescore", path="ranked"):
                final_scored, theta2 = self._score_specs(final_specs, theta, k)
            self._note_theta(theta2)
            return [topk_select(docs, sc, k) for docs, sc in final_scored]

        # ---- phase 2, resident="mirror": range-aligned block pivot
        # (Block-Max WAND) on the host.  A doc in block b of term t scores
        # at most
        #   mult_t * bound(b) + sum_{t' != t} mult_t' * max bound of the
        #                       t'-blocks overlapping b's docID span
        # so a block whose aligned upper bound misses theta generates no
        # candidates -- and any doc with score >= theta survives through
        # EVERY block that contains it (the bound above holds for each).
        with obs.span("pivot", path="ranked", resident="mirror"):
            final_specs = []
            for i, (terms, mult) in enumerate(specs):
                if len(terms) == 0:
                    final_specs.append((terms, mult, np.zeros(0, np.int64)))
                    continue
                ub = mult * self.list_ub[terms]
                total_ub = float(ub.sum())
                cand_chunks = [seeds[i]] if i in seeds else []
                aligned = self._aligned_rest(terms, mult)
                for j, (rows, rest) in enumerate(aligned):
                    keep = mult[j] * self.bounds[rows] + rest >= theta[i]
                    self.stats["blocks_kept"] += int(keep.sum())
                    self.stats["blocks_total"] += len(rows)
                    share = (
                        float(theta[i]) * float(ub[j]) / total_ub
                        if total_ub > 0 and np.isfinite(theta[i])
                        else -np.inf
                    )
                    cand_chunks.append(
                        self._block_docs_filtered(
                            rows[keep], rest[keep], float(mult[j]),
                            float(theta[i]), share,
                        )
                    )
                cand = (
                    np.unique(np.concatenate(cand_chunks))
                    if cand_chunks
                    else np.zeros(0, np.int64)
                )
                self.stats["candidates"] += len(cand)
                final_specs.append((terms, mult, cand))

        # ---- phase 3: doc-aligned block-max pivot filter (UB >= theta) +
        # two-round threshold+compact rescore + (score desc, docID asc) cut
        with obs.span("rescore", path="ranked"):
            final_scored, theta2 = self._score_specs(final_specs, theta, k)
        self._note_theta(theta2)
        return [topk_select(docs, sc, k) for docs, sc in final_scored]
