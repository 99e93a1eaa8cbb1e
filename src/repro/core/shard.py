"""Sharded multi-device arena: list-hash partitioning over a jax Mesh.

The block arena of ``repro.core.arena`` is one flat address space: every
partition of every list transcoded into consecutive 512-byte Stream-VByte
tiles with globally monotone locate keys.  That layout is exactly what makes
sharding trivial -- a shard is just a SUBSET of lists, and because blocks of
one list are consecutive rows, slicing the arena by owning list yields a
smaller arena with the same invariants:

* **list-hash partitioning**: list t lives on shard ``splitmix64(t) %
  n_shards``.  A hash (not round-robin) keeps hot lists spread whatever the
  id layout of the corpus, and makes ownership a pure function of the list
  id -- no routing table to ship, any frontend can compute it.
* **per-shard sub-arenas**: each shard's rows are gathered into a
  ``DeviceArena`` of its own, with list ids remapped to shard-local
  (ascending, so per-shard ``block_keys`` stay globally non-decreasing) and
  the SAME global ``stride`` -- probe keys are therefore identical to the
  unsharded ones, which is what makes 1-shard sharding bit-identical.
  The ranked sidecar (freq blocks, norm codes, block-max bounds, idf)
  slices the same way.
* **routing + merge contract**: cursors route to ``owner[term]`` on the
  host; results merge by PURE SCATTER, because the fused kernels emit
  absolute docIDs (no rebasing) and partition-LOCAL ranks (a partition
  lives wholly inside one shard).  f32 BM25 contributions are scalars.
  Nothing crosses shards mid-query -- the only cross-shard operation is the
  host-side scatter at the result boundary.
* **placement**: with a ``jax.sharding.Mesh`` over a "shard" axis (one
  device per shard), the per-shard tiles, sidecars, and ranked freq blocks
  are stacked [S, ...] (padded to extents of the arena alone, ``_pads``)
  and placed with
  ``NamedSharding(mesh, P("shard"))`` -- each device holds ONLY its shard.
  Queries then run as ONE ``shard_map`` dispatch: every device executes the
  same fused locate -> decode_search (or bm25 locate -> decode+score+match)
  program over its resident shard.  Without a mesh (or on the numpy
  backend) the shards are served as a host-side loop over per-shard
  ``EngineCore``s -- same results, same routing, no device collective.
  Each stacked array is assembled from one buffer per device, so a lost
  shard's buffers are deleted on its own device (``evict``): the
  dispatchers then run over the live shards' sub-mesh until the restored
  slice is uploaded (``upload``) and swapped back in (``install``).

* **replication + health (ISSUE-7)**: with ``replicas=R`` each list lives
  on R shards -- replica r of list t on ``(splitmix64(t) + r) % n_shards``,
  still a pure function of (t, r, S).  ``route()`` honors a mutable
  per-shard ``dead`` mask: healthy routing picks the primary (row 0, so the
  no-fault path is byte-identical to R=1), a dead primary fails over to the
  first live replica, and lists with NO live replica come back unserved for
  the caller to degrade on (``ResilientEngine``) or raise
  ``ShardsUnavailable``.  Because the merge is a pure scatter and every
  replica slice carries the same global stride, replica-served answers are
  bit-identical to primary-served ones.

An empty shard (no lists hash to it) is a valid degenerate sub-arena: its
``list_blk_offsets`` are all zero, so every cursor staged to it (only
padding cursors can be) resolves past-the-end.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro import obs
from repro.core.arena import DeviceArena, RankedSidecar, to_i32
from repro.kernels.blockmax_pivot.kernel import QMIN_NONE
from repro.kernels.vbyte_decode.kernel import BLOCK_BYTES, BLOCK_VALS

INT32_MAX = np.iinfo(np.int32).max
# stacked shards hold this many times an even share of the arena's blocks:
# list-hash placement of Zipf-sized lists leaves the busiest of 4 shards
# (2 replicas) under 1.24x the even share for 20,000 list orders of the
# Gov2-shaped corpus, so the padded shapes do not move with the order
SHARD_HEADROOM = 1.25


def shard_of_list(lists: np.ndarray, n_shards: int) -> np.ndarray:
    """Owning shard per list id: splitmix64 finalizer mod n_shards.

    A multiplicative bit-mix, not ``t % n_shards``: corpora routinely have
    structured list ids (frequency-ordered, hash-bucketed) and a plain mod
    would pile hot lists onto one shard.
    """
    x = np.asarray(lists, dtype=np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(n_shards)).astype(np.int64)


class ShardsUnavailable(RuntimeError):
    """Raised when routing finds lists with NO live replica shard."""

    def __init__(self, lists):
        self.lists = np.asarray(lists, dtype=np.int64)
        super().__init__(f"no live replica serves lists {self.lists.tolist()}")


def replica_owners(n_lists: int, n_shards: int, replicas: int) -> np.ndarray:
    """[R, n_lists] owning shard of each list's replicas (row 0 = primary).

    Replica r of list t lives on ``(shard_of_list(t) + r) % n_shards`` --
    like the primary, a pure function of (t, r, S): any frontend (or a
    checkpoint-recovery path re-routing onto a different shard count) can
    compute the whole placement without a table.
    """
    primary = shard_of_list(np.arange(n_lists, dtype=np.int64), n_shards)
    r = np.arange(replicas, dtype=np.int64)
    return (primary[None, :] + r[:, None]) % n_shards


def local_map_of(lists_s: np.ndarray, n_lists: int) -> np.ndarray:
    """Global -> shard-local list-id map for one shard's ascending lists."""
    m = np.zeros(n_lists, np.int64)
    m[lists_s] = np.arange(len(lists_s), dtype=np.int64)
    return m


def make_shard_mesh(n_shards: int):
    """Mesh with a "shard" axis, one device per shard; None if the process
    has too few jax devices (the engines then loop over shards instead)."""
    import jax

    devs = jax.devices()
    if len(devs) < n_shards:
        return None
    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), ("shard",))


@dataclass
class ShardedArena:
    """The global arena list-hash-split into per-shard sub-arenas.

    Routing metadata (``owner`` / ``local_list`` / ``lists_of``) is built
    eagerly -- it is O(n_lists).  The sub-arena SLICES (row gathers of the
    whole arena) materialize lazily on first ``shards`` access: a numpy
    engine built with ``shards=N`` never routes (see ``query_engine``), so
    it must never pay for N arena copies either.
    """

    n_shards: int
    arena: DeviceArena                  # the global (unsharded) arena
    owner: np.ndarray                   # [n_lists] primary shard per list
    local_list: np.ndarray              # [n_lists] id within the primary
    lists_of: list[np.ndarray]          # per shard: global list ids, asc
    mesh: object = None                 # Mesh over "shard", or None
    replicas: int = 1                   # copies of each list (R <= S)
    owner_r: np.ndarray | None = None   # [R, n_lists] replica owners
    local_r: np.ndarray | None = None   # [R, n_lists] local id per replica
    dead: np.ndarray | None = None      # [S] bool, honored by route()
    evicted: np.ndarray | None = None   # [S] bool: device buffers deleted
    _shards: list | None = field(default=None, repr=False, compare=False)
    _rows_of: list | None = field(default=None, repr=False, compare=False)
    _pchunks: list | None = field(default=None, repr=False, compare=False)
    # mesh path: per kind ("arena", "pivot") the [L, ...] arrays over the
    # live shards' sub-mesh, each assembled from one buffer per device
    _dev: dict = field(default_factory=dict, repr=False, compare=False)
    _pad: dict | None = field(default=None, repr=False, compare=False)
    _meshes: dict = field(default_factory=dict, repr=False, compare=False)

    @classmethod
    def build(cls, arena: DeviceArena, n_shards: int, mesh="auto", replicas: int = 1):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        # R > S would place two copies of a list on one shard: no extra
        # fault tolerance, just wasted rows -- clamp to full replication
        replicas = min(int(replicas), n_shards)
        n_lists = len(arena.list_blk_offsets) - 1
        owner_r = replica_owners(n_lists, n_shards, replicas)
        local_r = np.zeros((replicas, n_lists), np.int64)
        lists_of = []
        for s in range(n_shards):
            lists_s = np.flatnonzero((owner_r == s).any(axis=0))
            lists_of.append(lists_s)
            for r in range(replicas):
                sel = np.flatnonzero(owner_r[r] == s)
                local_r[r, sel] = np.searchsorted(lists_s, sel)
        if arena.block_codec is not None:
            # the shard_map bodies are single-codec: multi-codec arenas
            # serve shards through the host loop (per-shard EngineCores
            # dispatch per codec); an explicit mesh request cannot be met
            if mesh not in ("auto", None):
                raise ValueError("shard_mesh is single-codec; multi-codec "
                                 "arenas use the host shard loop "
                                 "(shard_mesh=None)")
            mesh = None
        if mesh == "auto":
            mesh = make_shard_mesh(n_shards)
        elif mesh is not None:
            if "shard" not in getattr(mesh, "axis_names", ()):
                raise ValueError("shard_mesh needs a 'shard' axis")
            # the SHARD AXIS specifically must be 1:1 with the shards --
            # the [S, ...] stacking splits dim 0 over it; a mesh whose
            # total device count merely multiplies out to n_shards would
            # stage S rows over a smaller axis and misroute
            axis = int(dict(mesh.shape)["shard"])
            if axis != n_shards:
                raise ValueError(f"mesh 'shard' axis is {axis}, need {n_shards} (1:1)")
        return cls(
            n_shards=n_shards,
            arena=arena,
            owner=owner_r[0],
            local_list=local_r[0],
            lists_of=lists_of,
            mesh=mesh,
            replicas=replicas,
            owner_r=owner_r,
            local_r=local_r,
            dead=np.zeros(n_shards, bool),
            evicted=np.zeros(n_shards, bool),
        )

    # ------------------------------------------------------------------
    # health-aware routing
    # ------------------------------------------------------------------
    def route(self, terms) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(owner, local, served) per term, honoring the ``dead`` mask.

        Picks each term's FIRST live replica (primary preferred, so the
        no-fault routing is byte-identical to ``replicas=1``).  ``served``
        is False where no live replica exists; engines raise
        ``ShardsUnavailable`` on those, ``ResilientEngine`` pre-filters
        them into degraded results instead.
        """
        terms = np.asarray(terms, dtype=np.int64)
        if self.owner_r is None or not self.dead.any():
            return self.owner[terms], self.local_list[terms], np.ones(len(terms), bool)
        own = self.owner_r[:, terms]
        alive = ~self.dead[own]
        served = alive.any(axis=0)
        pick = np.argmax(alive, axis=0)
        idx = np.arange(own.shape[1])
        return own[pick, idx], self.local_r[:, terms][pick, idx], served

    def route_one(self, t: int) -> tuple[int, int]:
        """Single-term routing; raises ``ShardsUnavailable`` if unserved."""
        owner, local, served = self.route(np.asarray([t], dtype=np.int64))
        if not served[0]:
            raise ShardsUnavailable([t])
        return int(owner[0]), int(local[0])

    def unserved_lists(self) -> np.ndarray:
        """Global list ids with NO live replica under the ``dead`` mask."""
        if self.owner_r is None or not self.dead.any():
            return np.zeros(0, np.int64)
        return np.flatnonzero(self.dead[self.owner_r].all(axis=0))

    @property
    def shards(self) -> list[DeviceArena]:
        """Per-shard sub-arenas (materialized on first access)."""
        n_lists = len(self.arena.list_blk_offsets) - 1
        if self._shards is None:
            self._shards = [
                _slice_arena(self.arena, lists_s, local_map_of(lists_s, n_lists))
                for lists_s in self.lists_of
            ]
        return self._shards

    @property
    def rows_of(self) -> list[np.ndarray]:
        """Per shard: the GLOBAL arena row of each shard-local row.

        The merge half of the pivot dispatch: kept blocks come back as
        shard-local rows and scatter onto the global address space through
        this map.  Routing-metadata-sized (one int per arena row), cached
        independently of the sub-arena slices (the mesh path releases
        those after staging).
        """
        if self._rows_of is None:
            lob = self.arena.part_list[self.arena.part_of_block]
            n_lists = len(self.arena.list_blk_offsets) - 1
            rows = []
            # membership, not owner equality: with replicas a global row
            # belongs to EVERY shard holding a copy of its list
            for lists_s in self.lists_of:
                in_s = np.zeros(n_lists, bool)
                in_s[lists_s] = True
                rows.append(np.flatnonzero(in_s[lob]))
            self._rows_of = rows
        return self._rows_of

    @property
    def pivot_chunks(self) -> list:
        """Per shard: the ``PivotChunks`` bound tiles of its sub-arena."""
        if self._pchunks is None:
            from repro.core.engine_core import build_pivot_chunks

            self._pchunks = [build_pivot_chunks(sub) for sub in self.shards]
        return self._pchunks

    def shard_nbytes(self) -> list[int]:
        return [sub.nbytes() for sub in self.shards]

    def route_rows(self, rows) -> tuple[np.ndarray, np.ndarray]:
        """(shard, shard-local row) of GLOBAL arena rows: each row goes to
        the first live replica of its list (``route``) and maps onto that
        shard's sub-arena through ``rows_of``.  Raises ``ShardsUnavailable``
        where a row's list has no live replica."""
        rows = np.asarray(rows, dtype=np.int64)
        a = self.arena
        lists = a.part_list[a.part_of_block[rows]]
        owner, _, served = self.route(lists)
        if not served.all():
            raise ShardsUnavailable(np.unique(lists[~served]))
        local = np.empty(len(rows), np.int64)
        for s in np.unique(owner):
            m = owner == s
            local[m] = np.searchsorted(self.rows_of[s], rows[m])
        return owner, local

    # ------------------------------------------------------------------
    # stacked [S, ...] placement for the shard_map dispatch
    # ------------------------------------------------------------------
    def live_shards(self) -> tuple:
        """Shards whose device buffers are resident, ascending."""
        return tuple(int(s) for s in np.flatnonzero(~self.evicted))

    def live_mesh(self):
        """The mesh over the live shards' devices (the whole mesh while no
        shard is evicted): the shard_map dispatchers run over it."""
        live = self.live_shards()
        if len(live) == self.n_shards:
            return self.mesh
        if live not in self._meshes:
            import jax

            devs = self.mesh.devices.reshape(-1)
            self._meshes[live] = jax.sharding.Mesh(
                np.asarray([devs[s] for s in live]), ("shard",)
            )
        return self._meshes[live]

    def _pads(self) -> dict:
        """The per-shard extents every stacked array is padded to.

        They depend on the arena alone, not on which lists hash where, so
        every list-id order of one corpus stacks to the same shapes -- the
        same device footprint and the same compiled programs -- and a
        restored shard stacks to the shapes the programs were built for.
        Partitions, lists and pivot chunks pad to the whole arena's counts
        (small arrays); blocks pad to ``SHARD_HEADROOM`` times an even
        share of the arena's blocks, or to the busiest shard where its
        lists outgrow that.
        """
        if self._pad is None:
            a = self.arena
            chunks = -(-np.diff(a.list_blk_offsets) // BLOCK_VALS)
            even = -(-self.replicas * a.n_blocks // self.n_shards)
            busiest = max(len(rows) for rows in self.rows_of)
            self._pad = {
                "nb": max(1, busiest,
                          min(a.n_blocks, int(np.ceil(SHARD_HEADROOM * even)))),
                "np": max(1, len(a.first_blk)),
                "nl": max(1, len(a.list_blk_offsets) - 1),
                "nc": max(1, int(chunks.sum())),
            }
        return self._pad

    def _stack_arena(self, s: int, sub: DeviceArena) -> dict:
        """Shard s's arena tiles as host arrays padded to ``_pads``.

        Padding rows are benign by construction: lens=1/data=0 decodes to
        zeros, ``block_last`` pads with int32 max (no list's block range
        covers a padding row), and ``list_blk_offsets`` pads by repeating
        its last value so any staged-padding cursor resolves past-the-end.
        Every narrowing is checked (``to_i32``).
        """
        if sub.block_codec is not None:
            # the shard_map bodies decode one codec; the engines gate the
            # mesh path off for multi-codec arenas before reaching here
            raise ValueError("shard_map stacking is single-codec; "
                             "multi-codec arenas use the host shard loop")
        pad = self._pads()
        nb_m, np_m, nl_m = pad["nb"], pad["np"], pad["nl"]
        nb, nl = sub.n_blocks, len(self.lists_of[s])
        st = {
            "lens": np.ones((nb_m, BLOCK_VALS), np.int32),
            "data": np.zeros((nb_m, BLOCK_BYTES), np.uint8),
            "block_base": np.zeros(nb_m, np.int32),
            "block_last": np.full(nb_m, INT32_MAX, np.int32),
            "part_of_block": np.zeros(nb_m, np.int32),
            "first_blk": np.zeros(np_m, np.int32),
            "list_blk_offsets": np.full(nl_m + 1, nb, np.int32),
        }
        st["lens"][:nb] = sub.lens[:nb]
        st["data"][:nb] = sub.data[:nb]
        st["block_base"][:nb] = to_i32(sub.block_base, "block_base")
        st["block_last"][:nb] = to_i32(sub.block_last(), "block_last")
        st["part_of_block"][:nb] = to_i32(sub.part_of_block, "part_of_block")
        st["first_blk"][: len(sub.first_blk)] = to_i32(sub.first_blk, "first_blk")
        st["list_blk_offsets"][: nl + 1] = to_i32(
            sub.list_blk_offsets, "list_blk_offsets"
        )
        r = sub.ranked
        if r is not None:
            st["freq_lens"] = np.ones((nb_m, BLOCK_VALS), np.int32)
            st["freq_data"] = np.zeros((nb_m, BLOCK_BYTES), np.uint8)
            st["norm_q"] = np.zeros((nb_m, BLOCK_VALS), np.uint8)
            st["idf"] = np.zeros(nl_m, np.float32)
            st["lob"] = np.zeros(nb_m, np.int32)
            st["freq_lens"][:nb] = r.freq_lens[:nb]
            st["freq_data"][:nb] = r.freq_data[:nb]
            st["norm_q"][:nb] = r.norm_q
            st["idf"][:nl] = r.idf
            st["lob"][:nb] = to_i32(sub.part_list[sub.part_of_block], "lob")
        return st

    def _stack_pivot(self, pc) -> dict:
        """One shard's pivot bound tiles padded to ``_pads``; padding
        chunks stage nblk 0, so nothing survives them."""
        nc_m, nc = self._pads()["nc"], len(pc.nblk)
        qb = np.zeros((nc_m, BLOCK_VALS), np.int32)
        nblk = np.zeros(nc_m, np.int32)
        qb[:nc] = pc.qb
        nblk[:nc] = pc.nblk
        return {"qb_chunks": qb, "chunk_nblk": nblk}

    def _upload(self, kind: str, s: int, sub: DeviceArena | None) -> dict:
        """Shard s's ``kind`` arrays as [1, ...] buffers on its device,
        stacked from ``sub`` (the restored slice) or from its host slice."""
        import jax

        if kind == "arena":
            host = self._stack_arena(
                s, sub if sub is not None else self.shards[s]
            )
        else:
            from repro.core.engine_core import build_pivot_chunks

            host = self._stack_pivot(
                build_pivot_chunks(sub) if sub is not None
                else self.pivot_chunks[s]
            )
        dev = self.mesh.devices.reshape(-1)[s]
        return {k: jax.device_put(v[None], dev) for k, v in host.items()}

    def _assemble(self, parts: dict) -> dict:
        """{shard: {name: [1, ...] buffer on its device}} -> {name: [L,
        ...] array over the live mesh}, sharing the buffers (no copy)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        live = sorted(parts)
        sharding = NamedSharding(self.live_mesh(), PartitionSpec("shard"))
        return {
            k: jax.make_array_from_single_device_arrays(
                (len(live),) + v.shape[1:], sharding,
                [parts[s][k] for s in live],
            )
            for k, v in parts[live[0]].items()
        }

    def _split(self, arrs: dict) -> dict:
        """Inverse of ``_assemble``: {shard: {name: its buffer}}."""
        devs = list(self.mesh.devices.reshape(-1))
        parts: dict = {}
        for k, a in arrs.items():
            for sh in a.addressable_shards:
                parts.setdefault(devs.index(sh.device), {})[k] = sh.data
        return parts

    def _resident(self, kind: str) -> dict:
        if self.mesh is None:
            raise ValueError(f"the stacked {kind} arrays need a mesh")
        got = self._dev.get(kind)
        if got is None:
            got = self._dev[kind] = self._assemble(
                {s: self._upload(kind, s, None) for s in self.live_shards()}
            )
        return got

    def stacked_dev(self) -> dict:
        """The stacked arena tiles, each shard's on its own device."""
        fresh = "arena" not in self._dev
        got = self._resident("arena")
        if fresh:
            # the host sub-arena slices existed only to feed the stacking:
            # on the mesh path nothing reads them after the device copies
            # exist, so release them (the property rebuilds on demand)
            self._shards = None
        return got

    def stacked_pivot_dev(self) -> dict:
        """The [S, ...] pivot bound tiles, staged LAZILY and separately.

        Only the ``ShardMapPivot`` dispatch of kernel-resident ranked
        engines reads ``qb_chunks`` / ``chunk_nblk``; staging them inside
        ``stacked_dev`` would charge every search/bm25 mesh engine the
        host re-tiling plus ~n_blocks x 512 B of device memory for
        arrays it never touches.
        """
        return self._resident("pivot")

    # ------------------------------------------------------------------
    # losing and restoring one shard's device buffers (mesh path)
    # ------------------------------------------------------------------
    def evict(self, s: int) -> None:
        """Delete shard s's buffers on its device.  The dispatchers then
        run over the sub-mesh of the live shards (``live_mesh``), so no
        cursor can reach s; routing has already marked it ``dead``."""
        if self.mesh is None or self.evicted[s]:
            return
        parts = {kind: self._split(arrs) for kind, arrs in self._dev.items()}
        self._dev = {}
        self.evicted[s] = True
        for kind, by_shard in parts.items():
            for buf in by_shard.pop(s, {}).values():
                buf.delete()
            if by_shard:
                self._dev[kind] = self._assemble(by_shard)

    def upload(self, s: int, sub: DeviceArena) -> dict:
        """Stack the restored slice of shard s and put it on its device,
        for ``install``; safe off the serving thread (it only reads)."""
        return {kind: self._upload(kind, s, sub) for kind in list(self._dev)}

    def install(self, s: int, sub: DeviceArena, parts: dict) -> None:
        """Swap shard s's uploaded buffers back into the stacked arrays
        (at a batch boundary); a kind first staged after the upload is
        stacked from ``sub`` here."""
        if self.mesh is None or not self.evicted[s]:
            return
        split = {kind: self._split(arrs) for kind, arrs in self._dev.items()}
        self.evicted[s] = False
        for kind, by_shard in split.items():
            by_shard[s] = parts.get(kind) or self._upload(kind, s, sub)
            self._dev[kind] = self._assemble(by_shard)


def _slice_arena(
    a: DeviceArena, lists_s: np.ndarray, local_list: np.ndarray
) -> DeviceArena:
    """Sub-arena of the lists in ``lists_s`` (ascending global ids).

    Pure gathers: the payload bytes, sidecars, and lane masks of a shard
    are row-for-row the global ones, so a 1-shard slice reproduces the
    global arena exactly.  Only the locate keys are recomputed -- same
    global ``stride``, shard-LOCAL list ids (ascending with the global
    ids, so the keys stay globally non-decreasing within the shard).

    Multi-codec arenas (§14) slice per codec: the shard's SVB rows and EF
    tiles are gathered from the global codec arrays through ``codec_row``,
    and shard-local codec rows are renumbered in block order -- the same
    pure-gather property, per codec.
    """
    in_shard = np.zeros(len(a.list_blk_offsets) - 1, bool)
    in_shard[lists_s] = True
    list_of_block = a.part_list[a.part_of_block]
    rows_s = np.flatnonzero(in_shard[list_of_block])
    parts_s = np.flatnonzero(in_shard[a.part_list])
    n_blk_s = a.n_blk[parts_s]
    first_blk_s = np.zeros(len(parts_s), np.int64)
    if len(parts_s):
        first_blk_s[1:] = np.cumsum(n_blk_s)[:-1]
    part_list_s = local_list[a.part_list[parts_s]]
    part_of_block_s = np.repeat(np.arange(len(parts_s), dtype=np.int64), n_blk_s)
    block_last = a.block_last()[rows_s]
    blk_counts = a.list_blk_offsets[lists_s + 1] - a.list_blk_offsets[lists_s]
    list_blk_offsets_s = np.zeros(len(lists_s) + 1, np.int64)
    np.cumsum(blk_counts, out=list_blk_offsets_s[1:])
    ranked = None
    if a.ranked is not None:
        r = a.ranked
        ranked = RankedSidecar(
            freq_lens=r.freq_lens[rows_s],
            freq_data=r.freq_data[rows_s],
            norm_q=r.norm_q[rows_s],
            block_max_q=r.block_max_q[rows_s],
            bound_scale=r.bound_scale,
            idf=r.idf[lists_s],
            list_ub=r.list_ub[lists_s],
            kmin=r.kmin,
            kstep=r.kstep,
            norm_table=r.norm_table,
            params=r.params,
        )
    block_codec_s = codec_row_s = ef_lo_s = ef_hi_s = ef_lbits_s = None
    if a.block_codec is None:
        lens_s, data_s = a.lens[rows_s], a.data[rows_s]
    else:
        from repro.core.arena import CODEC_EF

        block_codec_s = a.block_codec[rows_s]
        cr = a.codec_row[rows_s]
        ef_m = block_codec_s == CODEC_EF
        codec_row_s = np.zeros(len(rows_s), np.int64)
        codec_row_s[~ef_m] = np.arange(int((~ef_m).sum()))
        codec_row_s[ef_m] = np.arange(int(ef_m.sum()))
        lens_s, data_s = a.lens[cr[~ef_m]], a.data[cr[~ef_m]]
        ef_lo_s = a.ef_lo[cr[ef_m]]
        ef_hi_s = a.ef_hi[cr[ef_m]]
        ef_lbits_s = a.ef_lbits[cr[ef_m]]
    return DeviceArena(
        lens=lens_s,
        data=data_s,
        block_base=a.block_base[rows_s],
        block_keys=block_last + part_list_s[part_of_block_s] * a.stride,
        lane_valid=a.lane_valid[rows_s],
        part_of_block=part_of_block_s,
        first_blk=first_blk_s,
        n_blk=n_blk_s,
        sizes=a.sizes[parts_s],
        bases=a.bases[parts_s],
        part_list=part_list_s,
        list_blk_offsets=list_blk_offsets_s,
        stride=a.stride,
        n_blocks=len(rows_s),
        ranked=ranked,
        block_codec=block_codec_s,
        codec_row=codec_row_s,
        ef_lo=ef_lo_s,
        ef_hi=ef_hi_s,
        ef_lbits=ef_lbits_s,
    )


# --------------------------------------------------------------------------
# shard_map dispatchers: one device program over all shards at once
# --------------------------------------------------------------------------
class _ShardMapDispatch:
    """Shared staging/merge for the shard_map dispatchers.

    ``__call__(local_terms, probes, cuts)`` takes cursors PRE-SORTED by
    owning shard (``cuts`` delimiting each shard's run, as produced by the
    engines' stable argsort over owners), stages them into [L, B] int32
    buffers (L the live shards, B = pow2 bucket of the fullest shard;
    padding cursors probe local list 0 at docID 0), runs ONE jitted
    shard_map dispatch over the live shards' mesh, and slices each shard's
    run back out.  The int32 probe clip happens on the host, before
    staging -- same subtlety as the unsharded path.
    """

    def __init__(
        self,
        sharded: ShardedArena,
        backend: str,
        interpret: bool,
        max_bucket: int | None = None,
        injector=None,
    ):
        if sharded.mesh is None:
            raise ValueError("shard_map dispatch needs a mesh")
        self.sharded = sharded
        self.backend = backend
        self.interpret = interpret
        # shard-dispatch fault boundary (ISSUE-7): a ShardFaultInjector
        # consulted per dispatch for every shard that receives cursors --
        # the mesh-path mirror of the per-shard EngineCore check
        self.injector = injector
        self.stride = sharded.arena.stride
        # every shard holds whole lists, so the global arena's longest list
        # bounds each shard's locate search
        self.iters = sharded.arena.locate_iters
        # per-shard staging cap PER DISPATCH: batches whose fullest shard
        # exceeds it run in rounds, so gathered tiles stay bounded and jit
        # traces are reused (same role as TopKEngine.MAX_BUCKET unsharded)
        self.max_bucket = max_bucket
        # one jitted shard_map per set of live shards: an evicted shard's
        # device leaves the mesh until its restored buffers are installed;
        # per live set, the staged (B, ...) shapes its program has served
        # and those ``warm`` loaded it for
        self._fns: dict = {}
        self._ran: dict = {}
        self._warmed: dict = {}

    # padding value of the staged probe buffer; subclasses whose "probes"
    # are not docIDs (the pivot dispatch stages qmin there) override both
    PAD_PROBE = 0

    def _clip_probes(self, p):
        # clip BEFORE the int32 staging cast (probes >= 2^31 must
        # resolve past-the-end after the merge, not wrap negative)
        return np.clip(p, 0, self.stride - 1)

    def _stage(self, local_terms, probes, cuts, live):
        from repro.core.engine_core import pow2_bucket

        counts = np.diff(cuts)
        gone = np.ones(len(counts), bool)
        gone[list(live)] = False
        if counts[gone].any():
            raise RuntimeError("cursors routed to evicted shards "
                               f"{np.flatnonzero(gone & (counts > 0)).tolist()}")
        B = pow2_bucket(int(counts.max()) if len(counts) else 1)
        probes = np.asarray(probes)
        tp = np.zeros((len(live), B), np.int32)
        # probes may carry trailing axes (the pivot dispatch stages a
        # [128]-lane qmin tile per cursor); dim 0 stays the cursor axis
        pp = np.full((len(live), B) + probes.shape[1:], self.PAD_PROBE, np.int32)
        for i, s in enumerate(live):
            sl = slice(int(cuts[s]), int(cuts[s + 1]))
            tp[i, : counts[s]] = local_terms[sl]
            pp[i, : counts[s]] = self._clip_probes(probes[sl])
        return tp, pp, counts

    def _put(self, arr, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(arr, NamedSharding(mesh, PartitionSpec("shard")))

    def _merge(self, outs, cuts, counts, live):
        merged = []
        for o in outs:
            o = np.asarray(o)
            # per-cursor outputs may carry trailing axes (the pivot
            # dispatch returns a [B, 128] lane list per shard)
            m = np.empty((int(cuts[-1]),) + o.shape[2:], o.dtype)
            for i, s in enumerate(live):
                m[int(cuts[s]) : int(cuts[s + 1])] = o[i, : counts[s]]
            merged.append(m)
        return merged

    def _body(self, arrs: dict, terms, probes):
        raise NotImplementedError

    def _build(self, mesh):
        import jax
        from jax.sharding import PartitionSpec as P

        def wrapped(arrs, terms, probes):
            # one shard per device: strip / re-add the local shard axis
            local = {k: v[0] for k, v in arrs.items()}
            out = self._body(local, terms[0], probes[0])
            return tuple(o[None] for o in out)

        smap = jax.shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(P("shard"), P("shard"), P("shard")),
            out_specs=P("shard"),
            check_vma=False,
        )
        return jax.jit(smap)

    def _arrs(self) -> dict:
        """The stacked device arrays this dispatcher's body reads."""
        return self.sharded.stacked_dev()

    def _run(self, live, tp, pp, seen: dict):
        """ONE dispatch of the live shards' program on staged buffers,
        its staged shape noted in ``seen``."""
        mesh = self.sharded.live_mesh()
        fn = self._fns.get(live)
        if fn is None:
            fn = self._fns[live] = self._build(mesh)
        seen.setdefault(live, set()).add(pp.shape[1:])
        return fn(self._arrs(), self._put(tp, mesh), self._put(pp, mesh))

    def _dispatch(self, local_terms, probes, cuts):
        live = self.sharded.live_shards()
        tp, pp, counts = self._stage(local_terms, probes, cuts, live)
        return self._merge(self._run(live, tp, pp, self._ran), cuts, counts, live)

    def warm(self) -> None:
        """Load the live shards' program, on padding cursors, for every
        power-of-two bucket from the smallest any live set has served to
        twice the largest (a lost shard's cursors move to a replica, at
        most doubling its run), so the waves served while a shard is out
        load no program.  Shapes this live set has run are skipped."""
        import jax

        live = self.sharded.live_shards()
        ran = set().union(*self._ran.values())
        if not live or not ran:
            return
        top = 2 * max(sh[0] for sh in ran)
        if self.max_bucket is not None:
            top = min(top, self.max_bucket)
        want, b = set(), min(sh[0] for sh in ran)
        while b <= top:
            want |= {(b,) + sh[1:] for sh in ran}
            b *= 2
        done = self._ran.get(live, set()) | self._warmed.get(live, set())
        for shape in sorted(want - done):
            tp = np.zeros((len(live), shape[0]), np.int32)
            pp = np.full((len(live),) + shape, self.PAD_PROBE, np.int32)
            jax.block_until_ready(self._run(live, tp, pp, self._warmed))

    def __call__(self, local_terms, probes, cuts):
        counts = np.diff(cuts)
        if self.injector is not None:
            self.injector.check_shards(np.flatnonzero(counts > 0))
        if obs.enabled():
            kind = type(self).__name__
            for s in np.flatnonzero(counts > 0):
                obs.count(
                    "shard_dispatch", shard=str(int(s)), path="shard_map", kind=kind
                )
        mb = self.max_bucket
        if mb is None or len(counts) == 0 or int(counts.max()) <= mb:
            return self._dispatch(local_terms, probes, cuts)
        # round r takes cursors [cuts[s] + r*mb, +mb) of EVERY shard, so no
        # dispatch stages more than max_bucket rows per shard
        n = int(cuts[-1])
        outs = None
        for r in range(-(-int(counts.max()) // mb)):
            lo = np.minimum(cuts[:-1] + r * mb, cuts[1:])
            hi = np.minimum(lo + mb, cuts[1:])
            idx = np.concatenate([np.arange(int(a), int(b)) for a, b in zip(lo, hi)])
            sub_cuts = np.zeros(len(cuts), np.int64)
            np.cumsum(hi - lo, out=sub_cuts[1:])
            res = self._dispatch(local_terms[idx], probes[idx], sub_cuts)
            if outs is None:
                outs = [np.empty((n,) + o.shape[1:], o.dtype) for o in res]
            for o, ro in zip(outs, res):
                o[idx] = ro
        return outs


class ShardMapSearch(_ShardMapDispatch):
    """Fused locate -> decode_search over every shard in one dispatch.

    Returns (value, rank) int64 arrays aligned with the sorted cursor
    order; past-the-end cursors are pre-masked to -1 (same contract as the
    unsharded device pipeline).
    """

    def _body(self, arrs, terms, probes):
        import jax.numpy as jnp

        from repro.core.engine_core import decode_search_graph, locate_graph

        rows, pe, past = locate_graph(
            arrs["block_last"],
            arrs["list_blk_offsets"],
            self.stride,
            self.iters,
            terms,
            probes,
        )
        value, rank_in = decode_search_graph(
            arrs["lens"][rows],
            arrs["data"][rows],
            arrs["block_base"][rows],
            pe,
            self.backend,
            self.interpret,
        )
        part = arrs["part_of_block"][rows]
        rank = (rows - arrs["first_blk"][part]) * BLOCK_VALS + rank_in
        return jnp.where(past, -1, value), jnp.where(past, -1, rank)

    def __call__(self, local_terms, probes, cuts):
        value, rank = super().__call__(local_terms, probes, cuts)
        return value.astype(np.int64), rank.astype(np.int64)


class ShardMapBM25(_ShardMapDispatch):
    """Fused bm25 locate -> decode+score+match over every shard at once.

    Returns f32 contributions aligned with the sorted cursor order (0.0
    past the end / non-member, as the unsharded device pipeline).
    """

    def __init__(
        self,
        sharded,
        backend,
        interpret,
        k1p1: float,
        max_bucket: int | None = None,
        injector=None,
    ):
        if sharded.arena.ranked is None:
            raise ValueError("ShardMapBM25 needs a ranked arena")
        super().__init__(
            sharded, backend, interpret, max_bucket=max_bucket, injector=injector
        )
        self.k1p1 = float(k1p1)
        self.norm_table = sharded.arena.ranked.norm_table

    def _body(self, arrs, terms, probes):
        import jax.numpy as jnp

        from repro.core.engine_core import locate_graph
        from repro.kernels.bm25_score.ops import score_probe_graph

        rows, pe, past = locate_graph(
            arrs["block_last"],
            arrs["list_blk_offsets"],
            self.stride,
            self.iters,
            terms,
            probes,
        )
        contrib = score_probe_graph(
            arrs["lens"][rows],
            arrs["data"][rows],
            arrs["freq_lens"][rows],
            arrs["freq_data"][rows],
            arrs["norm_q"][rows].astype(jnp.int32),
            arrs["block_base"][rows],
            pe,
            arrs["idf"][arrs["lob"][rows]],
            self.norm_table,
            self.k1p1,
            self.backend,
            self.interpret,
        )
        return (jnp.where(past, jnp.float32(0.0), contrib),)

    def __call__(self, local_terms, probes, cuts):
        (contrib,) = super().__call__(local_terms, probes, cuts)
        return contrib


class ShardMapScoreRows(ShardMapBM25):
    """All-lane BM25 scores of shard-LOCAL arena rows, every shard at once.

    Cursors are rows of their shard's stacked freq tiles (global rows map
    through ``ShardedArena.route_rows``); each shard's chip gathers and
    scores its own rows with ``bm25_score_blocks``.  A shard's tiles are a
    pure row gather of the global arena and its idf the global list's, so
    the [n, 128] f32 scores (aligned with the sorted cursor order) are
    bit-identical to scoring the global arena.
    """

    def _body(self, arrs, rows, _probes):
        import jax.numpy as jnp

        from repro.kernels.bm25_score.ops import score_rows_graph

        return (score_rows_graph(
            arrs["freq_lens"][rows],
            arrs["freq_data"][rows],
            arrs["norm_q"][rows].astype(jnp.int32),
            arrs["idf"][arrs["lob"][rows]],
            self.norm_table,
            self.k1p1,
            self.backend,
            self.interpret,
        ),)

    def __call__(self, local_rows, cuts):
        # rows are the only per-cursor input: the probe slot stages zeros
        return super().__call__(
            local_rows, np.zeros(len(local_rows), np.int64), cuts
        )


class ShardMapPivot(_ShardMapDispatch):
    """Block-Max pivot selection over every shard in one dispatch (§9).

    Cursors here are (shard-local chunk row, qmin) pairs -- the "probe"
    slot carries the per-(query, term) minimal admissible bound code the
    host reduced from (theta, multiplicities, co-candidate bounds), so
    broadcasting a new theta to every shard is just staging fresh qmins.
    Returns (compact [n, 128], count [n], pivot [n], maxq [n]) int64
    aligned with the sorted cursor order; ``compact`` lists each cursor's
    surviving SHARD-LOCAL block lanes (callers map lane -> local row ->
    global row via ``PivotChunks.base`` and ``ShardedArena.rows_of``).
    Padding cursors stage qmin = QMIN_NONE and keep nothing.
    """

    PAD_PROBE = QMIN_NONE  # padding cursors prune their whole chunk

    def __init__(self, sharded, backend, interpret, max_bucket=None, injector=None):
        if sharded.arena.ranked is None:
            raise ValueError("ShardMapPivot needs a ranked arena")
        super().__init__(
            sharded, backend, interpret, max_bucket=max_bucket, injector=injector
        )

    def _clip_probes(self, p):
        # qmins are bound codes in [0, QMIN_NONE], not docIDs: clip to the
        # code range (the docID clip could LOWER a qmin on tiny-stride
        # corpora and desync the sharded kept set from the unsharded one)
        return np.clip(p, 0, self.PAD_PROBE)

    def _arrs(self) -> dict:
        # only the pivot tiles: the bound chunks are staged lazily and
        # separately from the search/bm25 arrays (stacked_pivot_dev), so
        # mirror-resident mesh engines never pay for them
        return self.sharded.stacked_pivot_dev()

    def _body(self, arrs, rows, qmins):
        from repro.core.engine_core import pivot_graph

        compact, count, pivot, maxq = pivot_graph(
            arrs["qb_chunks"][rows],
            qmins,
            arrs["chunk_nblk"][rows],
            self.backend,
            self.interpret,
        )
        return compact, count, pivot, maxq

    def __call__(self, local_rows, qmins, cuts):
        compact, count, pivot, maxq = super().__call__(local_rows, qmins, cuts)
        return (
            compact.astype(np.int64),
            count.astype(np.int64),
            pivot.astype(np.int64),
            maxq.astype(np.int64),
        )
