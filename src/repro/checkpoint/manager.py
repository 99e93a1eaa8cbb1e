"""Fault-tolerant checkpointing.

Features required for 1000+-node runnability:
  * atomic writes (tmp file + rename) -- a killed host never corrupts the
    latest checkpoint;
  * retention of the last ``keep`` checkpoints;
  * async save (background thread) so the train loop is not blocked;
  * restore-to-new-mesh: leaves are stored logically (full arrays); on load
    they are ``jax.device_put`` with the *target* sharding, so a job may
    restart on a different mesh shape (elastic scaling);
  * integer arrays that are strictly increasing (data-pipeline shard
    indices, CSR adjacency, sample orders) are stored OptVB-packed with the
    paper's optimal partitioning -- the framework's own codec (DESIGN.md
    section 4.3).
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import threading
import time
import zipfile

import numpy as np

import jax

from repro import obs
from repro.core import build_partitioned_index
from repro.core.costs import gaps_from_sorted
from repro.core.index import PartitionedIndex


# --------------------------------------------------------------------------
# OptVB packing of sorted integer arrays
# --------------------------------------------------------------------------

def pack_sorted_int_array(arr: np.ndarray) -> dict:
    """Pack a strictly-increasing int array with the paper's codec."""
    idx = build_partitioned_index([np.asarray(arr, dtype=np.int64)], "optimal")
    return {
        "kind": "optvb",
        "n": int(arr.size),
        "endpoints": idx.endpoints,
        "sizes": idx.sizes,
        "tags": idx.tags,
        "offsets": idx.offsets,
        "payload": idx.payload,
        "list_part_offsets": idx.list_part_offsets,
        "list_sizes": idx.list_sizes,
    }


def unpack_sorted_int_array(packed: dict) -> np.ndarray:
    """Decode a packed array on the host, partition by partition, into
    int64.  No device arena: its docID lanes are int32, and leaves such as
    an arena's ``block_keys`` (list x stride + docID) pass 2^31."""
    idx = PartitionedIndex(
        n_lists=1,
        list_part_offsets=packed["list_part_offsets"],
        list_sizes=packed["list_sizes"],
        endpoints=packed["endpoints"],
        sizes=packed["sizes"],
        tags=packed["tags"],
        offsets=packed["offsets"],
        payload=packed["payload"],
    )
    chunks, base = [], -1
    for p in range(len(idx.endpoints)):
        chunks.append(idx._decode_partition(p, base))
        base = int(idx.endpoints[p])
    return np.concatenate(chunks) if chunks else np.zeros(0, np.int64)


def _is_strictly_increasing(a: np.ndarray) -> bool:
    return a.ndim == 1 and a.size > 1 and bool(np.all(a[1:] > a[:-1]))


# everything a corrupt/truncated checkpoint can throw at restore time: bad
# zip central directory (truncated npz), short member payload or shape
# mismatch (ValueError), missing npz keys (KeyError), unreadable files
# (OSError), bad JSON (json.JSONDecodeError is a ValueError)
RESTORE_ERRORS = (OSError, ValueError, KeyError, zipfile.BadZipFile)


# --------------------------------------------------------------------------
# Manager
# --------------------------------------------------------------------------

class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self.async_save = async_save
        self._thread: threading.Thread | None = None

    # ---------------- save ----------------
    def save(self, step: int, tree) -> None:
        host_tree = jax.tree_util.tree_map(np.asarray, jax.device_get(tree))
        if self.async_save:
            self.wait()
            self._thread = threading.Thread(
                target=self._save_sync, args=(step, host_tree), daemon=True
            )
            self._thread.start()
        else:
            self._save_sync(step, host_tree)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _save_sync(self, step: int, host_tree) -> None:
        with obs.timer("checkpoint_save_ms"):
            leaves, treedef = jax.tree_util.tree_flatten(host_tree)
            arrays = {}
            manifest = {"step": step, "treedef": str(treedef), "leaves": []}
            for i, leaf in enumerate(leaves):
                leaf = np.asarray(leaf)
                entry = {"i": i, "dtype": str(leaf.dtype), "shape": list(leaf.shape)}
                if leaf.dtype.kind in "iu" and _is_strictly_increasing(leaf):
                    packed = pack_sorted_int_array(leaf)
                    entry["codec"] = "optvb"
                    for k, v in packed.items():
                        if isinstance(v, np.ndarray):
                            arrays[f"l{i}_{k}"] = v
                        else:
                            entry[k] = v
                else:
                    entry["codec"] = "raw"
                    arrays[f"l{i}"] = leaf
                manifest["leaves"].append(entry)

            tmp = self.dir / f".tmp-{step}-{time.time_ns()}"
            tmp.mkdir()
            np.savez(tmp / "arrays.npz", **arrays)
            (tmp / "manifest.json").write_text(json.dumps(manifest))
            final = self.dir / f"step_{step:010d}"
            if final.exists():
                shutil.rmtree(final)
            tmp.rename(final)  # atomic publish
            self._gc()
        if obs.enabled():
            obs.count(
                "checkpoint_saved_bytes",
                sum(a.nbytes for a in arrays.values()),
            )
            obs.count("checkpoint_saves")

    def _gc(self) -> None:
        ckpts = sorted(self.dir.glob("step_*"))
        for old in ckpts[: -self.keep]:
            shutil.rmtree(old, ignore_errors=True)

    # ---------------- restore ----------------
    def steps(self) -> list[int]:
        """All retained checkpoint steps, ascending."""
        return sorted(int(p.name.split("_")[1]) for p in self.dir.glob("step_*"))

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def manifest(self, step: int) -> dict:
        """Parsed manifest of one retained step (raises if unreadable)."""
        path = self.dir / f"step_{step:010d}" / "manifest.json"
        return json.loads(path.read_text())

    def restore(self, target_tree, step: int | None = None, shardings=None):
        """Load into the structure of ``target_tree``.

        ``shardings``: optional pytree of Sharding -- enables restore onto a
        different mesh than the checkpoint was written from (elastic).

        With ``step=None`` a corrupt or truncated newest checkpoint (bad
        JSON, short zip payload, missing members) is SKIPPED with a warning
        and the newest *intact* retained step restores instead -- a
        half-written checkpoint from a crashed host must degrade recovery
        by one save interval, not kill it.  An explicit ``step`` never
        falls back: the caller asked for that exact state.
        """
        if step is not None:
            return self._restore_step(target_tree, step, shardings)
        steps = self.steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        last_err: Exception | None = None
        for s in reversed(steps):
            try:
                return self._restore_step(target_tree, s, shardings)
            except RESTORE_ERRORS as e:
                print(
                    f"[ckpt] step {s} unreadable ({type(e).__name__}: {e}); "
                    "falling back to the previous retained step",
                    file=sys.stderr,
                )
                last_err = e
        raise FileNotFoundError(
            f"no intact checkpoint in {self.dir}"
        ) from last_err

    def _restore_step(self, target_tree, step: int, shardings=None):
        path = self.dir / f"step_{step:010d}"
        nbytes = 0
        with obs.timer("checkpoint_restore_ms"):
            manifest = json.loads((path / "manifest.json").read_text())
            data = np.load(path / "arrays.npz")
            leaves_t, treedef = jax.tree_util.tree_flatten(target_tree)
            out = []
            for entry, tgt in zip(manifest["leaves"], leaves_t):
                i = entry["i"]
                if entry["codec"] == "optvb":
                    packed = {k: data[f"l{i}_{k}"] for k in
                              ("endpoints", "sizes", "tags", "offsets", "payload",
                               "list_part_offsets", "list_sizes")}
                    arr = unpack_sorted_int_array(packed).astype(entry["dtype"])
                else:
                    arr = data[f"l{i}"]
                nbytes += arr.nbytes
                out.append(arr.reshape(entry["shape"]))
            tree = jax.tree_util.tree_unflatten(treedef, out)
            if shardings is not None:
                tree = jax.tree_util.tree_map(
                    lambda a, s: jax.device_put(a, s), tree, shardings
                )
        if obs.enabled():
            obs.count("checkpoint_restored_bytes", nbytes)
            obs.count("checkpoint_restores")
        return tree, step
