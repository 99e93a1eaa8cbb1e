"""jit'd wrappers + host-side packer for the block Stream-VByte decoder.

Backend policy lives here (shared by these ops and ``core.query_engine``):
``default_backend()`` picks the compiled Pallas kernel on TPU/GPU and the
vectorized-numpy mirror on CPU; ``default_interpret()`` only emulates the
Pallas kernel (interpret mode) when no accelerator is present.  Passing
``interpret=None`` anywhere means "resolve via ``default_interpret()``".
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from .kernel import (
    BLOCK_BYTES,
    BLOCK_VALS,
    BM,
    META_BASE,
    META_PROBE,
    decode_blocks,
    decode_search_blocks,
)
from .ref import decode_blocks_ref, decode_search_ref


def default_backend() -> str:
    """"pallas" (compiled) on an accelerator, vectorized numpy otherwise.

    ``REPRO_BACKEND=numpy|ref|pallas`` overrides the choice -- the knob the
    CI matrix uses to run the whole suite through the jitted device
    pipeline (``ref``) on CPU-only runners.  A JAX that fails to
    initialise raises: serving from the host would hide the device.
    """
    env = os.environ.get("REPRO_BACKEND", "").strip()
    if env:
        if env not in ("numpy", "ref", "pallas"):
            raise ValueError(
                f"REPRO_BACKEND={env!r}: expected numpy, ref, or pallas"
            )
        return env
    return "pallas" if jax.default_backend() in ("tpu", "gpu") else "numpy"


def default_interpret() -> bool:
    """Pallas interpret mode only off-accelerator: TPU/GPU must COMPILE.

    Raises, like ``default_backend``, when JAX fails to initialise."""
    return jax.default_backend() not in ("tpu", "gpu")


def _resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def pack_blocks(values: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
    """Encode uint32 values into the kernel's block layout.

    Returns (lens [nb,128] int32, data [nb,512] uint8, n_values).  Blocks are
    padded to a multiple of BM * BLOCK_VALS values (pad value 0 -> len 1).
    """
    # lazy: repro.core.costs pulls in the repro.core package, whose engines
    # import back into this module (a cycle when ops is imported first)
    from repro.core.costs import bit_length_np

    values = np.asarray(values, dtype=np.uint32)
    n = values.size
    per_super = BM * BLOCK_VALS
    n_pad = ((n + per_super - 1) // per_super) * per_super
    v = np.zeros(n_pad, np.uint32)
    v[:n] = values
    lens = np.clip((bit_length_np(v) + 7) // 8, 1, 4).astype(np.int32)
    lens = lens.reshape(-1, BLOCK_VALS)
    nb = lens.shape[0]
    data = np.zeros((nb, BLOCK_BYTES), np.uint8)
    v = v.reshape(nb, BLOCK_VALS).astype(np.uint64)
    ends = np.cumsum(lens, axis=1)
    starts = ends - lens
    for j in range(4):
        sel = lens > j
        rows, cols = np.nonzero(sel)
        data[rows, starts[sel] + j] = ((v[sel] >> np.uint64(8 * j)) & np.uint64(0xFF)).astype(np.uint8)
    return lens, data, n


def decode_blocks_np(lens: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Pure-numpy mirror of the block decoder (the off-accelerator path).

    lens: [nb, 128] in 1..4; data: [nb, 512] uint8 -> [nb, 128] int64.
    Same layout as ``decode_blocks`` / ``decode_blocks_ref`` but with no jax
    in the loop, so CPU-served query batches avoid dispatch overhead.
    """
    lens = np.asarray(lens, dtype=np.int64)
    data = np.asarray(data, dtype=np.uint8)
    starts = np.cumsum(lens, axis=1) - lens
    out = np.zeros(lens.shape, dtype=np.int64)
    rows = np.arange(lens.shape[0])[:, None]
    for j in range(4):
        sel = lens > j
        byte = data[rows, np.where(sel, starts + j, 0)].astype(np.int64)
        out |= np.where(sel, byte << (8 * j), 0)
    return out


def decode_block_rows(
    lens_rows: np.ndarray,
    data_rows: np.ndarray,
    backend: str = "numpy",
    interpret: bool | None = None,
) -> np.ndarray:
    """Decode a gathered set of block rows with the chosen backend.

    backend: "numpy" (vectorized host decode), "ref" (jnp oracle), or
    "pallas" (the MXU one-hot-matmul kernel; interpret=None auto-selects
    compiled off the default jax backend).  Rows need not be a multiple of
    BM -- the pallas path pads internally, to a power-of-two row bucket so
    that every list length reuses one of a few compiled kernel shapes.
    Returns [n_rows, 128] int64.
    """
    if backend == "numpy":
        return decode_blocks_np(lens_rows, data_rows)
    if backend == "ref":
        out = decode_blocks_ref(
            jnp.asarray(np.asarray(lens_rows, np.int32)), jnp.asarray(data_rows)
        )
        return np.asarray(out).astype(np.int64)
    if backend == "pallas":
        n_rows = lens_rows.shape[0]
        pad = max(BM, 1 << (max(n_rows, 1) - 1).bit_length()) - n_rows
        if pad:
            lens_rows = np.concatenate(
                [lens_rows, np.ones((pad, BLOCK_VALS), np.int32)]
            )
            data_rows = np.concatenate(
                [data_rows, np.zeros((pad, BLOCK_BYTES), np.uint8)]
            )
        out = decode_blocks(
            jnp.asarray(np.asarray(lens_rows, np.int32)),
            jnp.asarray(data_rows),
            interpret=_resolve_interpret(interpret),
        )
        return np.asarray(out)[:n_rows].astype(np.int64)
    raise ValueError(f"unknown backend {backend!r}")


def decode(lens, data, n: int, use_kernel: bool = True,
           interpret: bool | None = None):
    """Block-decode to values [n] (int32)."""
    if use_kernel:
        out = decode_blocks(jnp.asarray(lens), jnp.asarray(data),
                            interpret=_resolve_interpret(interpret))
    else:
        out = decode_blocks_ref(jnp.asarray(lens.astype(np.int32)), jnp.asarray(data))
    return out.reshape(-1)[:n]


def decode_sorted(lens, data, n: int, base: int = -1, **kw):
    """Decode d-gap-encoded sorted ids (gap-1 convention, see core.costs)."""
    gaps = decode(lens, data, n, **kw).astype(jnp.int64) + 1
    return base + jnp.cumsum(gaps)


# --------------------------------------------------------------------------
# Fused decode + NextGEQ over arena rows (DESIGN.md §4)
# --------------------------------------------------------------------------

def decode_search_np(
    lens: np.ndarray, data: np.ndarray, block_base: np.ndarray,
    rows: np.ndarray, probes: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized-numpy fused search: decode each cursor's arena row and
    resolve NextGEQ in one pass.  Duplicate rows are decoded once.

    Returns (value [C] int64, rank [C] int64): smallest in-row value >=
    probe (value of the LAST lane when none qualifies) and the count of
    in-row values < probe (0..128).
    """
    rows = np.asarray(rows, dtype=np.int64)
    probes = np.asarray(probes, dtype=np.int64)
    urows, inv = np.unique(rows, return_inverse=True)
    gaps = decode_blocks_np(lens[urows], data[urows])
    uvals = np.asarray(block_base, np.int64)[urows][:, None] + np.cumsum(
        gaps + 1, axis=1
    )
    vals = uvals[inv]  # [C, 128]
    rank = (vals < probes[:, None]).sum(axis=1)
    value = vals[np.arange(len(rows)), np.minimum(rank, BLOCK_VALS - 1)]
    return value, rank


def decode_search(
    lens, data, block_base, rows, probes,
    backend: str = "numpy", interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Fused decode+NextGEQ over arena rows; numpy in/out, all backends.

    lens [nb,128] int32 / data [nb,512] uint8 / block_base [nb]: the block
    arena (see ``repro.core.arena``).  rows [C]: the arena row located for
    each cursor.  probes [C]: absolute probe docIDs; each must be <= the
    last real value of its row for the result to be meaningful (callers
    mask out-of-range cursors -- the engine clamps them to probe 0).

    Returns (value [C] int64, rank [C] int64) as ``decode_search_np``.
    This convenience wrapper ships the gathered rows host->device per call;
    the QueryEngine's jitted pipeline keeps everything resident instead.
    """
    if backend == "numpy":
        return decode_search_np(lens, data, block_base, rows, probes)
    if backend not in ("ref", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    rows = np.asarray(rows, dtype=np.int64)
    n = len(rows)
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    pad = (-n) % BM
    rows_p = np.concatenate([rows, np.zeros(pad, np.int64)]) if pad else rows
    probes_p = np.zeros(n + pad, np.int64)
    probes_p[:n] = np.asarray(probes, dtype=np.int64)
    lens_g = jnp.asarray(np.asarray(lens, np.int32)[rows_p])
    data_g = jnp.asarray(np.asarray(data, np.uint8)[rows_p])
    bases_g = np.asarray(block_base, np.int64)[rows_p].astype(np.int32)
    probes_i = probes_p.astype(np.int32)
    if backend == "ref":
        value, rank = decode_search_ref(
            lens_g, data_g, jnp.asarray(bases_g), jnp.asarray(probes_i)
        )
    else:
        meta = np.zeros((n + pad, BLOCK_VALS), np.int32)
        meta[:, META_BASE] = bases_g
        meta[:, META_PROBE] = probes_i
        out = decode_search_blocks(
            lens_g, data_g, jnp.asarray(meta),
            interpret=_resolve_interpret(interpret),
        )
        value, rank = out[:, 0], out[:, 1]
    return (
        np.asarray(value)[:n].astype(np.int64),
        np.asarray(rank)[:n].astype(np.int64),
    )


# Machine-readable triple contract (DESIGN.md §10), verified on every PR by
# repro.analyze.contracts: a PURE LITERAL (the checker ast.literal_eval's it
# without importing jax).  Params are "name:role"; "meta:staging=a+b" marks
# a pallas staging tile carrying roles a+b, ":gather" a numpy-only row
# gather, ":config" a backend-local knob -- both excluded from the
# cross-backend role agreement.
CONTRACT = {
    "family": "vbyte_decode",
    "identity": "integer",
    "ops": {
        "decode": {
            "roles": ["lens", "data"],
            "out": ["vals:int64[nr,128]"],
            "backends": {
                "numpy": {
                    "module": "ops",
                    "fn": "decode_blocks_np",
                    "params": ["lens:lens", "data:data"],
                },
                "ref": {
                    "module": "ref",
                    "fn": "decode_blocks_ref",
                    "params": ["lens:lens", "data:data"],
                },
                "pallas": {
                    "module": "kernel",
                    "fn": "decode_blocks",
                    "params": ["lens:lens", "data:data", "interpret:config"],
                },
            },
        },
        "decode_search": {
            "roles": ["lens", "data", "base", "probe"],
            "out": ["value:int64[nr]", "rank:int64[nr]"],
            "backends": {
                "numpy": {
                    "module": "ops",
                    "fn": "decode_search_np",
                    "params": [
                        "lens:lens",
                        "data:data",
                        "block_base:base",
                        "rows:gather",
                        "probes:probe",
                    ],
                },
                "ref": {
                    "module": "ref",
                    "fn": "decode_search_ref",
                    "params": [
                        "lens_rows:lens",
                        "data_rows:data",
                        "bases:base",
                        "probes:probe",
                    ],
                },
                "pallas": {
                    "module": "kernel",
                    "fn": "decode_search_blocks",
                    "params": [
                        "lens:lens",
                        "data:data",
                        "meta:staging=base+probe",
                        "interpret:config",
                    ],
                },
            },
        },
    },
}
