"""FMA/contraction sanitizer (checker 2 of ``repro.analyze``; DESIGN.md §10).

Compiles the single-source jit-graph halves the engines are built
from (``engine_core.GRAPH_CONTRACTS``: locate / decode_search / ef_search /
pivot / pivot_score / score_rows / score_probe) with synthetic gathered-row
arguments, then walks the
OPTIMIZED HLO -- the op stream XLA actually runs, after fusion -- with the
shared walker of ``launch.hlo_walker`` and asserts the identity class each
graph declared:

* ``integer`` graphs must be float-free end to end.  The decode / locate /
  pivot pipelines are bit-identical across backends *by construction*
  because every op is integer; a float dtype anywhere in their optimized
  HLO means someone routed a value through f32 math (e.g. an accidental
  mean, a float cast "for safety") and the construction no longer holds.

* ``f32-bit-exact`` graphs (BM25 scoring) promise the same f32 op ORDER on
  every backend.  XLA is free to rewrite ``a * b + c`` into a fused
  multiply-add whose intermediate is not rounded -- 1 ulp off the
  two-op sequence (exactly why the norm dequant is a table GATHER, see
  ``bm25.norm_table``) -- so any float ``add``/``subtract`` consuming a
  ``multiply`` result, and any float ``dot`` whose contraction size is
  outside the graph's allow-list, fails the gate.

Checked on the ``ref`` backend: that is the lowering whose HLO the
bit-identity contract quantifies over (pallas bodies are checked for
equivalence by the property tests; numpy never lowers).
"""

from __future__ import annotations

import re

import numpy as np

from repro.analyze.report import Finding
from repro.launch.hlo_walker import (
    entry_computation,
    iter_graph,
    operand_names,
    parse_hlo,
    shape_dtypes,
)

FLOAT_TYPES = {"f16", "bf16", "f32", "f64", "c64", "c128", "f8e4m3fn", "f8e5m2"}
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dot_contraction(ins, comp) -> int:
    """Contraction size of one dot instr (product of lhs contracted dims)."""
    from repro.launch.hlo_walker import _shape_dims

    m = _CONTRACT_RE.search(ins.line)
    ops = operand_names(ins.line)
    lhs_type = comp.symbols.get(ops[0]) if ops else None
    size = 1
    if lhs_type and m and m.group(1):
        _, ldims = _shape_dims(lhs_type)
        for d in m.group(1).split(","):
            di = int(d)
            if di < len(ldims):
                size *= ldims[di]
    return size


def check_hlo_text(
    text: str, identity: str, graph: str, allow_dots=()
) -> list[Finding]:
    """Findings for one optimized-HLO module under one identity class."""
    comps = parse_hlo(text)
    findings: list[Finding] = []
    for comp, ins, _mult, _tc in iter_graph(comps, entry_computation(comps)):
        is_float = bool(shape_dtypes(ins.type_str) & FLOAT_TYPES)
        where = f"{graph}:{comp.name}/{ins.name}"
        if identity == "integer":
            if is_float:
                findings.append(
                    Finding(
                        "hlo",
                        "float-in-integer-graph",
                        where,
                        f"{ins.op} produces {ins.type_str.strip()} inside an "
                        "integer-class graph",
                    )
                )
            continue
        if not is_float:
            continue
        if ins.op in ("add", "subtract"):
            defs = {i.name: i for i in comp.instrs}
            for op_name in operand_names(ins.line):
                src = defs.get(op_name)
                if src is not None and src.op == "multiply":
                    findings.append(
                        Finding(
                            "hlo",
                            "fma-contraction",
                            where,
                            f"float {ins.op} consumes multiply {src.name!r}: "
                            "XLA contracts this into an unrounded FMA, "
                            "breaking f32 bit-exactness",
                        )
                    )
        if ins.op == "dot":
            size = _dot_contraction(ins, comp)
            if size not in tuple(allow_dots):
                findings.append(
                    Finding(
                        "hlo",
                        "dot-contraction",
                        where,
                        f"float dot with contraction size {size} not in the "
                        f"graph's allow-list {sorted(allow_dots)}",
                    )
                )
    return findings


def graph_specs(backend: str = "ref", nr: int | None = None, nb: int = 64):
    """name -> (traceable fn, example args) for the registered graph halves.

    Arguments are synthetic but shaped exactly as the engines stage them:
    one pow2 bucket of ``nr`` gathered arena rows (default ``BM``) over an
    ``nb``-block arena (values are irrelevant -- only the traced graph
    matters).  Pallas kernels are traced compiled (``interpret=False``).
    """
    import jax.numpy as jnp

    from repro.core.engine_core import (
        decode_search_graph,
        ef_search_graph,
        locate_graph,
        pivot_graph,
        pivot_score_graph,
    )
    from repro.kernels.bm25_score.ops import score_probe_graph, score_rows_graph
    from repro.kernels.ef_search.kernel import EF_HI_WORDS
    from repro.kernels.vbyte_decode.kernel import BLOCK_BYTES, BLOCK_VALS, BM

    nr = BM if nr is None else nr
    stride = 131
    rng = np.random.default_rng(0)
    lens = jnp.asarray(np.ones((nr, BLOCK_VALS), np.int32))
    data = jnp.asarray(rng.integers(0, 255, (nr, BLOCK_BYTES)).astype(np.uint8))
    base = jnp.asarray(np.zeros(nr, np.int32))
    pe = jnp.asarray(np.zeros(nr, np.int32))
    norms = jnp.asarray(np.zeros((nr, BLOCK_VALS), np.int32))
    idf = jnp.asarray(np.ones(nr, np.float32))
    table = jnp.asarray(np.linspace(0.5, 2.0, 256).astype(np.float32))
    k1p1 = jnp.float32(2.2)
    last = jnp.asarray(np.arange(nb, dtype=np.int32) * 7)
    offs = jnp.asarray(np.array([0, nb], np.int32))
    terms = jnp.asarray(np.zeros(nr, np.int32))
    probes = jnp.asarray(np.zeros(nr, np.int32))
    qb = jnp.asarray(np.zeros((nr, BLOCK_VALS), np.int32))
    qmins = jnp.asarray(np.zeros((nr, BLOCK_VALS), np.int32))
    nblk = jnp.asarray(np.full(nr, BLOCK_VALS, np.int32))
    ef_lo = jnp.asarray(np.zeros((nr, BLOCK_VALS), np.int32))
    ef_hi = jnp.asarray(np.zeros((nr, EF_HI_WORDS), np.int32))
    ef_lb = jnp.asarray(np.zeros(nr, np.int32))

    def locate(t, p):
        return locate_graph(last, offs, stride, nb.bit_length(), t, p)

    def decode_search(ln, d, b, p):
        return decode_search_graph(ln, d, b, p, backend, False)

    def ef_search(l, h, lb, b, p):
        return ef_search_graph(l, h, lb, b, p, backend, False)

    def score_probe(ln, d, fl, fd, nm, b, p, i, tb, k):
        return score_probe_graph(ln, d, fl, fd, nm, b, p, i, tb, k, backend, False)

    def pivot(q, qm, nbk):
        return pivot_graph(q, qm, nbk, backend, False)

    def score_rows(fl, fd, nm, i, tb, k):
        return score_rows_graph(fl, fd, nm, i, tb, k, backend, False)

    def pivot_score(q, qm, nbk, b, fl, fd, nm, i, tb, k):
        return pivot_score_graph(
            q, qm, nbk, b, fl, fd, nm, i, tb, k, 8, backend, False
        )

    return {
        "locate_graph": (locate, (terms, probes)),
        "decode_search_graph": (decode_search, (lens, data, base, pe)),
        "ef_search_graph": (ef_search, (ef_lo, ef_hi, ef_lb, base, pe)),
        "score_probe_graph": (
            score_probe,
            (lens, data, lens, data, norms, base, pe, idf, table, k1p1),
        ),
        "pivot_graph": (pivot, (qb, qmins, nblk)),
        "score_rows_graph": (score_rows, (lens, data, norms, idf, table, k1p1)),
        "pivot_score_graph": (
            pivot_score,
            (qb, qmins, nblk, base, lens, data, norms, idf, table, k1p1),
        ),
    }


def check_graphs(backend: str = "ref") -> list[Finding]:
    """Compile the registered graph halves and sanitize their HLO."""
    import jax

    from repro.core.engine_core import GRAPH_CONTRACTS

    specs = graph_specs(backend)
    findings: list[Finding] = []
    if set(specs) != set(GRAPH_CONTRACTS):
        findings.append(
            Finding(
                "hlo",
                "contract-coverage",
                "engine_core.GRAPH_CONTRACTS",
                f"registry names {sorted(GRAPH_CONTRACTS)} but the sanitizer "
                f"compiles {sorted(specs)}; keep the two in lockstep",
            )
        )
    for name in sorted(set(specs) & set(GRAPH_CONTRACTS)):
        fn, args = specs[name]
        contract = GRAPH_CONTRACTS[name]
        text = jax.jit(fn).lower(*args).compile().as_text()
        findings += check_hlo_text(
            text,
            contract["identity"],
            name,
            allow_dots=contract.get("allow_dot_contractions", ()),
        )
    return findings
