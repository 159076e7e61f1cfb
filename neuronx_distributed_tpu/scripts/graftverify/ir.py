"""Lowered-IR extraction for graftverify.

Everything here consumes a ``jax.stages.Lowered`` — the product of
``fn.lower(*abstract_args)``, a TRACE (milliseconds) and never an XLA
compile — and reads facts straight off the StableHLO module:

* :func:`donation_table` — declared donations (``Lowered.args_info``)
  versus materialized ``input_output_alias``es (the ``tf.aliasing_output``
  argument attribute jax emits for every donation XLA accepted).
* :func:`transfer_census` — infeed/outfeed/send/recv and host-callback
  custom_calls, counted call-graph-aware.
* :func:`collective_table` — all_reduce/all_gather/reduce_scatter/
  collective_permute/all_to_all ops with element counts, payload bytes and
  a per-rank ring-model wire-byte figure.

The op walk is CALL-GRAPH AWARE: shard_map bodies lower to private
``func.func``s reached through ``func.call``, so an op inside a body called
N times counts N times. Multiplicities propagate from ``main`` — ops in a
never-called function count zero.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "collective_table",
    "donation_table",
    "mlir_functions",
    "stable_table_basis",
    "transfer_census",
    "wire_ratio",
]

# StableHLO ops that move bytes between host and device (GV02).
_TRANSFER_OPS = (
    "stablehlo.infeed",
    "stablehlo.outfeed",
    "stablehlo.send",
    "stablehlo.recv",
)
# custom_call targets that are partition/layout MARKERS, not transfers
_SHARDING_TARGETS = {
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
    "MoveToDevice",
}
# host-callback custom_call target fragments (jax's python callbacks and
# host transfers lower to custom_calls named like these on every backend)
_CALLBACK_TARGET_RE = re.compile(
    r"callback|python|host_transfer|py_func", re.IGNORECASE
)

_COLLECTIVE_OPS = (
    "stablehlo.all_reduce",
    "stablehlo.all_gather",
    "stablehlo.reduce_scatter",
    "stablehlo.collective_permute",
    "stablehlo.all_to_all",
)

# element-type byte widths by MLIR spelling; every f8 flavour is 1 byte
_ELT_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
}

_REPLICA_GROUPS_RE = re.compile(r"tensor<(\d+)x(\d+)xi64>")


def _module_of(lowered):
    """The StableHLO MLIR module of a Lowered (no compile)."""
    mod = lowered.compiler_ir()
    return mod


def _iter_ops(op):
    """Every operation nested under ``op`` (regions/blocks, depth-first),
    excluding ``op`` itself."""
    for region in op.regions:
        for block in region.blocks:
            for child in block.operations:
                yield child
                yield from _iter_ops(child)


def _sym_name(func_op) -> str:
    return str(func_op.attributes["sym_name"]).strip('"')


def mlir_functions(lowered) -> Dict[str, object]:
    """name → ``func.func`` op for every function in the lowered module."""
    out: Dict[str, object] = {}
    for op in _module_of(lowered).body.operations:
        if op.operation.name == "func.func":
            out[_sym_name(op)] = op
    return out


def _call_multiplicities(funcs: Dict[str, object]) -> Dict[str, int]:
    """How many times each function executes per dispatch of ``main``:
    multiplicity propagated through the ``func.call`` graph (HLO call
    graphs are acyclic). Functions unreachable from main get 0."""
    calls: Dict[str, Dict[str, int]] = {}
    for name, fop in funcs.items():
        counts: Dict[str, int] = {}
        for op in _iter_ops(fop.operation):
            if op.operation.name == "func.call":
                callee = str(op.attributes["callee"]).lstrip("@").strip('"')
                counts[callee] = counts.get(callee, 0) + 1
        calls[name] = counts
    mult = {name: 0 for name in funcs}
    if "main" in mult:
        mult["main"] = 1
        # one pass in caller-before-callee order settles the acyclic graph
        for caller in _topo_order(calls):
            m = mult.get(caller, 0)
            if not m:
                continue
            for callee, k in calls[caller].items():
                if callee in mult:
                    mult[callee] += m * k
    return mult


def _topo_order(calls: Dict[str, Dict[str, int]]) -> List[str]:
    """Callers before callees (DFS postorder reversed); call graphs from a
    single lowering are acyclic."""
    seen: Dict[str, bool] = {}
    order: List[str] = []

    def visit(name: str) -> None:
        if seen.get(name):
            return
        seen[name] = True
        for callee in calls.get(name, ()):
            visit(callee)
        order.append(name)

    for name in calls:
        visit(name)
    return list(reversed(order))


def _effective_ops(lowered):
    """Yield ``(op, multiplicity)`` for every op that executes when main
    runs once."""
    funcs = mlir_functions(lowered)
    mult = _call_multiplicities(funcs)
    for name, fop in funcs.items():
        m = mult.get(name, 0)
        if not m:
            continue
        for op in _iter_ops(fop.operation):
            yield op, m


def _tensor_facts(mlir_type) -> Tuple[int, int, str]:
    """(element_count, element_bytes, spelled_type) for a tensor type; a
    non-ranked-tensor (token, tuple) reads as 0 elements."""
    s = str(mlir_type)
    m = re.match(r"tensor<(.*)>", s)
    if m is None:
        return 0, 0, s
    body = m.group(1)
    dims: List[int] = []
    elt = body
    if "x" in body:
        parts = body.split("x")
        elt = parts[-1]
        for p in parts[:-1]:
            if p.isdigit():
                dims.append(int(p))
            else:
                return 0, 0, s  # dynamic dim: no static byte count
    n = 1
    for d in dims:
        n *= d
    elt_bytes = _ELT_BYTES.get(elt, 1 if elt.startswith("f8") else 0)
    return n, elt_bytes, s


# --- GV01: donation aliasing --------------------------------------------------


def donation_table(lowered) -> dict:
    """Declared vs materialized donations of one lowered program.

    ``declared`` — flat arg positions whose ``args_info`` leaf carries
    ``donated=True`` (the ``donate_argnums`` declaration, flattened).
    ``pruned`` — declared positions pjit removed from the computation
    entirely (``keep_unused=False``): the buffer is freed, never copied —
    a tree-level donation covering metadata leaves the program does not
    read; NOT the GV01 bug.
    ``aliased`` — kept positions carrying a ``tf.aliasing_output``
    attribute in the StableHLO (the aliases jax computed at lowering).
    ``deferred`` — kept positions carrying ``jax.buffer_donor = true``:
    under a mesh jax cannot pair donors with outputs until the compiler
    fixes shardings, and mesh-free it finds no output of the donor's aval
    but an unpaired one of its SIZE; either way it forwards the donation
    to XLA verbatim — the declaration provably REACHED the IR; the
    pairing itself is compile-time (the one check lowering alone cannot
    close).
    ``dropped`` — declared, KEPT, and neither aliased nor deferred: the
    donated buffer is read but its bytes are silently copied every
    dispatch (no output of its aval or its size is left to take it) — the
    HBM-doubling bug GV01 catches.

    MLIR argument j is flat position ``sorted(kept_var_idx)[j]`` —
    positional identification without the mapping miscounts every program
    with a pruned arg (verified against jax's own dropped-donation
    warning on this container)."""
    import jax

    declared: List[int] = []
    avals: Dict[int, str] = {}
    try:
        leaves = jax.tree_util.tree_leaves(lowered.args_info)
    except Exception:
        leaves = []
    for i, leaf in enumerate(leaves):
        if getattr(leaf, "donated", False):
            declared.append(i)
        aval = getattr(leaf, "aval", None) or getattr(leaf, "_aval", None)
        if aval is not None:
            avals[i] = str(aval)
    kept: List[int] = list(range(len(leaves)))
    try:
        kept_idx = lowered._lowering.compile_args.get("kept_var_idx")
        if kept_idx is not None:
            kept = sorted(int(i) for i in kept_idx)
    except Exception:
        pass  # no pruning info: assume everything kept (over-report side)
    aliased: List[int] = []
    deferred: List[int] = []
    main = mlir_functions(lowered).get("main")
    if main is not None:
        try:
            arg_attrs = main.attributes["arg_attrs"]
        except KeyError:
            arg_attrs = ()
        for j, attrs in enumerate(arg_attrs):
            if j >= len(kept):
                break
            s = str(attrs)
            if "tf.aliasing_output" in s:
                aliased.append(kept[j])
            elif "jax.buffer_donor" in s:
                deferred.append(kept[j])
    pruned = sorted(set(declared) - set(kept))
    dropped = sorted(
        (set(declared) & set(kept)) - set(aliased) - set(deferred)
    )
    return {
        "declared": declared,
        "aliased": aliased,
        "deferred": deferred,
        "pruned": pruned,
        "dropped": dropped,
        "dropped_avals": {i: avals.get(i, "?") for i in dropped},
    }


# --- GV02: transfer census ----------------------------------------------------


def transfer_census(lowered) -> List[dict]:
    """Host-transfer ops that execute per dispatch: ``[{"op", "target",
    "count"}, ...]`` aggregated over the call graph. Empty == the program
    is transfer-free, the hot-path contract."""
    counts: Dict[Tuple[str, str], int] = {}
    for op, m in _effective_ops(lowered):
        name = op.operation.name
        target = ""
        if name == "stablehlo.custom_call":
            target = str(op.attributes["call_target_name"]).strip('"')
            if target in _SHARDING_TARGETS:
                continue
            if not _CALLBACK_TARGET_RE.search(target):
                continue
        elif name not in _TRANSFER_OPS:
            continue
        key = (name, target)
        counts[key] = counts.get(key, 0) + m
    return [
        {"op": op_name, "target": target, "count": n}
        for (op_name, target), n in sorted(counts.items())
    ]


# --- GV03: collective wire-byte table -----------------------------------------


def _group_size(op) -> Optional[int]:
    """Participant count of a collective from its ``replica_groups``
    (tensor<GxRxi64> → R). collective_permute carries pairs, not groups —
    its wire model does not need R."""
    try:
        attr = str(op.attributes["replica_groups"])
    except KeyError:
        return None
    m = _REPLICA_GROUPS_RE.search(attr)
    if m is None:
        return None
    r = int(m.group(2))
    return r if r > 0 else None


def _wire_bytes(kind: str, in_elems: int, out_elems: int, elt_bytes: int,
                ranks: Optional[int]) -> int:
    """Per-rank bytes moved by one collective, ring-algorithm model (the
    EQuARX accounting in parallel/quantized_collectives.comm_bytes uses the
    same equivalences). Unknown rank counts degrade to the payload bytes —
    a documented overestimate for all_reduce, never an undercount of the
    ratchet."""
    payload = in_elems * elt_bytes
    if kind == "stablehlo.collective_permute":
        return payload  # each rank forwards its block once
    if ranks is None or ranks < 2:
        return payload
    if kind == "stablehlo.all_reduce":
        return (2 * (ranks - 1) * payload) // ranks
    if kind == "stablehlo.all_gather":
        return (ranks - 1) * payload  # operand is the per-shard block
    if kind == "stablehlo.reduce_scatter":
        return ((ranks - 1) * payload) // ranks
    if kind == "stablehlo.all_to_all":
        return ((ranks - 1) * payload) // ranks
    return payload


def collective_table(lowered) -> dict:
    """Per-kind collective census of one lowered program:

    ``{"by_kind": {kind: {"ops", "elements", "payload_bytes",
    "wire_bytes"}}, "detail": [...], "ops": N, "wire_bytes": total}`` —
    ops/elements/bytes are per DISPATCH (call-graph multiplicities
    applied); ``wire_bytes`` is the per-rank ring-model figure
    :func:`_wire_bytes` documents. ``detail`` lists each distinct op site
    (kind, elements, element bytes, ranks, count, wire bytes per op) so a
    consumer can pick out e.g. the routed row-parallel reductions by
    element count."""
    by_kind: Dict[str, Dict[str, int]] = {}
    detail: Dict[Tuple[str, int, int, Optional[int]], int] = {}
    for op, m in _effective_ops(lowered):
        kind = op.operation.name
        if kind not in _COLLECTIVE_OPS:
            continue
        in_elems, elt_bytes, _ = _tensor_facts(op.operands[0].type)
        out_elems, _, _ = _tensor_facts(op.results[0].type)
        ranks = _group_size(op)
        short = kind.replace("stablehlo.", "")
        row = by_kind.setdefault(
            short,
            {"ops": 0, "elements": 0, "payload_bytes": 0, "wire_bytes": 0},
        )
        row["ops"] += m
        row["elements"] += m * in_elems
        row["payload_bytes"] += m * in_elems * elt_bytes
        wb = _wire_bytes(kind, in_elems, out_elems, elt_bytes, ranks)
        row["wire_bytes"] += m * wb
        key = (short, in_elems, elt_bytes, ranks, wb)
        detail[key] = detail.get(key, 0) + m
    total = sum(r["wire_bytes"] for r in by_kind.values())
    ops = sum(r["ops"] for r in by_kind.values())
    return {
        "by_kind": dict(sorted(by_kind.items())),
        "detail": [
            {"kind": k, "elements": e, "elt_bytes": b, "ranks": r,
             "wire_bytes": wb, "ops": n}
            for (k, e, b, r, wb), n in sorted(
                detail.items(),
                key=lambda it: (it[0][0], it[0][1], it[0][2], it[0][4]),
            )
        ],
        "ops": ops,
        "wire_bytes": total,
    }


def wire_ratio(baseline_table: dict, candidate_table: dict) -> float:
    """``baseline_wire_bytes / candidate_wire_bytes`` — the static form of
    the EQuARX claim (exact-psum table over quantized-ring table ≥ 3.9 at
    block_size=256). 0.0 when the candidate moves nothing."""
    cand = candidate_table.get("wire_bytes", 0)
    if not cand:
        return 0.0
    return baseline_table.get("wire_bytes", 0) / cand


def stable_table_basis(table: dict) -> str:
    """Deterministic one-line rendering of a collective table — the GV03
    fingerprint basis, so any byte movement changes the fingerprint."""
    parts = []
    for kind, row in table["by_kind"].items():
        parts.append(
            f"{kind}[ops={row['ops']},elems={row['elements']},"
            f"wire={row['wire_bytes']}B]"
        )
    return " ".join(parts) if parts else "no-collectives"
