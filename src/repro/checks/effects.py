"""Per-node buffer effect inference (DESIGN.md §3.3).

Answers, for every node of a captured graph, *which input buffers it reads
and which it writes*.  "Buffer" means a graph **input node** (a param leaf,
a cache pool, a token array): inside a graph every op output is a fresh SSA
value, so the only state that can be hazarded across nodes — or across two
graphs sharing arrays, like the paged decode step and a prefill chunk over
one page pool — is the inputs.

Inference walks the jaxpr equations each node carries in its meta
(``_eqns`` / ``_imports`` / ``_exports``, attached by ``core.capture``),
propagating the set of buffer *roots* every intermediate value is a version
of:

* ``scatter*`` / ``dynamic_update_slice`` **write** their operand's roots
  (functional update = a new version of the same logical buffer; the output
  carries the roots forward);
* view/layout primitives (reshape, transpose, convert, ...) carry roots
  unchanged;
* ``scan`` / ``while`` / ``cond`` and call-like primitives recurse into
  their sub-jaxprs with positional argument mapping, iterating loop carries
  to a fixpoint — a scatter *inside* a ``lax.scan`` over layers must
  still be seen;
* every other primitive reads its operands and produces fresh values.

Hand-built graphs (no jaxpr meta) may annotate nodes explicitly with
``meta={"effects": {"reads": [...], "writes": [...], "carries": [...]}}``;
nodes with neither are treated conservatively as pure readers of everything
their deps carry.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Iterable, Mapping

from jax.extend import core as jex

from repro.core.graph import Graph

__all__ = ["NodeEffects", "GraphEffects", "infer_effects", "shared_buffers"]

_EMPTY: frozenset[str] = frozenset()

# primitives whose (single) output is the same logical buffer as invars[0]
_CARRY_PRIMS = {
    "reshape", "transpose", "squeeze", "expand_dims", "rev",
    "copy", "convert_element_type", "stop_gradient", "device_put",
    "sharding_constraint",
}
_LOOP_FIXPOINT_LIMIT = 8


def _is_write(prim: str) -> bool:
    return prim.startswith("scatter") or prim == "dynamic_update_slice"


@dataclass(frozen=True)
class NodeEffects:
    """Buffer footprint of one node.  ``source`` records inference precision:
    ``"jaxpr"`` (traced), ``"annotated"`` (meta), ``"input"`` (buffer root),
    or ``"opaque"`` (no information — conservative reader)."""

    node: str
    reads: frozenset[str]
    writes: frozenset[str]
    source: str = "jaxpr"


@dataclass
class GraphEffects:
    """Effect sets for every node of one graph, at one ``Graph.version``."""

    graph_name: str
    version: int
    buffers: tuple[str, ...]                 # graph input node names
    effects: dict[str, NodeEffects]
    # (node, export slot) -> buffer roots its output carries
    slot_roots: dict[str, tuple[frozenset[str], ...]]

    def writers(self, buf: str) -> list[str]:
        return [n for n, e in self.effects.items() if buf in e.writes]

    def readers(self, buf: str) -> list[str]:
        return [n for n, e in self.effects.items()
                if buf in e.reads and buf not in e.writes]

    def written(self) -> set[str]:
        out: set[str] = set()
        for e in self.effects.values():
            out |= e.writes
        return out

    def read_only(self, bufs: Iterable[str]) -> bool:
        """True when no node writes any of ``bufs`` — the static
        certification behind running this graph concurrently with another
        graph's writes to those buffers."""
        w = self.written()
        return not any(b in w for b in bufs)


def infer_effects(graph: Graph) -> GraphEffects:
    """Infer :class:`NodeEffects` for every node of ``graph``."""
    effects: dict[str, NodeEffects] = {}
    slot_roots: dict[str, tuple[frozenset[str], ...]] = {}
    buffers: list[str] = []

    for name in graph.topo_order():
        node = graph[name]
        if node.fn is None:
            buffers.append(name)
            effects[name] = NodeEffects(name, _EMPTY, _EMPTY, source="input")
            slot_roots[name] = (frozenset({name}),)
            continue
        meta = node.meta or {}

        def dep_roots(dep_idx: int, slot: int, n_slots: int,
                      _node=node) -> frozenset[str]:
            slots = slot_roots.get(_node.deps[dep_idx], ())
            if n_slots <= 1 or len(slots) <= 1:
                return slots[0] if slots else _EMPTY
            return slots[slot] if slot < len(slots) else _EMPTY

        if "_eqns" in meta and "_imports" in meta:
            reads, writes, outs = _jaxpr_effects(meta, dep_roots)
            effects[name] = NodeEffects(name, reads, writes)
            slot_roots[name] = outs
        elif "effects" in meta:
            ann = meta["effects"]
            effects[name] = NodeEffects(
                name,
                reads=frozenset(ann.get("reads", ())),
                writes=frozenset(ann.get("writes", ())),
                source="annotated",
            )
            slot_roots[name] = (frozenset(ann.get("carries", ())),)
        else:
            all_dep = _EMPTY
            for d in node.deps:
                for r in slot_roots.get(d, ()):
                    all_dep |= r
            effects[name] = NodeEffects(name, all_dep, _EMPTY, source="opaque")
            slot_roots[name] = (_EMPTY,)

    return GraphEffects(
        graph_name=graph.name,
        version=graph.version,
        buffers=tuple(buffers),
        effects=effects,
        slot_roots=slot_roots,
    )


# -- jaxpr walk --------------------------------------------------------------

def _jaxpr_effects(
    meta: Mapping[str, Any],
    dep_roots: Callable[[int, int, int], frozenset[str]],
) -> tuple[frozenset[str], frozenset[str], tuple[frozenset[str], ...]]:
    env: dict[Any, frozenset[str]] = {}
    for var, dep_idx, slot, n_slots in meta["_imports"]:
        env[var] = dep_roots(dep_idx, slot, n_slots)
    reads: set[str] = set()
    writes: set[str] = set()
    _walk_eqns(meta["_eqns"], env, reads, writes)
    outs = tuple(_roots_of(env, v) for v in meta["_exports"])
    return frozenset(reads), frozenset(writes), outs


def _roots_of(env: Mapping[Any, frozenset[str]], v: Any) -> frozenset[str]:
    if isinstance(v, jex.Var):
        return env.get(v, _EMPTY)
    return _EMPTY   # literals / dropped vars carry no buffer


def _walk_eqns(
    eqns: Iterable[Any],
    env: dict[Any, frozenset[str]],
    reads: set[str],
    writes: set[str],
) -> None:
    for eqn in eqns:
        prim = eqn.primitive.name
        in_roots = [_roots_of(env, v) for v in eqn.invars]
        for r in in_roots:
            reads.update(r)
        n_out = len(eqn.outvars)
        out_roots: list[frozenset[str]] = [_EMPTY] * n_out

        if _is_write(prim):
            # functional update: a new version of the operand's buffer
            writes.update(in_roots[0])
            out_roots[0] = in_roots[0]
        elif prim in _CARRY_PRIMS:
            out_roots[0] = in_roots[0]
        elif prim == "scan":
            out_roots = _walk_scan(eqn, in_roots, reads, writes)
        elif prim == "while":
            out_roots = _walk_while(eqn, in_roots, reads, writes)
        elif prim == "cond":
            out_roots = _walk_cond(eqn, in_roots, reads, writes)
        else:
            sub, _ = _sub_jaxpr(eqn)
            if sub is not None and len(sub.invars) == len(eqn.invars):
                out_roots = _walk_sub(sub, in_roots, reads, writes)
            # else: opaque primitive — fresh outputs, no carried roots

        for ov, r in zip(eqn.outvars, out_roots):
            if isinstance(ov, jex.Var):
                env[ov] = r


def _sub_jaxpr(eqn: Any) -> tuple[Any, Any]:
    """Open jaxpr of a call-like eqn (jit / remat / custom_*), or (None, None)."""
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if sub is None:
        return None, None
    if hasattr(sub, "jaxpr"):          # ClosedJaxpr
        return sub.jaxpr, list(sub.consts)
    return sub, []


def _walk_sub(
    sub: Any,
    in_roots: list[frozenset[str]],
    reads: set[str],
    writes: set[str],
) -> list[frozenset[str]]:
    """Walk a sub-jaxpr with positional invar/outvar mapping; returns the
    eqn-level output roots."""
    env = {v: r for v, r in zip(sub.invars, in_roots) if isinstance(v, jex.Var)}
    _walk_eqns(sub.eqns, env, reads, writes)
    return [_roots_of(env, v) for v in sub.outvars]


def _walk_scan(
    eqn: Any,
    in_roots: list[frozenset[str]],
    reads: set[str],
    writes: set[str],
) -> list[frozenset[str]]:
    sub = eqn.params["jaxpr"].jaxpr
    n_const = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    body_in = list(in_roots)    # consts + carry + xs, positionally = sub.invars
    outs: list[frozenset[str]] = []
    for _ in range(_LOOP_FIXPOINT_LIMIT):
        outs = _walk_sub(sub, body_in, reads, writes)
        changed = False
        for k in range(n_carry):
            merged = body_in[n_const + k] | outs[k]
            if merged != body_in[n_const + k]:
                body_in[n_const + k] = merged
                changed = True
        if not changed:
            break
    # eqn outvars = carry outs + ys, positionally = sub outvars
    return outs


def _walk_while(
    eqn: Any,
    in_roots: list[frozenset[str]],
    reads: set[str],
    writes: set[str],
) -> list[frozenset[str]]:
    cond = eqn.params["cond_jaxpr"].jaxpr
    body = eqn.params["body_jaxpr"].jaxpr
    n_cc = eqn.params["cond_nconsts"]
    n_bc = eqn.params["body_nconsts"]
    cond_consts = in_roots[:n_cc]
    body_in = list(in_roots[n_cc:])           # body consts + carry
    carry0 = n_bc
    outs: list[frozenset[str]] = []
    for _ in range(_LOOP_FIXPOINT_LIMIT):
        outs = _walk_sub(body, body_in, reads, writes)
        changed = False
        for k in range(len(outs)):            # body outvars = the carry
            merged = body_in[carry0 + k] | outs[k]
            if merged != body_in[carry0 + k]:
                body_in[carry0 + k] = merged
                changed = True
        if not changed:
            break
    _walk_sub(cond, cond_consts + body_in[carry0:], reads, writes)
    return outs


def _walk_cond(
    eqn: Any,
    in_roots: list[frozenset[str]],
    reads: set[str],
    writes: set[str],
) -> list[frozenset[str]]:
    branches = eqn.params["branches"]
    operand_roots = in_roots[1:]              # invars[0] is the predicate
    merged: list[frozenset[str]] | None = None
    for br in branches:
        outs = _walk_sub(br.jaxpr, operand_roots, reads, writes)
        if merged is None:
            merged = outs
        else:
            merged = [a | b for a, b in zip(merged, outs)]
    return merged or []


# -- cross-graph aliasing ----------------------------------------------------

def shared_buffers(
    bind_a: Mapping[str, Any],
    bind_b: Mapping[str, Any],
) -> list[tuple[str, str]]:
    """Input buffers two graphs share, found by array **object identity**
    over their bound name→value input mappings (``CapturedGraph.bind``).

    Two graphs alias state exactly when the caller passes the *same* array
    to both — e.g. the serving engine threads one page pool through the
    decode step and every prefill chunk.  Leaf names differ per graph
    (``in.1pagesk`` vs ``in.1k``), so identity, not naming, is the ground
    truth.  Returns ``(name_in_a, name_in_b)`` pairs.
    """
    by_id: dict[int, list[str]] = {}
    for name, val in bind_a.items():
        if val is not None and not isinstance(val, (int, float, bool)):
            by_id.setdefault(id(val), []).append(name)
    pairs: list[tuple[str, str]] = []
    for name_b, val in bind_b.items():
        if val is None or isinstance(val, (int, float, bool)):
            continue
        for name_a in by_id.get(id(val), ()):
            pairs.append((name_a, name_b))
    return pairs
