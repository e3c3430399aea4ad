"""jaxpr → :class:`Graph` capture (the front half of ``graphi.compile``).

``capture(fn, *specs)`` traces ``fn`` with :func:`jax.make_jaxpr`, inlines
``jit``/``remat``/``custom_*`` call boundaries, fuses trivial data-movement
and elementwise chains into their consumers, and emits one :class:`OpNode`
per surviving equation group.  Every node carries

* roofline statistics (``flops`` / ``bytes_in`` / ``bytes_out``) derived from
  the equation avals with the same accounting conventions as
  ``analysis/hlo_cost.py`` (dot = 2·|out|·K, elementwise = |out|, data
  movement = 0 flops, ``scan`` bodies × trip count), and
* a runnable ``fn`` (a tiny ``Primitive.bind`` interpreter over the group's
  equations), so the sequential oracle ``Graph.execute`` and the host
  runtime ``HostScheduler`` both execute captured graphs bit-exactly.

This is the Opara-style automatic whole-model capture (arXiv 2312.10351)
replacing the hand-built DAGs: any JAX function — a model forward, an
``lm_loss``, a full train step — becomes a schedulable Graphi graph.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.extend import core as jex

from .graph import Graph

__all__ = ["CapturedGraph", "capture"]


# -- primitive classification ------------------------------------------------

# call-like primitives whose sub-jaxpr is semantically "just run the body":
# inlined so the graph sees the real operator DAG, not opaque call nodes
_INLINE_PRIMS = {
    "jit", "closed_call", "remat2",
    "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
}
_MAX_INLINE_DEPTH = 32

# pure data movement / layout: zero flops, fused into consumers when possible
_MOVEMENT_PRIMS = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "rev", "convert_element_type", "bitcast_convert_type", "copy", "gather",
    "iota", "select_n", "stop_gradient", "sharding_constraint", "device_put",
    "split",
}
_GEMM_PRIMS = {"dot_general"}
_CONV_PRIMS = {"conv_general_dilated"}
_LOOP_PRIMS = {"scan", "while", "fori_loop"}
# a hand-written kernel: its own node, never folded into a neighbour
_KERNEL_PRIMS = {"pallas_call"}
_REDUCE_PREFIXES = ("reduce_", "cum", "arg")


def _kind_of(prim_name: str) -> str:
    if prim_name in _GEMM_PRIMS:
        return "gemm"
    if prim_name in _CONV_PRIMS:
        return "conv"
    if prim_name in _LOOP_PRIMS:
        return "scan"
    if prim_name in _KERNEL_PRIMS:
        return "kernel"
    if prim_name == "cond":
        return "control"
    if prim_name in _MOVEMENT_PRIMS:
        return "movement"
    if prim_name.startswith(_REDUCE_PREFIXES) or prim_name == "sort":
        return "reduce"
    return "elementwise"


_FUSABLE_KINDS = ("movement", "elementwise")


# -- aval helpers ------------------------------------------------------------

def _aval_bytes(aval: Any) -> float:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0.0
    return float(size) * np.dtype(dtype).itemsize


def _aval_size(aval: Any) -> float:
    return float(getattr(aval, "size", 0) or 0)


def _sub_jaxpr(eqn: Any):
    """(open jaxpr, consts) of a call-like eqn's body, or (None, None)."""
    sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    if sub is None:
        return None, None
    if hasattr(sub, "jaxpr"):          # ClosedJaxpr
        return sub.jaxpr, list(sub.consts)
    return sub, []                      # open Jaxpr (remat)


def _eqn_flops(eqn: Any) -> float:
    """Analytic flop count for one equation (hlo_cost.py conventions)."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        (lhs_c, _), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        k = 1.0
        for d in lhs_c:
            k *= lhs.shape[d]
        return 2.0 * _aval_size(eqn.outvars[0].aval) * k
    if prim == "conv_general_dilated":
        rhs = eqn.invars[1].aval
        dn = eqn.params["dimension_numbers"]
        cout = rhs.shape[dn.rhs_spec[0]] if rhs.shape else 1
        kernel = float(np.prod(rhs.shape)) if rhs.shape else 1.0
        return 2.0 * _aval_size(eqn.outvars[0].aval) * kernel / max(cout, 1)
    if prim in _LOOP_PRIMS or prim == "cond":
        body, _ = _sub_jaxpr(eqn)
        trips = float(eqn.params.get("length", 1)) if prim == "scan" else 1.0
        if body is None and prim == "cond":
            branches = eqn.params.get("branches", ())
            costs = [sum(_eqn_flops(e) for e in b.jaxpr.eqns) for b in branches]
            return max(costs, default=0.0)
        if body is None:
            return 0.0
        return trips * sum(_eqn_flops(e) for e in body.eqns)
    if prim == "pallas_call":
        # the kernel body (over block refs) runs once per grid step
        trips = float(np.prod(eqn.params["grid_mapping"].grid))
        return trips * sum(_eqn_flops(e) for e in eqn.params["jaxpr"].eqns)
    sub, _ = _sub_jaxpr(eqn)
    if sub is not None:
        return sum(_eqn_flops(e) for e in sub.eqns)
    if prim.startswith("scatter"):
        # scatter passes the whole operand through and touches only the
        # updates: price it by the update size, not the output buffer —
        # a paged-KV decode graph writes one token row into a pool whose
        # aval is thousands of times larger than the work done
        upd = eqn.invars[-1].aval if len(eqn.invars) >= 3 else eqn.outvars[0].aval
        return _aval_size(upd)
    kind = _kind_of(prim)
    if kind == "movement":
        return 0.0
    if kind == "reduce":
        return sum(_aval_size(v.aval) for v in eqn.invars[:1]
                   if isinstance(v, jex.Var))
    return sum(_aval_size(v.aval) for v in eqn.outvars)


def _gemm_rows(eqn: Any) -> int | None:
    """M (the paper's MKL panel dimension) of a dot_general, for the
    cost model's tall-skinny scaling cap."""
    if eqn.primitive.name != "dot_general":
        return None
    (lhs_c, _), (lhs_b, _) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    rows = 1
    for d, extent in enumerate(lhs.shape):
        if d not in lhs_c and d not in lhs_b:
            rows *= extent
    return rows


# -- flattening (inline call-like prims) -------------------------------------

def _flatten(eqns, sub_map: dict, constenv: dict, depth: int = 0) -> list:
    """Inline call-like eqns and alpha-rename every binder.

    JAX caches traced sub-jaxprs, so two call sites of the same layer share
    one jaxpr *object* — inlining both without renaming would make one Var
    the output of two eqns.  Every surviving eqn therefore gets fresh
    outvars; ``sub_map`` carries the old→new substitution for its scope.
    """
    out: list = []
    for eqn in eqns:
        invars = [sub_map.get(v, v) if isinstance(v, jex.Var) else v
                  for v in eqn.invars]
        if eqn.primitive.name in _INLINE_PRIMS and depth < _MAX_INLINE_DEPTH:
            sub, consts = _sub_jaxpr(eqn)
            if sub is not None and len(sub.invars) == len(eqn.invars):
                inner: dict = dict(zip(sub.invars, invars))
                for cv, c in zip(sub.constvars, consts):
                    constenv[cv] = c
                out.extend(_flatten(sub.eqns, inner, constenv, depth + 1))
                for outer_ov, sub_ov in zip(eqn.outvars, sub.outvars):
                    sub_map[outer_ov] = (
                        inner.get(sub_ov, sub_ov)
                        if isinstance(sub_ov, jex.Var) else sub_ov
                    )
                continue
        fresh = [jex.Var(ov.aval) for ov in eqn.outvars]
        for ov, fv in zip(eqn.outvars, fresh):
            sub_map[ov] = fv
        out.append(eqn.replace(invars=invars, outvars=fresh))
    return out


# -- captured graph ----------------------------------------------------------

@dataclass
class CapturedGraph:
    """A :class:`Graph` plus the pytree plumbing to call it like ``fn``.

    ``bind(args)`` maps a concrete argument tuple onto the graph's input
    nodes; ``unflatten(results)`` reassembles ``fn``'s output pytree from a
    per-node result mapping (as produced by ``Graph.execute`` or
    ``HostScheduler.run``); ``run(*args)`` is the sequential oracle.
    """

    graph: Graph
    name: str
    in_tree: Any
    n_in_leaves: int
    input_names: dict[int, str]          # used leaf index -> input node name
    out_tree: Any
    out_spec: list[tuple] = field(repr=False, default_factory=list)
    n_eqns: int = 0                      # flattened eqn count, pre-fusion

    def bind(self, args: Sequence[Any]) -> dict[str, Any]:
        leaves, in_tree = jax.tree_util.tree_flatten(tuple(args))
        if in_tree != self.in_tree or len(leaves) != self.n_in_leaves:
            raise TypeError(
                f"{self.name}: argument structure {in_tree} does not match "
                f"the captured structure {self.in_tree}"
            )
        return {self.input_names[i]: leaves[i] for i in self.input_names}

    def unflatten(self, results: Mapping[str, Any]) -> Any:
        leaves = []
        for spec in self.out_spec:
            if spec[0] == "node":
                _, node, slot, n_slots = spec
                val = results[node]
                leaves.append(val if n_slots == 1 else val[slot])
            elif spec[0] == "input":
                leaves.append(results[self.input_names[spec[1]]])
            else:  # const
                leaves.append(spec[1])
        return jax.tree_util.tree_unflatten(self.out_tree, leaves)

    def run(self, *args: Any) -> Any:
        """Execute via the sequential interpreter (the correctness oracle)."""
        return self.unflatten(self.graph.execute(self.bind(args)))


# -- node fn builder ---------------------------------------------------------

def _bind_eqn(eqn, invals):
    out = eqn.primitive.bind(*invals, **eqn.params)
    return out if eqn.primitive.multiple_results else (out,)


def _make_node_fn(members, imports, const_bindings, exports):
    """Build a node ``fn(*dep_vals) -> value | tuple`` over member eqns.

    ``imports``: per imported var ``(var, dep_index, slot, n_slots)``.
    """

    def run(*dep_vals: Any) -> Any:
        env: dict[Any, Any] = dict(const_bindings)
        for var, dep_idx, slot, n_slots in imports:
            val = dep_vals[dep_idx]
            env[var] = val if n_slots == 1 else val[slot]
        for eqn in members:
            invals = [v.val if isinstance(v, jex.Literal) else env[v]
                      for v in eqn.invars]
            for ov, o in zip(eqn.outvars, _bind_eqn(eqn, invals)):
                env[ov] = o
        vals = tuple(env[v] for v in exports)
        return vals[0] if len(vals) == 1 else vals

    return run


# -- main entry --------------------------------------------------------------

def _leaf_name(i: int, path: Any) -> str:
    raw = jax.tree_util.keystr(path)
    keep = "".join(c for c in raw if c.isalnum() or c in "._")
    keep = keep.strip("._")  # noqa: B005 — char-set strip is the intent
    return f"in.{keep[-48:]}" if keep else f"in.{i}"


def capture(fn, *specs: Any, name: str | None = None, fuse: bool = True) -> CapturedGraph:
    """Trace ``fn(*specs)`` and build the schedulable computation graph.

    ``specs`` may be concrete arrays or :class:`jax.ShapeDtypeStruct`
    pytrees (only shapes/dtypes are read at capture time).  ``fuse=False``
    keeps one node per equation (debugging aid).
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*specs)
    jaxpr = closed.jaxpr
    gname = name or getattr(fn, "__name__", None) or "captured"

    top_map: dict[Any, Any] = {}
    constenv: dict[Any, Any] = dict(zip(jaxpr.constvars, closed.consts))
    eqns = _flatten(jaxpr.eqns, top_map, constenv)

    in_leaves_p = jax.tree_util.tree_flatten_with_path(tuple(specs))[0]
    _, in_tree = jax.tree_util.tree_flatten(tuple(specs))
    invar_leaf = {v: i for i, v in enumerate(jaxpr.invars)}

    producer: dict[Any, int] = {}        # var -> producing eqn index
    for i, e in enumerate(eqns):
        for ov in e.outvars:
            producer[ov] = i

    out_vars = [top_map.get(v, v) if isinstance(v, jex.Var) else v
                for v in jaxpr.outvars]
    out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)
    del out_leaves

    # consumers of each produced var, by eqn index (graph outputs count too)
    consumers: dict[Any, list[int]] = {}
    for i, e in enumerate(eqns):
        for v in e.invars:
            if isinstance(v, jex.Var) and v in producer:
                consumers.setdefault(v, []).append(i)
    graph_out_vars = {v for v in out_vars if isinstance(v, jex.Var)}

    # fusion: walking consumers-first, a trivial eqn whose outputs all feed
    # exactly one surviving group folds into it.  Producers always precede
    # consumers in a jaxpr, so every group's anchor is its max-index eqn and
    # cross-group edges originate only at anchors — no cycle can form.
    group = list(range(len(eqns)))

    def find(i: int) -> int:
        while group[i] != i:
            group[i] = group[group[i]]
            i = group[i]
        return i

    if fuse:
        for i in range(len(eqns) - 1, -1, -1):
            if _kind_of(eqns[i].primitive.name) not in _FUSABLE_KINDS:
                continue
            if any(ov in graph_out_vars for ov in eqns[i].outvars):
                continue
            targets = {find(c) for ov in eqns[i].outvars
                       for c in consumers.get(ov, [])}
            if len(targets) == 1:
                group[i] = targets.pop()

    members: dict[int, list[int]] = {}
    for i in range(len(eqns)):
        members.setdefault(find(i), []).append(i)

    g = Graph(gname)

    # input source nodes (used leaves only)
    used_leaves: set[int] = set()
    for e in eqns:
        for v in e.invars:
            if isinstance(v, jex.Var) and v in invar_leaf:
                used_leaves.add(invar_leaf[v])
    for v in graph_out_vars:
        if v in invar_leaf:
            used_leaves.add(invar_leaf[v])
    input_names: dict[int, str] = {}
    taken: set[str] = set()
    for i in sorted(used_leaves):
        nm = _leaf_name(i, in_leaves_p[i][0])
        if nm in taken:
            nm = f"{nm}.{i}"
        taken.add(nm)
        input_names[i] = nm
        g.add_op(nm, kind="input", bytes_out=_aval_bytes(jaxpr.invars[i].aval))

    # where does a var live? -> (node name, slot, n_slots)
    var_home: dict[Any, tuple[str, int, int]] = {}
    for i, nm in input_names.items():
        var_home[jaxpr.invars[i]] = (nm, 0, 1)

    prim_counts: dict[str, int] = {}
    node_exports: dict[int, list[Any]] = {}

    for anchor in sorted(members):
        idxs = members[anchor]
        grp_eqns = [eqns[i] for i in idxs]
        own_vars = {ov for e in grp_eqns for ov in e.outvars}

        exports: list[Any] = []
        for e in grp_eqns:
            for ov in e.outvars:
                external = any(find(c) != anchor for c in consumers.get(ov, []))
                if (external or ov in graph_out_vars) and ov not in exports:
                    exports.append(ov)
        if not exports:                   # dead group head: export anchor outs
            exports = [ov for ov in eqns[anchor].outvars]
        node_exports[anchor] = exports

        imports: list[Any] = []
        const_bindings: dict[Any, Any] = {}
        for e in grp_eqns:
            for v in e.invars:
                if not isinstance(v, jex.Var) or v in own_vars:
                    continue
                if v in var_home:
                    if v not in imports:
                        imports.append(v)
                elif v in constenv:
                    const_bindings[v] = constenv[v]
                elif v not in imports:
                    imports.append(v)     # will fail loudly below if unplaced

        dep_names: list[str] = []
        import_spec: list[tuple] = []
        for v in imports:
            home = var_home.get(v)
            if home is None:
                raise ValueError(
                    f"capture({gname}): unplaced variable {v} in group "
                    f"{eqns[anchor].primitive.name}"
                )
            nm, slot, n_slots = home
            if nm not in dep_names:
                dep_names.append(nm)
            import_spec.append((v, dep_names.index(nm), slot, n_slots))

        anchor_eqn = eqns[anchor]
        prim = anchor_eqn.primitive.name
        ordinal = prim_counts.get(prim, 0)
        prim_counts[prim] = ordinal + 1
        node_name = f"{prim}.{ordinal}"

        flops = sum(_eqn_flops(e) for e in grp_eqns)
        bytes_in = sum(_aval_bytes(v.aval) for v in imports)
        bytes_in += sum(float(getattr(c, "nbytes", 0) or 0)
                        for c in const_bindings.values())
        bytes_out = sum(_aval_bytes(v.aval) for v in exports)

        meta: dict[str, Any] = {"n_eqns": len(grp_eqns),
                                "prims": tuple(e.primitive.name for e in grp_eqns),
                                # effect-inference hooks (repro.checks.effects):
                                # the group's jaxpr eqns, its import spec
                                # (var, dep_index, slot, n_slots) and export
                                # vars in slot order — lets the checker trace
                                # which *input buffers* a node reads, writes
                                # (scatter / dynamic_update_slice, incl.
                                # inside scan/while bodies), or passes through
                                "_eqns": tuple(grp_eqns),
                                "_imports": tuple(import_spec),
                                "_exports": tuple(exports)}
        rows = _gemm_rows(anchor_eqn)
        if rows is not None:
            meta["rows"] = rows

        kind = _kind_of(prim)
        g.add_op(
            node_name,
            kind="elementwise" if kind == "movement" else kind,
            flops=flops,
            bytes_in=bytes_in,
            bytes_out=bytes_out,
            deps=tuple(dep_names),
            meta=meta,
            fn=_make_node_fn(grp_eqns, import_spec, const_bindings, exports),
        )
        for slot, v in enumerate(exports):
            var_home[v] = (node_name, slot, len(exports))

    out_spec: list[tuple] = []
    for v in out_vars:
        if isinstance(v, jex.Literal):
            out_spec.append(("const", v.val))
        elif isinstance(v, jex.Var) and v in var_home:
            nm, slot, n_slots = var_home[v]
            if v in invar_leaf:
                out_spec.append(("input", invar_leaf[v]))
            else:
                out_spec.append(("node", nm, slot, n_slots))
        elif v in constenv:
            out_spec.append(("const", constenv[v]))
        else:
            raise ValueError(f"capture({gname}): unplaced output {v}")

    g.validate()
    return CapturedGraph(
        graph=g,
        name=gname,
        in_tree=in_tree,
        n_in_leaves=len(in_leaves_p),
        input_names=input_names,
        out_tree=out_tree,
        out_spec=out_spec,
        n_eqns=len(eqns),
    )
