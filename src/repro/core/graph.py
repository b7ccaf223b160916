"""Computational graph built from a jaxpr — SPA's ONNX-graph analogue.

The paper builds a tripartite graph (operator / data / parameter nodes) from
an ONNX trace.  Here the standardized trace is JAX's own jaxpr: every JAX
frontend lowers to the same primitive vocabulary, which is what makes the
engine framework-agnostic *within* the JAX ecosystem (DESIGN.md §2).

Call-like primitives (``jit``/pjit, ``custom_jvp_call``, ``custom_vjp_call``,
``remat``) are inlined so the graph is flat; ``scan``/``while`` are rejected —
SPA analysis traces models in unrolled mode (models expose ``unroll=True``).

The graph also doubles as an interpreter (``evaluate``) so OBSPA can capture
intermediate activations (layer inputs for Hessian accumulation) without any
framework hooks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import tree_util as jtu


@dataclasses.dataclass
class DataNode:
    uid: int
    shape: tuple[int, ...]
    dtype: Any
    param_path: str | None = None       # set for parameter leaves
    producer: "OpNode | None" = None
    consumers: list["OpNode"] = dataclasses.field(default_factory=list)
    is_const: bool = False

    @property
    def is_param(self) -> bool:
        return self.param_path is not None

    def __repr__(self):
        tag = self.param_path or ("const" if self.is_const else "data")
        return f"DataNode({self.uid}, {tag}, {self.shape})"


@dataclasses.dataclass
class OpNode:
    uid: int
    prim: str
    params: dict
    invars: list["DataNode | None"]      # None for literal scalars
    outvars: list[DataNode]
    literals: list[Any]                  # literal values aligned with invars

    def __repr__(self):
        return f"OpNode({self.uid}, {self.prim})"


class GraphError(Exception):
    pass


INLINE_PRIMS = {"jit", "pjit", "custom_jvp_call", "custom_vjp_call",
                "remat", "checkpoint", "closed_call", "core_call",
                "custom_vjp_call_jaxpr"}

REJECT_PRIMS = {"scan", "while", "cond"}


class CompGraph:
    """Flat computational graph over a traced model function."""

    def __init__(self):
        self.ops: list[OpNode] = []
        self.data: dict[int, DataNode] = {}
        self.params: dict[str, DataNode] = {}   # param_path -> node
        self.inputs: list[DataNode] = []        # non-param invars
        self.outputs: list[DataNode] = []
        self._uid = 0

    # ----- construction helpers -----
    def _new_data(self, aval, **kw) -> DataNode:
        n = DataNode(self._uid, tuple(aval.shape), aval.dtype, **kw)
        self._uid += 1
        self.data[n.uid] = n
        return n

    def _new_op(self, prim, params, invars, outvars, literals) -> OpNode:
        op = OpNode(self._uid, prim, params, invars, outvars, literals)
        self._uid += 1
        self.ops.append(op)
        for v in invars:
            if v is not None:
                v.consumers.append(op)
        for v in outvars:
            v.producer = op
        return op

    # ----- evaluation (used by OBSPA activation capture) -----
    def evaluate(self, param_values: dict[str, jax.Array],
                 input_values: Sequence[jax.Array],
                 capture: set[int] | None = None,
                 ) -> tuple[list[jax.Array], dict[int, jax.Array]]:
        """Execute the graph; optionally capture given data-node uids."""
        env: dict[int, Any] = {}
        for path, node in self.params.items():
            env[node.uid] = param_values[path]
        for node, val in zip(self.inputs, input_values):
            env[node.uid] = val
        for node in self.data.values():
            if node.is_const:
                env[node.uid] = node._const_val           # type: ignore
        captured: dict[int, jax.Array] = {}
        capture = capture or set()
        for op in self.ops:
            invals = []
            for v, lit in zip(op.invars, op.literals):
                invals.append(env[v.uid] if v is not None else lit)
            prim = op.params["_prim_obj"]
            outs = prim.bind(*invals, **{k: v for k, v in op.params.items()
                                         if k != "_prim_obj"})
            if not prim.multiple_results:
                outs = [outs]
            for ov, o in zip(op.outvars, outs):
                env[ov.uid] = o
                if ov.uid in capture:
                    captured[ov.uid] = o
        return [env[o.uid] for o in self.outputs], captured


def keystr(path) -> str:
    """Dotted pytree path ("layers.attn.wq") — the key format every
    pruning, importance and checkpoint table is indexed by."""
    return jtu.keystr(path, simple=True, separator=".")


def trace_graph(fn: Callable, params, *args) -> CompGraph:
    """Trace ``fn(params, *args)`` and build the computational graph.

    ``params`` is the pytree whose leaves become parameter nodes (keyed by
    pytree path); ``args`` become plain input nodes.
    """
    closed = jax.make_jaxpr(fn)(params, *args)
    g = CompGraph()

    flat_params, _ = jtu.tree_flatten_with_path(params)
    param_paths = [keystr(p) for p, _ in flat_params]
    n_params = len(flat_params)

    var_map: dict[Any, DataNode] = {}

    jaxpr = closed.jaxpr
    # invars: params first (tree-flattened), then args flattened
    for i, var in enumerate(jaxpr.invars):
        if i < n_params:
            node = g._new_data(var.aval, param_path=param_paths[i])
            g.params[param_paths[i]] = node
        else:
            node = g._new_data(var.aval)
            g.inputs.append(node)
        var_map[var] = node
    for var, val in zip(jaxpr.constvars, closed.consts):
        node = g._new_data(var.aval, is_const=True)
        node._const_val = val                      # type: ignore
        var_map[var] = node

    _build_eqns(g, jaxpr.eqns, var_map)

    for var in jaxpr.outvars:
        if hasattr(var, "val"):                    # literal output
            continue
        g.outputs.append(var_map[var])
    return g


def _build_eqns(g: CompGraph, eqns, var_map: dict):
    from jax._src.core import Literal

    for eqn in eqns:
        name = eqn.primitive.name
        if name in REJECT_PRIMS:
            raise GraphError(
                f"primitive {name!r} in analysis trace — SPA analysis requires "
                f"unrolled model tracing (pass unroll=True)")
        if name in INLINE_PRIMS:
            _inline(g, eqn, var_map)
            continue
        invars: list[DataNode | None] = []
        literals: list[Any] = []
        for v in eqn.invars:
            if isinstance(v, Literal):
                invars.append(None)
                literals.append(v.val)
            else:
                invars.append(var_map[v])
                literals.append(None)
        outvars = [g._new_data(v.aval) for v in eqn.outvars]
        params = dict(eqn.params)
        params["_prim_obj"] = eqn.primitive
        g._new_op(name, params, invars, outvars, literals)
        for v, node in zip(eqn.outvars, outvars):
            var_map[v] = node


def _inline(g: CompGraph, eqn, var_map: dict):
    """Inline a call-like primitive's inner jaxpr."""
    from jax._src.core import Literal

    params = eqn.params
    inner = None
    for key in ("jaxpr", "call_jaxpr"):
        if key in params:
            inner = params[key]
            break
    if inner is None:
        raise GraphError(f"cannot inline {eqn.primitive.name}: {list(params)}")
    consts = ()
    if hasattr(inner, "jaxpr"):                    # ClosedJaxpr
        consts = inner.consts
        inner = inner.jaxpr

    sub_map: dict[Any, DataNode] = {}
    # custom_vjp_call prepends fn-consts to invars; align from the END.
    n = len(inner.invars)
    outer_invars = list(eqn.invars)[-n:]
    for ivar, outer in zip(inner.invars, outer_invars):
        if isinstance(outer, Literal):
            node = g._new_data(outer.aval, is_const=True)
            node._const_val = outer.val            # type: ignore
        else:
            node = var_map[outer]
        sub_map[ivar] = node
    for cvar, cval in zip(inner.constvars, consts):
        node = g._new_data(cvar.aval, is_const=True)
        node._const_val = cval                     # type: ignore
        sub_map[cvar] = node

    _build_eqns(g, inner.eqns, sub_map)

    for outer_out, inner_out in zip(eqn.outvars, inner.outvars):
        if isinstance(inner_out, Literal):
            node = g._new_data(inner_out.aval, is_const=True)
            node._const_val = inner_out.val        # type: ignore
            var_map[outer_out] = node
        else:
            var_map[outer_out] = sub_map[inner_out]


# ---------------------------------------------------------------------------
# Small utilities used across the engine
# ---------------------------------------------------------------------------

def positions_array(pos: frozenset[int]) -> np.ndarray:
    return np.fromiter(sorted(pos), dtype=np.int64)


def graph_stats(g: CompGraph) -> dict:
    from collections import Counter
    return {
        "n_ops": len(g.ops),
        "n_data": len(g.data),
        "n_params": len(g.params),
        "prims": dict(Counter(op.prim for op in g.ops)),
    }
