"""Jaxpr graph analysis: walking, data-flow reachability, taint.

Everything progcheck knows about a program it learns here, from the
pre-lowering jaxpr (collectives are still explicit named primitives at
this level; after SPMD partitioning they dissolve into HLO channels).
Three analyses, each recursive over sub-jaxprs (pjit bodies, shard_map
regions, cond branches, custom-vjp calls, remat):

  walk_eqns          — every equation with the set of mesh axes bound at
                       its position (shard_map pushes its mesh's axes).
  input_dependence   — for each program output, WHICH inputs it
                       transitively data-depends on. A gradient that is
                       structurally zero (the stop_gradient contract)
                       depends on NO input — that is the machine-checkable
                       form of "no differentiable path" (check P1).
  double_sum_reduces — sum-reduces (psum/pmean) whose operand derives,
                       through value-preserving ops only, from another
                       sum-reduce over the same axis: the double-reduced-
                       gradient hazard (check P3).

Positional primitives (`optimization_barrier`) map outputs to inputs
1:1 — treating them conservatively would make every chained-psum bucket
look double-reduced, since bucket i+1's input is barrier-tied to bucket
i's OUTPUT purely as a scheduling hint.
"""

from __future__ import annotations

import dataclasses

from jax import core as jax_core
from jax.extend.core import Literal

# collectives whose payload crosses the interconnect (named-axis prims at
# the jaxpr level; inside shard_map regions jax 0.9 spells psum
# `psum_invariant`)
SUM_REDUCE_PRIMS = frozenset({"psum", "psum_invariant"})
COLLECTIVE_PRIMS = SUM_REDUCE_PRIMS | frozenset({
    "pmax", "pmin", "all_gather", "ppermute", "all_to_all",
    "reduce_scatter",
})
# host-boundary primitives that must never appear in a step program
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "outside_call", "host_callback_call",
})
# outputs depend only on the same-position input. The collectives matter:
# a tree-wide pmean is ONE multi-operand psum equation, and treating it
# conservatively would fuse the dependence of every gradient leaf in the
# tree — a structurally-zero key-encoder grad would inherit the query
# grads' inputs through the shared reduce.
POSITIONAL_PRIMS = frozenset({
    "optimization_barrier", "psum", "psum_invariant", "pmax", "pmin",
    "all_gather", "ppermute", "pbroadcast", "pvary",
})
# ops through which a value stays "the same quantity" for taint purposes:
# elementwise arithmetic, dtype casts, and layout moves. A dot_general or
# reduction produces a NEW quantity and clears the taint — without this
# restriction, a forward-pass psum would taint every gradient computed
# from its outputs and the gradsync reduce would always look double.
VALUE_PRESERVING_PRIMS = frozenset({
    "add", "add_any", "sub", "mul", "div", "neg", "sign", "abs", "max",
    "min", "select_n", "clamp", "convert_element_type", "reshape",
    "transpose", "squeeze", "broadcast_in_dim", "slice", "dynamic_slice",
    "concatenate", "copy", "stop_gradient", "integer_pow", "pow",
    "optimization_barrier", "rev", "expand_dims", "pad",
    # shard_map's check_rep rewrite inserts identity replication
    # adjustments between collectives — values pass through unchanged
    "pbroadcast", "pvary",
})


def _sub_jaxprs(eqn):
    """Every jaxpr hiding in an equation's params, as plain Jaxprs."""
    out = []
    for sub in jax_core.jaxprs_in_params(eqn.params):
        out.append(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
    return out


def _shard_map_axes(eqn) -> frozenset[str]:
    mesh = eqn.params.get("mesh")
    names = getattr(mesh, "axis_names", None)
    return frozenset(str(a) for a in names) if names else frozenset()


def walk_eqns(closed_jaxpr):
    """Yield `(eqn, bound_axes)` for every equation, depth-first through
    sub-jaxprs; `bound_axes` is the frozenset of mesh axis names in scope
    (pushed by enclosing shard_map equations)."""
    def walk(jaxpr, bound):
        for eqn in jaxpr.eqns:
            yield eqn, bound
            inner = bound
            if eqn.primitive.name == "shard_map":
                inner = bound | _shard_map_axes(eqn)
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub, inner)

    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    yield from walk(jaxpr, frozenset())


def named_axes(eqn) -> tuple[str, ...]:
    """The named mesh axes a collective reduces/gathers over (positional
    axis ints are filtered out)."""
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes if isinstance(a, str))


@dataclasses.dataclass
class CollectiveOp:
    prim: str
    axes: tuple[str, ...]
    operand_dtypes: tuple[str, ...]
    operand_elems: int          # total elements across operands
    operand_bytes: int          # total bytes across operands (native dtype)

    def json_obj(self) -> dict:
        return dataclasses.asdict(self)


def collect_collectives(closed_jaxpr) -> list[CollectiveOp]:
    """Every collective equation in the program, with its native operand
    payload (what the wire would carry at the operand's own dtype)."""
    out = []
    for eqn, _bound in walk_eqns(closed_jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        avals = [v.aval for v in eqn.invars
                 if not isinstance(v, Literal)]
        elems = sum(int(_size(a)) for a in avals)
        nbytes = sum(int(_size(a)) * _itemsize(a) for a in avals)
        out.append(CollectiveOp(
            prim=eqn.primitive.name,
            axes=named_axes(eqn),
            operand_dtypes=tuple(sorted({str(a.dtype) for a in avals})),
            operand_elems=elems,
            operand_bytes=nbytes,
        ))
    return out


def _size(aval) -> int:
    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    return size


def _itemsize(aval) -> int:
    try:
        return int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        return 4  # extended dtypes (PRNG keys): irrelevant to wire math


# ---------------------------------------------------------------------------
# input dependence
# ---------------------------------------------------------------------------


def input_dependence(closed_jaxpr) -> list[set[int]]:
    """For each flat output of the program, the set of flat-input indices
    it transitively data-depends on. Literals and consts contribute
    nothing, so a materialized zero-gradient (symbolic zero from a
    stop_gradient cotangent) yields an empty set.

    Call-like equations (one sub-jaxpr, arity-matched) map positionally;
    `cond` unions its branches plus the predicate; anything else —
    including `scan`/`while`, which none of the audited invariants need
    to see through precisely — is treated conservatively (every output
    depends on every input), which can only over-report dependence,
    never hide it."""
    memo: dict[int, list[set[int]]] = {}

    def deps_of(jaxpr) -> list[set[int]]:
        key = id(jaxpr)
        if key in memo:
            return memo[key]
        env: dict = {}
        for i, v in enumerate(jaxpr.invars):
            env[v] = {i}
        for v in jaxpr.constvars:
            env[v] = set()

        def read(v) -> set[int]:
            if isinstance(v, Literal):
                return set()
            return env.get(v, set())

        for eqn in jaxpr.eqns:
            in_sets = [read(v) for v in eqn.invars]
            name = eqn.primitive.name
            subs = _sub_jaxprs(eqn)
            if name in POSITIONAL_PRIMS and len(eqn.outvars) == len(eqn.invars):
                outs = list(in_sets)
            elif name == "cond" and len(subs) >= 1:
                pred, ops = in_sets[0], in_sets[1:]
                outs = None
                for sub in subs:
                    mapped = _map_through(deps_of(sub), ops)
                    outs = mapped if outs is None else [
                        a | b for a, b in zip(outs, mapped)
                    ]
                outs = [o | pred for o in outs]
            elif (len(subs) == 1 and len(subs[0].invars) == len(eqn.invars)
                  and len(subs[0].outvars) == len(eqn.outvars)):
                outs = _map_through(deps_of(subs[0]), in_sets)
            else:
                union: set[int] = set()
                for s in in_sets:
                    union |= s
                outs = [set(union) for _ in eqn.outvars]
            for v, s in zip(eqn.outvars, outs):
                env[v] = s
        result = [read(v) for v in jaxpr.outvars]
        memo[key] = result
        return result

    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    return deps_of(jaxpr)


def _map_through(inner: list[set[int]], in_sets: list[set[int]]) -> list[set[int]]:
    out = []
    for dep in inner:
        s: set[int] = set()
        for i in dep:
            if i < len(in_sets):
                s |= in_sets[i]
        out.append(s)
    return out


# ---------------------------------------------------------------------------
# double sum-reduce taint
# ---------------------------------------------------------------------------


def double_sum_reduces(closed_jaxpr) -> list[tuple[str, str]]:
    """`(prim, axis)` for every sum-reduce whose operand is, through
    value-preserving ops only, derived from another sum-reduce over the
    same named axis — reducing an already-reduced quantity again (the
    double-reduced gradient: grads end up scaled by n²... or by n, twice).

    Taint = set of axis names the value has already been sum-reduced
    over. It survives elementwise arithmetic, casts, and layout moves
    (`pmean`'s trailing div, bucket slicing/concat) and dies at anything
    that builds a NEW quantity (dot_general, reductions, forwards), so a
    loss that legitimately contains a psum does not taint the gradients
    computed from it."""
    violations: list[tuple[str, str]] = []

    def run(jaxpr, in_taints: list[frozenset]) -> list[frozenset]:
        env: dict = {}
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = t
        for v in jaxpr.constvars:
            env[v] = frozenset()

        def read(v) -> frozenset:
            if isinstance(v, Literal):
                return frozenset()
            return env.get(v, frozenset())

        for eqn in jaxpr.eqns:
            in_ts = [read(v) for v in eqn.invars]
            name = eqn.primitive.name
            subs = _sub_jaxprs(eqn)
            if name in SUM_REDUCE_PRIMS:
                axes = frozenset(named_axes(eqn))
                # operands map to outputs 1:1 — taint per operand, so one
                # already-reduced leaf cannot smear its siblings
                if len(in_ts) == len(eqn.outvars):
                    per_operand = in_ts
                else:
                    union = frozenset().union(*in_ts) if in_ts else frozenset()
                    per_operand = [union for _ in eqn.outvars]
                outs = []
                for t in per_operand:
                    for ax in axes:
                        if ax in t:
                            violations.append((name, ax))
                    outs.append(t | axes)
            elif name in POSITIONAL_PRIMS and len(eqn.outvars) == len(eqn.invars):
                outs = list(in_ts)
            elif name == "cond" and subs:
                ops = in_ts[1:]
                outs = None
                for sub in subs:
                    mapped = run(sub, list(ops) + [frozenset()] * max(
                        0, len(sub.invars) - len(ops)))
                    outs = mapped if outs is None else [
                        a | b for a, b in zip(outs, mapped)
                    ]
            elif (len(subs) == 1 and len(subs[0].invars) == len(eqn.invars)
                  and len(subs[0].outvars) == len(eqn.outvars)):
                outs = run(subs[0], in_ts)
            elif name in VALUE_PRESERVING_PRIMS:
                union = frozenset().union(*in_ts) if in_ts else frozenset()
                outs = [union for _ in eqn.outvars]
            else:
                # a new quantity: taint does not survive
                for sub in subs:  # still scan inner programs for violations
                    run(sub, [frozenset()] * len(sub.invars))
                outs = [frozenset() for _ in eqn.outvars]
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
        return [read(v) for v in jaxpr.outvars]

    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    run(jaxpr, [frozenset() for _ in jaxpr.invars])
    return violations


# ---------------------------------------------------------------------------
# producer tracing (dtype-policy checks)
# ---------------------------------------------------------------------------


def build_producers(jaxpr) -> dict:
    """var -> producing eqn, for ONE jaxpr level (no recursion — callers
    walk levels via walk_eqns and inspect each level's local graph)."""
    producers: dict = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producers[v] = eqn
    return producers


def trace_back(var, producers, through=("reshape", "concatenate",
                                        "transpose", "squeeze", "copy")):
    """Follow `var` backwards through pure layout ops; returns the first
    producing eqn that is NOT a layout op (None for inputs/literals)."""
    seen = 0
    while seen < 1000:
        seen += 1
        eqn = producers.get(var)
        if eqn is None:
            return None
        if eqn.primitive.name in through:
            nonlit = [v for v in eqn.invars
                      if not isinstance(v, Literal)]
            if len(nonlit) != 1:
                return eqn  # concat of several: stop here, caller inspects
            var = nonlit[0]
            continue
        return eqn
    return None


def iter_jaxprs(closed_jaxpr):
    """Yield every (sub)jaxpr level, outermost first."""
    def walk(jaxpr):
        yield jaxpr
        for eqn in jaxpr.eqns:
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub)

    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    yield from walk(jaxpr)
