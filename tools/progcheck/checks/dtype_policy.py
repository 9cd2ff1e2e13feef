"""P4/P5: the per-leaf reduce dtype policy, checked in the program.

gradsync's contract (parallel/gradsync.leaf_wire_dtype): integer leaves
are SUMMED exactly — never averaged, never cast — and bf16 float leaves
reduce in their OWN dtype under the float32 policy instead of being
silently widened (which doubles their wire bytes and hides the fact the
leaf was ever bf16). Source-level lint can't see either: both hazards
are one `.astype`/`/ n` away and live in traced code.

P4 — an integer sum-reduce result must not feed a division: psum(int)/n
is an average of a counter, which silently corrupts exact-sum semantics
(ratios land in some float, remainders vanish in int).

P5 — a sum-reduce operand must not be the direct product (through
layout ops) of a bf16→wider-float cast: that is the old `_pmean_grads`
widening regression, re-materialized.
"""

from __future__ import annotations


from jax.extend import core as jax_core

from tools.progcheck.jaxpr_utils import (
    SUM_REDUCE_PRIMS,
    build_producers,
    iter_jaxprs,
    trace_back,
)
from tools.progcheck.registry import Check, register

_LAYOUT = ("reshape", "concatenate", "transpose", "squeeze", "copy",
           "convert_element_type", "broadcast_in_dim", "slice")


def _is_int(aval) -> bool:
    kind = getattr(getattr(aval, "dtype", None), "kind", "")
    return kind in ("i", "u", "b")


@register
class IntLeavesNeverAveraged(Check):
    id = "P4"
    title = "integer reduce results are never averaged"
    rationale = ("an int leaf in a grads-shaped tree is a counter; "
                 "psum(int)/n silently corrupts its exact-sum semantics")

    def check_program(self, record):
        reported = False
        for jaxpr in iter_jaxprs(record.jaxpr):
            # vars that are (layout-transparently) integer sum-reduce
            # results
            int_reduced = set()
            for eqn in jaxpr.eqns:
                name = eqn.primitive.name
                if name in SUM_REDUCE_PRIMS:
                    for vin, vout in zip(eqn.invars, eqn.outvars):
                        if _is_int(vin.aval):
                            int_reduced.add(vout)
                elif name in _LAYOUT:
                    if any(v in int_reduced for v in eqn.invars
                           if not isinstance(v, jax_core.Literal)):
                        int_reduced.update(eqn.outvars)
                elif name == "div" and not reported:
                    num = eqn.invars[0]
                    if not isinstance(num, jax_core.Literal) and num in int_reduced:
                        reported = True
                        yield self.finding(
                            record,
                            "an integer sum-reduce result feeds a division "
                            "— integer leaves must be summed exactly, "
                            "never averaged (gradsync dtype policy)",
                        )


@register
class NoSilentBf16Widen(Check):
    id = "P5"
    title = "bf16 leaves are not widened before the reduce"
    rationale = ("casting a bf16 leaf to f32 on the wire doubles its "
                 "reduce bytes and silently reverts the per-leaf dtype "
                 "policy — the old _pmean_grads regression")

    def check_program(self, record):
        reported = set()
        for jaxpr in iter_jaxprs(record.jaxpr):
            producers = build_producers(jaxpr)
            for eqn in jaxpr.eqns:
                if eqn.primitive.name not in SUM_REDUCE_PRIMS:
                    continue
                for v in eqn.invars:
                    if isinstance(v, jax_core.Literal):
                        continue
                    src = trace_back(v, producers,
                                     through=("reshape", "concatenate",
                                              "transpose", "squeeze",
                                              "copy"))
                    if src is None or src.primitive.name != "convert_element_type":
                        continue
                    opnd = [x for x in src.invars
                            if not isinstance(x, jax_core.Literal)]
                    if not opnd:
                        continue
                    from_dt = str(opnd[0].aval.dtype)
                    to_dt = str(src.outvars[0].aval.dtype)
                    if from_dt == "bfloat16" and to_dt in ("float32",
                                                           "float64"):
                        key = (from_dt, to_dt)
                        if key in reported:
                            continue
                        reported.add(key)
                        yield self.finding(
                            record,
                            f"sum-reduce operand was widened {from_dt} -> "
                            f"{to_dt} immediately before the collective — "
                            "bf16 leaves must reduce in their own dtype "
                            "(gradsync dtype policy)",
                        )
