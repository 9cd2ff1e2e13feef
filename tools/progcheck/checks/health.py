"""P10: health diagnostics add no collectives to the step program.

The in-graph learning-health diagnostics (ISSUE 13; telemetry/health.py)
promise to ride the step's EXISTING metrics reduction: every scalar they
produce joins the one metrics pmean, and the stride gate is a lax.cond —
a control-flow primitive, never a collective. A diagnostics branch that
grew its own psum (or smuggled an all_gather of embeddings) would put a
new synchronization point on the every-step critical path — including
the off-stride steps, because a collective inside EITHER cond branch
must execute on both (SPMD cond semantics). This check compares each
`<base>+health` program against its base: the collective primitive
multiset must be identical (the metrics reduce may carry more bytes —
that is the design — but gather/permute collectives must not change at
all). P6 separately proves the diagnostics host no callbacks.
"""

from __future__ import annotations

from tools.progcheck.registry import Check, register

_SUFFIX = "+health"
# prims whose payload the health variant may legitimately grow: the
# metrics reduction the diagnostics ride
_REDUCE_PRIMS = ("psum", "psum_invariant", "pmean")


@register
class HealthNoNewCollectives(Check):
    id = "P10"
    title = "health-instrumented steps add no collectives over their base"
    families = ("train", "v3")
    rationale = ("the diagnostics contract is observational: scalars join "
                 "the existing metrics reduce — a new collective would "
                 "add an every-step synchronization point even at "
                 "off-stride steps (SPMD cond runs collectives in both "
                 "branches)")

    def finalize(self, inventory):
        by_name = {r.name: r for r in inventory}
        for rec in inventory:
            if not rec.name.endswith(_SUFFIX):
                continue
            base = by_name.get(rec.name[: -len(_SUFFIX)])
            if base is None:
                continue  # base family not traced this run
            base_prims = sorted(c.prim for c in base.collectives)
            health_prims = sorted(c.prim for c in rec.collectives)
            if base_prims != health_prims:
                yield self.finding(
                    rec,
                    f"collective set changed vs {base.name}: "
                    f"{base_prims} -> {health_prims} — diagnostics must "
                    "ride the existing metrics reduction, never add "
                    "their own collective",
                )
                continue
            base_gathers = sorted(
                (c.prim, tuple(c.axes), c.operand_bytes)
                for c in base.collectives if c.prim not in _REDUCE_PRIMS
            )
            health_gathers = sorted(
                (c.prim, tuple(c.axes), c.operand_bytes)
                for c in rec.collectives if c.prim not in _REDUCE_PRIMS
            )
            if base_gathers != health_gathers:
                yield self.finding(
                    rec,
                    f"non-reduce collective payloads changed vs "
                    f"{base.name}: {base_gathers} -> {health_gathers} — "
                    "the diagnostics may widen the metrics reduce only, "
                    "never a gather/permute",
                )
