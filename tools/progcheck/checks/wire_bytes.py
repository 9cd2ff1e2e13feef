"""P8: the gradsync wire-bytes claim is machine-checked.

GradSync.sync_bytes_per_step() is what telemetry/bench report as the
per-device sync payload — every "quantized cuts sync bytes 4×" claim in
a BENCH record rests on it. This check recomputes the payload FROM THE
JAXPR of the isolated reduce program (GradSync.audit_region_program) and
requires exact equality, so the analytic accounting can never drift from
what the program actually moves.

Wire conventions (mirroring the analytic side):
  - the grads-ready probe (one scalar f32 psum) is excluded — scalars
    are reserved for it by the audit program's contract;
  - quantized int8 rides an int32 CARRIER (XLA exposes no in-collective
    requantization) but the modeled wire payload is the int8 it carries:
    a carrier psum whose operand was converted FROM int8 counts 1 B/elem;
  - the per-leaf scale pmax counts at its native f32 width;
  - demo's sparse (vals, idx) pairs leave the region as P(data)-sharded
    outputs and merge at the outer jit level, so their wire share is the
    per-device slice of the payload avals.
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


def _size(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def measured_wire_bytes(record) -> int:
    """Per-device wire bytes the audited reduce program moves per call."""
    total = 0
    for jaxpr in iter_jaxprs(record.jaxpr):
        producers = build_producers(jaxpr)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in SUM_REDUCE_PRIMS:
                for v in eqn.invars:
                    if isinstance(v, jax_core.Literal):
                        continue
                    aval = v.aval
                    if aval.shape == ():
                        continue  # the grads-ready probe scalar
                    src = trace_back(v, producers, through=("reshape",))
                    if (str(aval.dtype) == "int32" and src is not None
                            and src.primitive.name == "convert_element_type"):
                        opnd = [x for x in src.invars
                                if not isinstance(x, jax_core.Literal)]
                        if opnd and str(opnd[0].aval.dtype) == "int8":
                            total += _size(aval)  # int8 payload on carrier
                            continue
                    total += _size(aval) * int(aval.dtype.itemsize)
            elif name == "pmax":
                for v in eqn.invars:
                    if isinstance(v, jax_core.Literal) or v.aval.shape == ():
                        continue
                    total += _size(v.aval) * int(v.aval.dtype.itemsize)
    # demo: the sparse payload leaves the region as sharded outputs
    payload = record.meta.get("payload_shape")
    n = record.meta.get("mesh_size", 1)
    if isinstance(payload, dict):
        import jax

        for key in ("vals", "idx"):
            for leaf in jax.tree.leaves(payload.get(key, ())):
                total += (_size(leaf) * int(leaf.dtype.itemsize)) // n
    return total


@register
class WireBytesMatchTelemetry(Check):
    id = "P8"
    title = "gradsync wire bytes match the analytic telemetry claim"
    rationale = ("sync_bytes_per_step feeds telemetry and BENCH records; "
                 "if the program moves different bytes than the analytic "
                 "count, every compression claim built on it is fiction")
    families = ("gradsync",)

    def check_program(self, record):
        gs = record.meta.get("gradsync")
        if gs is None:
            return
        if int(getattr(gs, "cadence", 1)) != 1:
            # the analytic count amortizes demo's payload over the cadence;
            # a static audit sees the sync-step program, so the surface
            # builds its audit strategies at cadence 1 where the two agree
            yield self.finding(
                record,
                f"audit program built at cadence {gs.cadence} — wire-bytes "
                "parity is only defined at cadence 1 (fix the surface)",
            )
            return
        claimed = int(gs.sync_bytes_per_step())
        measured = measured_wire_bytes(record)
        if measured != claimed:
            yield self.finding(
                record,
                f"jaxpr wire payload is {measured} B/device/sync but the "
                f"analytic sync-bytes claim is {claimed} B — the telemetry "
                "accounting and the compiled program have drifted",
            )
