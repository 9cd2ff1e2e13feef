"""progcheck: the jaxpr-level program auditor (ISSUE 9).

Three layers, mirroring the acceptance criteria:

- every shipped check has a SEEDED-VIOLATION fixture proving it fires
  (incl. the removed key-encoder stop_gradient and a double-reduced
  gradient), plus a clean negative;
- golden invariant-summary snapshots for train/v3 across all four
  grad_sync modes: a refactor that changes collective count/payload or
  the donation contract diffs loudly against the committed file;
- THE tier-1 gate: `python -m tools.progcheck --json` runs clean over
  the full surface (train/v3 all modes + serve buckets + probes +
  gradsync + trim variants + evals) on the CPU backend inside the 60 s
  budget.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from moco_tpu.parallel.mesh import DATA_AXIS  # noqa: E402
from jax import shard_map  # noqa: E402
from tools.progcheck.engine import Engine  # noqa: E402
from tools.progcheck.inventory import (  # noqa: E402
    golden_json,
    inventory_json,
)
from tools.progcheck.surface import build_surface  # noqa: E402

MESHMETA = {"mesh_axes": ("data",)}


def _record(name, closed, family="train", donated=None, meta=None):
    from tools.progcheck.inventory import make_record

    return make_record(name, family, None, closed, donated=donated,
                       meta={**MESHMETA, **(meta or {})})


def _run(rec, check_id):
    return Engine(select=(check_id,)).run(
        rec if isinstance(rec, list) else [rec]).findings


@pytest.fixture(scope="module")
def probe_records(mesh8):
    return build_surface(mesh=mesh8, families=("probe",), with_cost=False)


@pytest.fixture(scope="module")
def gradsync_records(mesh8):
    return build_surface(mesh=mesh8, families=("gradsync",), with_cost=False)


# ---------------------------------------------------------------------------
# P1: gradient flow into the key encoder / queue
# ---------------------------------------------------------------------------


def test_p1_clean_on_real_probes(probe_records):
    assert [r.name for r in probe_records] == ["probe/train", "probe/v3"]
    for rec in probe_records:
        assert _run(rec, "P1") == [], rec.name


def test_p1_fires_when_key_stop_gradient_removed(mesh8, monkeypatch):
    """THE seeded violation the ISSUE names: delete the key-branch
    stop_gradient (via a patched key path — the production helper minus
    its last stop_gradient) and the auditor must see gradient flow into
    params_k AND the queue."""
    import moco_tpu.train_step as ts
    from moco_tpu.ops.losses import l2_normalize
    from moco_tpu.parallel.collectives import batch_shuffle, batch_unshuffle

    def broken_key_path(config, model):
        def key_path(params_k, stats_k, im_k, key):
            im_k_shuf, perm = batch_shuffle(im_k, key, DATA_AXIS)
            k, mut_k = model.apply(
                {"params": params_k, "batch_stats": stats_k},
                im_k_shuf, train=True, mutable=["batch_stats"],
            )
            k = l2_normalize(k)
            k = batch_unshuffle(k, perm, DATA_AXIS)
            return k, mut_k["batch_stats"]  # stop_gradient DELETED

        return key_path

    monkeypatch.setattr(ts, "_build_key_path", broken_key_path)
    from tools.progcheck.surface import _probe_records

    rec = [r for r in _probe_records(mesh8) if r.name == "probe/train"][0]
    findings = _run(rec, "P1")
    assert findings, "P1 missed the removed stop_gradient"
    msgs = " ".join(f.message for f in findings)
    assert "params_k" in msgs
    # the queue grads stay zero: infonce_logits stop-grads the queue
    # ITSELF (defense in depth) — only the key-encoder path leaks here
    assert "queue" not in msgs


def test_p1_fires_when_v3_momentum_stop_gradient_removed(mesh8, monkeypatch):
    """v3 has TWO stop_gradients on the key path — one in _build_momentum_
    keys, one inside v3_contrastive_loss (defense in depth). The seeded
    violation removes both; P1 must still catch the leak."""
    import moco_tpu.v3_step as v3
    from moco_tpu.ops import losses

    def broken_momentum_keys(model):
        apply = v3._build_apply(model)

        def momentum_keys(params_k, stats_k, x1, x2):
            k1, stats_k = apply(params_k, stats_k, x1, predict=False)
            k2, stats_k = apply(params_k, stats_k, x2, predict=False)
            return k1, k2, stats_k  # stop_gradients DELETED

        return momentum_keys

    def broken_v3_loss(q, k, temperature, axis_name, chunks=1):
        # v3_contrastive_loss minus its own `k = stop_gradient(k)`
        from moco_tpu.parallel.collectives import all_gather_batch

        if axis_name is not None:
            k_all = all_gather_batch(k, axis_name)
            offset = lax.axis_index(axis_name) * q.shape[0]
        else:
            k_all, offset = k, 0
        logits = jnp.einsum("nc,mc->nm", q, k_all,
                            preferred_element_type=jnp.float32) / temperature
        labels = jnp.arange(q.shape[0], dtype=jnp.int32) + offset
        return losses.softmax_cross_entropy(logits, labels) * (
            2.0 * temperature)

    monkeypatch.setattr(v3, "_build_momentum_keys", broken_momentum_keys)
    monkeypatch.setattr(v3, "v3_contrastive_loss", broken_v3_loss)
    from tools.progcheck.surface import _probe_records

    rec = [r for r in _probe_records(mesh8) if r.name == "probe/v3"][0]
    findings = _run(rec, "P1")
    assert findings and "params_k" in findings[0].message


def test_p1_flags_vacuous_probe(mesh8):
    """A probe whose 'flow' grads are constants is auditing nothing."""
    def region(x):
        return lax.pmean(jnp.zeros((4,)), DATA_AXIS)

    fn = shard_map(region, mesh=mesh8, in_specs=(P(DATA_AXIS),),
                   out_specs=P())
    rec = _record("fix/vacuous", jax.make_jaxpr(fn)(jnp.zeros((16, 4))),
                  family="probe",
                  meta={"flow_groups": [("params_q", 0, 1)],
                        "zero_groups": []})
    findings = _run(rec, "P1")
    assert findings and "vacuous" in findings[0].message


# ---------------------------------------------------------------------------
# P2/P3: collective axis hygiene
# ---------------------------------------------------------------------------


def test_p2_flags_axis_missing_from_mesh(mesh8):
    def region(x):
        return lax.pmean(x, DATA_AXIS)

    fn = shard_map(region, mesh=mesh8, in_specs=(P(DATA_AXIS),),
                   out_specs=P())
    rec = _record("fix/axis", jax.make_jaxpr(fn)(jnp.zeros((16, 4))),
                  meta={"mesh_axes": ("model",)})  # program/mesh forked
    findings = _run(rec, "P2")
    assert findings and "'data'" in findings[0].message


def test_p2_p7_clean_on_resized_mesh_programs(mesh8):
    """ISSUE 11 satellite: the elastic relaunch's 2-device step programs
    are part of the audited surface — their collectives bind to the
    RESIZED mesh (P2) and the donation contract survives the rebuild
    (P7). The quantized record carries the [2, ...] accumulator leaves
    the dialect shim rebuilds fresh-zero at a mesh hop."""
    records = build_surface(mesh=mesh8, families=("resize",),
                            with_cost=False)
    assert [r.name for r in records] == ["resize/fused@2dev",
                                         "resize/quantized@2dev"]
    for rec in records:
        assert rec.meta["mesh_size"] == 2
        assert _run(rec, "P2") == [], rec.name
        assert _run(rec, "P7") == [], rec.name


def test_p3_fires_on_double_reduced_gradient(mesh8):
    """The ISSUE's second named fixture: grads pmean'd inline BEFORE the
    gradsync reduce — the classic silently-rescaled-gradient regression."""
    from moco_tpu.config import PretrainConfig
    from moco_tpu.parallel.gradsync import GradSync

    gs = GradSync(PretrainConfig(arch="resnet_tiny", cifar_stem=True,
                                 batch_size=16, epochs=1, lr=0.1), 8)

    def region(params, x, step):
        grads = jax.grad(lambda p: jnp.sum((x @ p) ** 2))(params)
        grads = lax.pmean(grads, DATA_AXIS)        # seeded double reduce
        reduced, _, _ = gs.region_reduce({"w": grads}, {}, step)
        return reduced

    fn = shard_map(region, mesh=mesh8,
                   in_specs=(P(), P(DATA_AXIS), P()), out_specs=P())
    closed = jax.make_jaxpr(fn)(jnp.zeros((4, 4)), jnp.zeros((16, 4)),
                                jnp.int32(0))
    findings = _run(_record("fix/double_grad", closed), "P3")
    assert findings and "reduced" in findings[0].message


def test_p3_clean_on_single_reduce_and_real_steps(mesh8, gradsync_records):
    def region(x):
        return lax.pmean(x, DATA_AXIS)

    fn = shard_map(region, mesh=mesh8, in_specs=(P(DATA_AXIS),),
                   out_specs=P())
    rec = _record("fix/single", jax.make_jaxpr(fn)(jnp.zeros((16, 4))))
    assert _run(rec, "P3") == []
    for rec in gradsync_records:  # chained/quantized psums are NOT double
        assert _run(rec, "P3") == [], rec.name


# ---------------------------------------------------------------------------
# P4/P5: dtype policy
# ---------------------------------------------------------------------------


def test_p4_flags_averaged_integer_reduce(mesh8):
    def region(x):
        return lax.psum(x, DATA_AXIS) / 8

    fn = shard_map(region, mesh=mesh8, in_specs=(P(DATA_AXIS),),
                   out_specs=P())
    closed = jax.make_jaxpr(fn)(jnp.zeros((16, 4), jnp.int32))
    findings = _run(_record("fix/intavg", closed), "P4")
    assert findings and "never averaged" in findings[0].message


def test_p5_flags_bf16_widened_before_reduce(mesh8):
    def region(x):
        return lax.psum(x.astype(jnp.float32), DATA_AXIS)

    fn = shard_map(region, mesh=mesh8, in_specs=(P(DATA_AXIS),),
                   out_specs=P())
    closed = jax.make_jaxpr(fn)(jnp.zeros((16, 4), jnp.bfloat16))
    findings = _run(_record("fix/widen", closed), "P5")
    assert findings and "bfloat16 -> float32" in findings[0].message


def test_p4_p5_clean_on_real_gradsync(gradsync_records):
    for rec in gradsync_records:
        assert _run(rec, "P4") == [], rec.name
        assert _run(rec, "P5") == [], rec.name


# ---------------------------------------------------------------------------
# P6: host callbacks
# ---------------------------------------------------------------------------


def test_p6_flags_debug_print_in_step():
    @jax.jit
    def step(x):
        jax.debug.print("loss={x}", x=x[0])
        return x * 2

    closed = jax.make_jaxpr(step)(jnp.zeros((4,)))
    findings = _run(_record("fix/callback", closed), "P6")
    assert findings and "debug_print" in findings[0].message


# ---------------------------------------------------------------------------
# P7: donation aliasing
# ---------------------------------------------------------------------------


def test_p7_flags_unaliasable_donation():
    import functools
    import warnings

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(x):
        return jnp.concatenate([x, x])  # no [4]-shaped output to alias

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed = jax.make_jaxpr(step)(jnp.zeros((4,)))
    donated = closed.jaxpr.eqns[0].params["donated_invars"]
    findings = _run(_record("fix/donate", closed, donated=donated), "P7")
    assert findings and "degrades to a copy" in findings[0].message


# ---------------------------------------------------------------------------
# P8: gradsync wire bytes vs telemetry claim
# ---------------------------------------------------------------------------


def test_p8_clean_on_all_modes(gradsync_records):
    # "quantized@2d" (ISSUE 15) is the DynamiQ multi-hop reduce over the
    # 2-D mesh — P8 verifies its per-hop bytes sum to the analytic claim
    assert sorted(r.mode for r in gradsync_records) == [
        "bucketed", "demo", "fused", "quantized", "quantized@2d"]
    for rec in gradsync_records:
        assert _run(rec, "P8") == [], rec.name


def test_p8_fires_when_program_moves_extra_bytes(mesh8):
    """Sabotage: the region psums a tensor the analytic accounting does
    not know about — the jaxpr payload and the telemetry claim diverge."""
    from moco_tpu.config import PretrainConfig
    from moco_tpu.parallel.gradsync import GradSync

    gs = GradSync(PretrainConfig(arch="resnet_tiny", cifar_stem=True,
                                 batch_size=16, epochs=1, lr=0.1), 8)
    params = {"w": jnp.zeros((64,), jnp.float32)}
    fn, args, payload = gs.audit_region_program(params, mesh8)

    def smuggling(grads, state, step):
        reduced, new_state = fn(grads, state, step)
        extra = shard_map(lambda z: lax.psum(z, DATA_AXIS), mesh=mesh8,
                          in_specs=(P(DATA_AXIS),), out_specs=P())(
            jnp.zeros((16, 4)))
        return jax.tree.map(lambda g: g + 0 * extra.sum(), reduced), new_state

    closed = jax.make_jaxpr(smuggling)(*args)
    rec = _record("gradsync/fused", closed, family="gradsync",
                  meta={"gradsync": gs, "payload_shape": payload,
                        "mesh_size": mesh8.size})
    findings = _run(rec, "P8")
    assert findings and "drifted" in findings[0].message


# ---------------------------------------------------------------------------
# P9: bounded compile set
# ---------------------------------------------------------------------------


def test_p9_flags_shape_outside_the_ladder(mesh8):
    def make(n):
        closed = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((n, 4)))
        return _record(f"serve/bucket{n}", closed, family="serve",
                       meta={"max_programs": 2})

    clean = [make(1), make(8)]
    assert _run(clean, "P9") == []
    findings = _run(clean + [make(32)], "P9")
    assert findings and "no longer closed" in findings[0].message


# ---------------------------------------------------------------------------
# golden invariant snapshots (satellite)
# ---------------------------------------------------------------------------


GOLDEN_PATH = os.path.join(REPO, "tools", "progcheck",
                           "golden_invariants.json")


def test_golden_invariant_summaries_match_committed(mesh8):
    """Collective count/shape/payload and the donation contract of the
    train and v3 steps, across ALL FOUR grad_sync modes, pinned against
    tools/progcheck/golden_invariants.json. A refactor that changes any
    of it must regenerate the golden deliberately:

        python -m tools.progcheck --families train,v3 --no-flops \\
            --write-golden tools/progcheck/golden_invariants.json
    """
    records = build_surface(mesh=mesh8, families=("train", "v3"),
                            with_cost=False)
    # JSON-normalize (tuples -> lists) so current compares to committed
    current = json.loads(json.dumps(golden_json(records, mesh8.size)))
    with open(GOLDEN_PATH, encoding="utf-8") as f:
        committed = json.load(f)
    assert sorted(current["programs"]) == sorted(committed["programs"])
    for name in sorted(current["programs"]):
        assert current["programs"][name] == committed["programs"][name], (
            f"{name}: program invariants drifted from the golden — if the "
            "change is intentional, regenerate (see docstring)"
        )


# ---------------------------------------------------------------------------
# THE tier-1 gate + inventory/report fold (satellite)
# ---------------------------------------------------------------------------


def test_repo_gate_full_surface_clean_within_budget(tmp_path):
    """ISSUE 9 acceptance: the gate runs clean over train/v3 (all four
    grad_sync modes) + serve bucket programs (+ probes, gradsync, trim
    variants, evals) on the CPU backend in < 60 s, and the inventory it
    writes feeds telemetry_report's --programs fold."""
    inv_path = str(tmp_path / "inventory.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.progcheck", "--json",
         "--inventory", inv_path],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert elapsed < 60.0, f"progcheck gate took {elapsed:.1f}s"
    out = json.loads(proc.stdout)
    assert out["findings"] == []
    names = {p["name"] for p in out["inventory"]["programs"]}
    for fam in ("train", "v3"):
        for mode in ("fused", "bucketed", "quantized", "demo"):
            assert f"{fam}/{mode}" in names
    assert {"serve/bucket1", "serve/bucket8", "serve/bucket32",
            "serve/bucket128"} <= names
    assert {"probe/train", "probe/v3"} <= names
    # ISSUE 11: the resized-mesh step programs (the elastic 1→2 relaunch's
    # compiles) are part of the audited surface, so P2 pins their
    # collectives to the 2-device mesh
    assert {"resize/fused@2dev", "resize/quantized@2dev"} <= names

    inv = json.load(open(inv_path))
    assert inv["program_count"] == len(names)
    # the fold telemetry_report --programs performs
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools",
                                         "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    summary = report.fold_programs({"steps": 0}, inv)
    assert summary["programs"]["count"] == inv["program_count"]
    assert set(summary["programs"]["gradsync_bytes_per_step"]) == {
        "fused", "bucketed", "quantized", "demo", "quantized@2d"}
    cross = summary["programs"].get("mfu_cross_check", [])
    assert cross, "no mfu_cross_check rows (cost_analysis unavailable?)"
    # v1 proxy: the backbone the analytic model counts IS the program's
    # dominant compute — the two counts must agree within 2x. The v3
    # proxy's 4096-wide projector/predictor MLPs (which mfu.py documents
    # as uncounted) dwarf the tiny backbone, so its ratio only has to be
    # finite and positive here; at real scale the backbone dominates.
    for row in cross:
        assert row["ratio"] > 0, row
        if row["name"].startswith("train/"):
            assert 0.5 < row["ratio"] < 2.0, row


def test_inventory_json_shape(gradsync_records, mesh8):
    inv = inventory_json(gradsync_records, mesh8.size)
    assert inv["version"] == 1 and inv["by_family"] == {"gradsync": 5}
    rec = inv["programs"][0]
    assert {"name", "family", "collectives", "collective_bytes",
            "in_avals"} <= set(rec)
    assert all(c["axes"] == ["data"] or c["axes"] == ("data",)
               for c in rec["collectives"])


def test_cli_list_checks():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.progcheck", "--list-checks"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert proc.returncode == 0
    for cid in ("P1", "P2", "P3", "P4", "P5", "P6", "P7", "P8", "P9"):
        assert cid in proc.stdout
