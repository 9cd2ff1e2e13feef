"""ISSUE 35, the benchmark's side: the six readers over the step records' phases
(every step of the window, steady state), on hand-made records; their copy of
the field names and of the stall rule held to the program's; their entries in
the manifest."""

import pytest

from pb_helpers import ROOT

CELLS = ["r50-v2-f32.synthetic", "sdar-30b-a3b-ep8.tokens512", "ouro-2.6b-l6.tokens512-v49k",
         "keye-vl2-30b-a3b-ep8.tokens8k"]
# name -> (unit, source, layer)
READERS = {
    "host_floor_ms_per_step": ("ms", "program_span", "driver loop"),
    "loop_other_ms_per_step": ("ms", "program_span", "driver loop"),
    "starved_steps_pct": ("%", "program_counter", "device"),
    "starved_idle_pct": ("%", "program_counter", "device"),
    "stall_ms_in_window": ("ms", "program_span", "driver loop"),
    "gc_ms_per_step": ("ms", "program_counter", "driver loop"),
}


@pytest.fixture(scope="module")
def manifest():
    from perfbench import harness

    return harness.Manifest(ROOT)


def read(manifest, name, records):
    from perfbench import harness

    mod = harness.load_module(manifest.find("layer_metrics", name + ".py"), "p35_" + name)
    return mod.read({"window_records": records, "records": records})


def step(n, step_s=0.132, wait_s=0.1, **more):
    """A device-bound step's record as the program writes it: the phases sum
    to `step_s`, `loop_s` takes what the others leave."""
    rec = dict(step=n, step_s=step_s, data_s=0.0001, host_s=0.024, telemetry_s=0.0006,
               wait_s=wait_s, **more)
    spanned = sum(v for k, v in rec.items() if k.endswith("_s") and k not in ("step_s", "gc_s"))
    rec["loop_s"] = round(step_s - spanned, 6)
    return {k: v for k, v in rec.items() if v != 0.0}


def window(stalled=(), starved=(), fenced=(), collected=(), steps=80):
    recs = []
    for n in range(1, steps + 1):
        more = {}
        if n in fenced:             # the fence takes the wait's place
            more.update(wait_s=0.0, fence_s=0.1)
        if n in starved:
            more["starved"] = 1
        if n in collected:
            more.update(gc_s=0.004, gc_n=1)
        if n in stalled:
            more.update(step_s=0.132 + 1.2, wait_s=more.get("wait_s", 0.1) + 1.2)
        recs.append(step(n, **more))
    return recs


FENCED = tuple(range(16, 81, 16))
STARVED = tuple(sorted({n + 1 for n in FENCED if n < 80} | set(range(2, 81, 10))))
SOUND = window(starved=STARVED, fenced=FENCED, collected=(40,))
ONE_STALL = window(stalled=(33,), starved=STARVED, fenced=FENCED)
EXPECTED = {
    # name: (a sound window, a window with one stall, a window with no `starved`)
    "host_floor_ms_per_step": (32.0, 32.0, 32.0),    # 0.1 + 24 + 0.6 + the loop's 7.3
    "loop_other_ms_per_step": (7.3, 7.3, 7.3),       # 132 less 124.7 under the spans
    "starved_steps_pct": (100.0 * len(STARVED) / 80, 100.0 * len(STARVED) / 80, 0.0),
    "starved_idle_pct": (100.0 * len(STARVED) * 0.024 / (80 * 0.132),
                         100.0 * len(STARVED) * 0.024 / (80 * 0.132 + 1.2), 0.0),
    "stall_ms_in_window": (0.0, 1200.0, 0.0),
    "gc_ms_per_step": (1e3 * 0.004 / 80, 0.0, 0.0),
}


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("case", ["sound", "one_stall", "no_starved", "empty", "older_program"])
def test_readers_over_hand_made_records(manifest, name, case):
    if case == "empty":
        assert read(manifest, name, []) is None
    elif case == "older_program":
        # the parent's records: four fields and nothing else; nothing to read, nothing raised
        older = [dict(step=n, step_s=0.132, data_s=0.0001, host_s=0.024, telemetry_s=0.0006)
                 for n in range(1, 81)]
        assert read(manifest, name, older) is None
    else:
        records = {"sound": SOUND, "one_stall": ONE_STALL,
                   "no_starved": window(fenced=FENCED)}[case]
        want = EXPECTED[name][("sound", "one_stall", "no_starved").index(case)]
        assert read(manifest, name, records) == pytest.approx(want, abs=1e-6)


def test_a_stall_is_taken_against_the_windows_own_median(manifest):
    from perfbench import step_phases

    # two stalls and a slow step that is none: 0.9 s over a 0.672 s step, 4.6 s, 0.017 s
    recs = [step(n, step_s=0.672, wait_s=0.64) for n in range(1, 77)]
    recs += [step(77, step_s=1.572, wait_s=1.54), step(78, step_s=5.272, wait_s=5.24),
             step(79, step_s=0.689, wait_s=0.657), step(80, step_s=0.672, wait_s=0.64)]
    assert step_phases.stall_ms({"window_records": recs}) == pytest.approx(900.0 + 4600.0)
    assert read(manifest, "stall_ms_in_window", recs) == pytest.approx(5500.0)
    assert not step_phases.is_stall(0.591, 0.546) and step_phases.is_stall(1.57, 0.672)


def test_a_step_that_drains_the_queue_is_held_against_two_steps(manifest):
    """As the chip reads them (PERF.md 6, PR 35): a fenced or printing step of the
    R50 cell takes 263 ms of a 131 ms median and the step after it 25 - 43."""
    recs = []
    for n in range(1, 81):
        if n % 16 == 0:
            recs.append(step(n, step_s=0.2633, wait_s=0.0, fence_s=0.2365))
        elif n % 10 == 1:
            recs.append(step(n, step_s=0.2633, wait_s=0.105, readback_s=0.1315))
        elif n % 16 == 1 or n % 10 == 2:
            recs.append(step(n, step_s=0.026, wait_s=0.0, starved=1))
        else:
            recs.append(step(n, step_s=0.1307, wait_s=0.1))
    assert read(manifest, "stall_ms_in_window", recs) == 0.0
    # 0.9 s lost in a fenced step: over TWICE the median
    recs[31] = step(32, step_s=1.1633, wait_s=0.0, fence_s=1.1365)
    assert read(manifest, "stall_ms_in_window", recs) == pytest.approx(1163.3 - 2 * 130.7)
    # the harness closes its window with a wait for the device inside the last
    # step's dispatch (`host` +0.133 s in every R50 run): a dispatch that took a
    # whole step waited for its own result, wherever in the window it stands
    closing = dict(step(80, step_s=0.2643, wait_s=0.0), host_s=0.2643 - 0.0007 - 0.0073)
    recs[79] = closing
    assert read(manifest, "stall_ms_in_window", recs) == pytest.approx(1163.3 - 2 * 130.7)
    assert read(manifest, "stall_ms_in_window", recs + [step(81)]) == pytest.approx(
        1163.3 - 2 * 130.7)
    # and a real stall on the window's last step is not hidden: 0.9 s in its wait,
    # 0.9 s in a dispatch that waited
    recs[79] = step(80, step_s=1.0307, wait_s=1.0)
    assert read(manifest, "stall_ms_in_window", recs) == pytest.approx(1163.3 - 2 * 130.7 + 900.0)
    recs[79] = dict(closing, step_s=1.1643, host_s=closing["host_s"] + 0.9)
    assert read(manifest, "stall_ms_in_window", recs) == pytest.approx(
        1163.3 - 2 * 130.7 + 1164.3 - 2 * 130.7)


def test_the_readers_names_and_rule_are_the_programs():
    from moco_tpu.telemetry import timing, trace
    from perfbench import step_phases

    assert step_phases.PHASES == timing.PHASE_FIELDS + (timing.LOOP_FIELD,)
    assert set(step_phases.HOST_FLOOR) | set(step_phases.SINCE_35) >= set(step_phases.PHASES)
    assert set(step_phases.SINCE_35) <= set(step_phases.PHASES)
    assert (step_phases.STALL_MIN_EXCESS_S, step_phases.STALL_MIN_SHARE, step_phases.DRAINS,
            step_phases.SYNC) == (trace.STALL_MIN_EXCESS_S, trace.STALL_MIN_SHARE,
                                  trace.STALL_DRAIN_FIELDS, trace.STALL_SYNC_FIELD)
    assert set(step_phases.DRAINS) | {step_phases.SYNC} <= set(step_phases.PHASES)
    for rec in ({"host_s": 0.023, "wait_s": 0.64}, {"host_s": 0.023, "fence_s": 1.3},
                {"readback_s": 0.2}, {"host_s": 0.805}, {"host_s": 0.671}, {}):
        assert step_phases.drained(rec, 0.672) == trace.drained(rec, 0.672)
    for step_s, median_s in ((1.57, 0.672), (0.689, 0.672), (0.591, 0.546), (0.24, 0.132),
                             (0.225, 0.132), (4.2, 4.0), (0.2633, 0.1307), (1.163, 0.1307)):
        for drained in (False, True):
            assert step_phases.is_stall(step_s, median_s, drained) == trace.is_stall(
                step_s, median_s, drained)
    # the counters as the program's timer and watch write them
    timer = timing.StepPhaseTimer()
    timer.epoch_start()

    class Done:
        def is_ready(self):
            return True

    timer.probe_idle(Done())
    assert step_phases.STARVED in timer.finish_step()
    watch = timing.GcWatch()
    try:
        import gc

        gc.collect()
        assert step_phases.GC_SECONDS in watch.drain()
    finally:
        watch.close()


def test_the_six_entries_stand_in_the_manifest_with_all_four_cells(manifest):
    """Neither a count of entries nor a last place is asserted (PERF.md 7.8 a, h, j):
    a later PR appends after them."""
    entries = {p["name"]: p for p in manifest.data["per_layer"]}
    for name, (unit, source, layer) in READERS.items():
        entry = entries[name]
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": source,
                         "layer": layer, "moves": "train_imgs_per_s_per_chip",
                         "workloads": CELLS}
        assert manifest.find("layer_metrics", name + ".py")
    names = [p["name"] for p in manifest.data["per_layer"]]
    first = names.index("host_floor_ms_per_step")
    assert names[first: first + len(READERS)] == list(READERS)
    assert first > names.index("sel_live_tile_share")      # appended, not put in the middle
    for cell in CELLS:
        assert set(READERS) <= {m["name"] for m in manifest.metrics("per_layer", cell)}
    # what they stand beside is as it was
    assert entries["loop_unspanned_ms_per_step"]["workloads"] == CELLS
    assert "workloads" not in entries["device_idle_pct"] and "workloads" not in entries["host_ms_per_step"]
