"""tools/mocolint in tier-1: the pluggable analysis engine (ISSUE 7).

Covers: per-rule positive+negative fixtures for the new rules R8-R11,
suppression + unused-suppression reporting, baseline round-trip, the
--json schema, and the repo gate — `python -m tools.mocolint moco_tpu
tools bench.py` must be CLEAN (zero unsuppressed findings) and fast
(single parse per file; the whole-repo budget is 5 s).

R1-R7 behavior parity is pinned by tests/test_lint_robustness.py, which
runs unmodified against the legacy shim.
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

from tools.mocolint import baseline as baseline_mod  # noqa: E402
from tools.mocolint.config import DEFAULT_CONFIG  # noqa: E402
from tools.mocolint.engine import Engine, module_name_for  # noqa: E402


def run_on(tmp_path, rel, body, select=None):
    """Write `body` at tmp_path/rel and run the default config on it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(body)
    return Engine(DEFAULT_CONFIG, select=select).run([str(path)]).findings


def rules_of(findings):
    return [f.rule for f in findings]


# -- R7: the fsdp extension (ISSUE 15) --------------------------------------


def test_r7_flags_param_gather_scatter_outside_parallel(tmp_path):
    """ISSUE 15: inline all_gather/psum_scatter on param-named operands
    outside parallel/ bypasses the ShardingPlan's per-leaf bookkeeping;
    gathers on non-param values (keys, batches) stay legal."""
    findings = run_on(
        tmp_path, "moco_tpu/stepish.py",
        "from jax import lax\n"
        "def region(params_q, k2, grads):\n"
        "    full = lax.all_gather(params_q, 'fsdp')\n"      # violation
        "    shard = lax.psum_scatter(grads, 'fsdp')\n"      # violation
        "    keys = lax.all_gather(k2, 'data')\n"            # legal
        "    return full, shard, keys\n",
        select=("R7",),
    )
    assert rules_of(findings) == ["R7", "R7"]
    assert any("ShardingPlan" in f.message for f in findings)
    assert any("gradsync API" in f.message for f in findings)


def test_r7_allows_param_gather_under_parallel(tmp_path):
    findings = run_on(
        tmp_path, "moco_tpu/parallel/fsdpish.py",
        "from jax import lax\n"
        "def gather(params):\n"
        "    return lax.all_gather(params, 'fsdp')\n",
        select=("R7",),
    )
    assert findings == []


# -- R8: host syncs in traced step code -------------------------------------

R8_POSITIVE = """\
import jax
import numpy as np

def build_step(tx):
    def train_step(state, batch):
        loss = compute(state, batch)
        metrics = {"loss": loss.item()}          # sync
        arr = np.asarray(loss)                   # host materialization
        scale = float(loss)                      # scalar coercion
        jax.block_until_ready(loss)              # fence
        if batch.shape[0] > 4:                   # shape branch
            loss = loss * 2
        return state, metrics
    return jax.jit(train_step, donate_argnums=(0,))
"""


def test_r8_flags_host_syncs_inside_traced_functions(tmp_path):
    found = run_on(tmp_path, "moco_tpu/train_step.py", R8_POSITIVE,
                   select=("R8",))
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 5, msgs
    assert ".item()" in msgs and "np.asarray" in msgs
    assert "`float(...)`" in msgs and "block_until_ready" in msgs
    assert "branch on `.shape`" in msgs


def test_r8_ignores_host_code_outside_traced_functions(tmp_path):
    # the SAME calls in build-time (host) code are legal: R8 is scoped to
    # traced bodies, not to the module
    body = """\
import jax
import numpy as np

def build_step(cfg, arrs):
    dim = int(np.asarray(arrs[0]).shape[-1])     # host setup: fine
    jax.block_until_ready(arrs)                  # host setup: fine
    def train_step(state, batch):
        return state
    return jax.jit(train_step)
"""
    assert run_on(tmp_path, "moco_tpu/train_step.py", body,
                  select=("R8",)) == []


def test_r8_sees_through_shard_map_and_nesting(tmp_path):
    body = """\
from jax import shard_map

def build(mesh):
    def region(x):
        def inner(y):
            return y.item()                      # nested: still traced
        return inner(x)
    return shard_map(region, mesh=mesh, in_specs=None, out_specs=None)
"""
    found = run_on(tmp_path, "moco_tpu/v3_step.py", body, select=("R8",))
    assert len(found) == 1 and ".item()" in found[0].message


def test_r8_scoped_to_step_builder_modules(tmp_path):
    # a traced .item() in a NON-step-builder module is not R8's business
    assert run_on(tmp_path, "moco_tpu/evals/lincls.py", R8_POSITIVE,
                  select=("R8",)) == []


def test_r8_clean_on_real_step_builders():
    for rel in ("moco_tpu/train_step.py", "moco_tpu/v3_step.py",
                "moco_tpu/serve/engine.py"):
        found = Engine(DEFAULT_CONFIG, select=("R8",)).run(
            [os.path.join(REPO, rel)]).findings
        assert found == [], [f.human() for f in found]


# -- R9: Python-side nondeterminism -----------------------------------------

def test_r9_flags_global_rng_and_wall_clock(tmp_path):
    body = """\
import random
import time
import numpy as np

def pick(xs):
    k = random.choice(xs)                        # global RNG
    jitter = np.random.rand()                    # numpy global RNG
    stamp = time.time()                          # wall clock as a value
    return k, jitter, stamp
"""
    found = run_on(tmp_path, "moco_tpu/data/augment.py", body,
                   select=("R9",))
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 3, msgs
    assert "random.choice" in msgs and "np.random.rand" in msgs
    assert "time.time()" in msgs


def test_r9_allows_seeded_generators_and_perf_counter(tmp_path):
    body = """\
import time
import numpy as np

def shuffle(n, seed, epoch):
    rng = np.random.RandomState(seed * 100003 + epoch)
    g = np.random.default_rng(seed)
    t0 = time.perf_counter()                     # telemetry: fine
    return rng.permutation(n), g, time.perf_counter() - t0
"""
    assert run_on(tmp_path, "moco_tpu/data/loader.py", body,
                  select=("R9",)) == []


def test_r9_keyword_seed_counts_as_seeded(tmp_path):
    body = """\
import numpy as np

def make(seed):
    return np.random.default_rng(seed=seed), np.random.RandomState(seed=seed)
"""
    assert run_on(tmp_path, "moco_tpu/data/loader.py", body,
                  select=("R9",)) == []


def test_r9_flags_set_iteration(tmp_path):
    body = """\
def order(tags):
    out = []
    for t in set(tags):                          # hash-order iteration
        out.append(t)
    return out, [x for x in {1, 2, 3}]           # set-literal comprehension
"""
    found = run_on(tmp_path, "moco_tpu/ops/queue.py", body, select=("R9",))
    assert len(found) == 2
    assert all("iteration over a set" in f.message for f in found)


def test_r9_scoped_to_bit_identity_modules(tmp_path):
    # the supervisor's restart jitter legitimately uses random: out of scope
    body = "import random\ndelay = random.uniform(0, 1)\n"
    assert run_on(tmp_path, "moco_tpu/resilience/supervisor.py", body,
                  select=("R9",)) == []


# -- R10: thread-safety audit ------------------------------------------------

R10_RACY = """\
import threading

class Racy:
    def __init__(self):
        self.count = 0                           # init: before the thread
        self._lock = threading.Lock()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        while True:
            self.count += 1                      # worker write, no lock

    def reset(self):
        self.count = 0                           # public write, no lock
"""


def test_r10_flags_unlocked_shared_writes(tmp_path):
    found = run_on(tmp_path, "mod.py", R10_RACY, select=("R10",))
    assert len(found) == 2
    assert {"_work", "reset"} <= {
        m for f in found for m in ("_work", "reset") if m in f.message
    }


def test_r10_accepts_locked_writes_and_worker_only_state(tmp_path):
    body = """\
import threading

class Locked:
    def __init__(self):
        self.count = 0
        self.progress = 0
        self._cond = threading.Condition()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def _work(self):
        with self._cond:
            self.count += 1                      # locked: fine
        self.progress += 1                       # worker-ONLY attr: fine

    def reset(self):
        with self._cond:
            self.count = 0                       # locked: fine
"""
    assert run_on(tmp_path, "mod.py", body, select=("R10",)) == []


def test_r10_tracks_worker_reachability_through_helpers(tmp_path):
    body = """\
import threading

class Indirect:
    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()
        threading.Thread(target=self._loop).start()

    def _loop(self):
        self._step()                             # helper reached from worker

    def _step(self):
        self.n += 1                              # effectively a worker write

    def reset(self):
        self.n = 0
"""
    found = run_on(tmp_path, "mod.py", body, select=("R10",))
    assert len(found) == 2, [f.message for f in found]


def test_r10_ignores_classes_without_threads(tmp_path):
    body = """\
class Plain:
    def a(self):
        self.x = 1

    def b(self):
        self.x = 2
"""
    assert run_on(tmp_path, "mod.py", body, select=("R10",)) == []


def test_r10_clean_on_real_threaded_classes():
    for rel in ("moco_tpu/serve/batcher.py", "moco_tpu/data/loader.py",
                "moco_tpu/resilience/watchdog.py", "moco_tpu/serve/http.py"):
        found = Engine(DEFAULT_CONFIG, select=("R10",)).run(
            [os.path.join(REPO, rel)]).findings
        assert found == [], [f.human() for f in found]


# -- R11: import boundaries --------------------------------------------------

def test_r11_transitive_serve_chain(tmp_path):
    (tmp_path / "moco_tpu" / "serve").mkdir(parents=True)
    (tmp_path / "moco_tpu" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "serve" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "helper.py").write_text("import optax\n")
    (tmp_path / "moco_tpu" / "serve" / "svc.py").write_text(
        "from moco_tpu.helper import thing\n"
    )
    found = Engine(DEFAULT_CONFIG, select=("R11",)).run(
        [str(tmp_path / "moco_tpu")]).findings
    assert len(found) == 1
    assert "import chain reaches 'optax'" in found[0].message
    assert found[0].path.endswith("svc.py")


def test_r11_stdlib_only_supervisor(tmp_path):
    found = run_on(tmp_path, "moco_tpu/resilience/supervisor.py",
                   "import os\nimport numpy as np\n", select=("R11",))
    assert len(found) == 1
    assert "stdlib-only" in found[0].message and "numpy" in found[0].message


def test_r11_stdlib_only_transitive_through_package(tmp_path):
    (tmp_path / "moco_tpu" / "resilience").mkdir(parents=True)
    (tmp_path / "moco_tpu" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "resilience" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "heavy.py").write_text("import jax\n")
    (tmp_path / "moco_tpu" / "resilience" / "supervisor.py").write_text(
        "from moco_tpu.heavy import thing\n"
    )
    found = Engine(DEFAULT_CONFIG, select=("R11",)).run(
        [str(tmp_path / "moco_tpu")]).findings
    assert len(found) == 1
    assert "non-stdlib 'jax'" in found[0].message


def test_r11_orbax_must_stay_lazy(tmp_path):
    body = """\
import orbax.checkpoint as ocp                   # module level: flagged

def save(tree):
    import orbax.checkpoint as lazy_ocp          # lazy: fine
    return lazy_ocp, ocp
"""
    found = run_on(tmp_path, "moco_tpu/checkpoint.py", body,
                   select=("R11",))
    assert len(found) == 1 and found[0].line == 1
    assert "imported lazily" in found[0].message


def test_r11_type_checking_imports_are_exempt(tmp_path):
    body = """\
from typing import TYPE_CHECKING

if TYPE_CHECKING:
    import orbax.checkpoint as ocp               # annotations only: fine
"""
    assert run_on(tmp_path, "moco_tpu/checkpoint.py", body,
                  select=("R11",)) == []


def test_r11_clean_on_real_boundary_files():
    paths = [os.path.join(REPO, p) for p in
             ("moco_tpu", "tools/supervise.py")]
    found = Engine(DEFAULT_CONFIG, select=("R11",)).run(paths).findings
    assert found == [], [f.human() for f in found]


# -- suppression -------------------------------------------------------------

def test_suppression_trailing_and_standalone(tmp_path):
    body = """\
def f():
    try:
        pass
    except:  # mocolint: disable=R1 -- fixture exercises the syntax
        pass
    # mocolint: disable=R1
    try:
        pass
    except Exception:
        raise
"""
    # NB the second suppression covers line 7 (`try:`) where nothing
    # fires -> reported as unused
    result = Engine(DEFAULT_CONFIG, select=("R1",)).run(
        [_write(tmp_path, "mod.py", body)])
    assert rules_of(result.findings) == ["SUP"]
    assert len(result.suppressed) == 1


def test_suppression_is_rule_specific(tmp_path):
    body = """\
try:
    pass
except:  # mocolint: disable=R3 -- wrong id: does NOT cover R1
    pass
"""
    result = Engine(DEFAULT_CONFIG, select=("R1", "R3")).run(
        [_write(tmp_path, "mod.py", body)])
    assert rules_of(result.findings) == ["R1", "SUP"]


def test_select_subset_does_not_flag_other_rules_suppressions(tmp_path):
    """A valid R8 suppression must not read as 'unused' just because a
    --select run never gave R8 the chance to fire."""
    body = """\
import jax

def build():
    def step(x):
        return x.item()  # mocolint: disable=R8 -- fixture: deliberate
    return jax.jit(step)
"""
    path = _write(tmp_path, "moco_tpu/train_step.py", body)
    full = Engine(DEFAULT_CONFIG).run([path])
    assert full.findings == [] and len(full.suppressed) == 1
    subset = Engine(DEFAULT_CONFIG, select=("R1",)).run([path])
    assert subset.findings == []


def test_suppression_all_and_docstring_mentions_ignored(tmp_path):
    body = '''\
"""Docs quoting the syntax: # mocolint: disable=R1 — not a suppression."""
try:
    pass
except:  # mocolint: disable=all -- chaos fixture
    pass
'''
    result = Engine(DEFAULT_CONFIG, select=("R1",)).run(
        [_write(tmp_path, "mod.py", body)])
    assert result.findings == [] and len(result.suppressed) == 1


def _write(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(body)
    return str(path)


# -- baseline ----------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    dirty = _write(tmp_path, "mod.py", "try:\n    x=1\nexcept:\n    pass\n")
    engine = Engine(DEFAULT_CONFIG, select=("R1",))
    first = engine.run([dirty])
    assert rules_of(first.findings) == ["R1"]
    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), first.findings)
    second = engine.run([dirty], baseline_path=str(bl))
    assert second.findings == [] and rules_of(second.baselined) == ["R1"]


def test_baseline_catches_new_occurrences(tmp_path):
    dirty = _write(tmp_path, "mod.py", "try:\n    x=1\nexcept:\n    pass\n")
    engine = Engine(DEFAULT_CONFIG, select=("R1",))
    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), engine.run([dirty]).findings)
    # a SECOND identical violation exceeds the grandfathered count
    (tmp_path / "mod.py").write_text(
        "try:\n    x=1\nexcept:\n    pass\n"
        "try:\n    y=2\nexcept:\n    pass\n"
    )
    result = engine.run([dirty], baseline_path=str(bl))
    assert rules_of(result.findings) == ["R1"]
    assert rules_of(result.baselined) == ["R1"]


def test_overlapping_paths_scan_each_file_once(tmp_path):
    """A dir plus a file inside it must not double findings — doubled
    occurrences would exceed their baseline budget."""
    dirty = _write(tmp_path, "pkg/mod.py",
                   "try:\n    x=1\nexcept:\n    pass\n")
    engine = Engine(DEFAULT_CONFIG, select=("R1",))
    result = engine.run([str(tmp_path / "pkg"), dirty, dirty])
    assert result.files_scanned == 1
    assert rules_of(result.findings) == ["R1"]
    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), result.findings)
    again = engine.run([str(tmp_path / "pkg"), dirty],
                       baseline_path=str(bl))
    assert again.findings == []


def test_baseline_survives_path_respelling(tmp_path, monkeypatch):
    """`moco_tpu` vs `./moco_tpu` vs absolute must fingerprint the same:
    a committed baseline can't depend on how the CI invocation spells
    the root."""
    monkeypatch.chdir(tmp_path)
    dirty = _write(tmp_path, "pkg/mod.py",
                   "try:\n    x=1\nexcept:\n    pass\n")
    engine = Engine(DEFAULT_CONFIG, select=("R1",))
    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), engine.run(["pkg"]).findings)
    for spelling in ("pkg", "./pkg", dirty, os.path.join(".", "pkg")):
        result = engine.run([spelling], baseline_path=str(bl))
        assert result.findings == [], (spelling,
                                       [f.human() for f in result.findings])


def test_committed_baseline_is_empty():
    """The repo carries NO grandfathered findings: the baseline file
    exists to exercise the mechanism, not to hide debt."""
    assert baseline_mod.load(
        os.path.join(REPO, "tools", "mocolint", "baseline.json")) == {}


# -- CLI: json schema + the tier-1 repo gate ---------------------------------

def _cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.mocolint", *args],
        capture_output=True, text=True, cwd=cwd,
    )


def test_cli_json_schema(tmp_path):
    dirty = _write(tmp_path, "mod.py", "try:\n    x=1\nexcept:\n    pass\n")
    proc = _cli(["--json", "--select", "R1", dirty])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["version"] == 1 and payload["tool"] == "mocolint"
    assert payload["files_scanned"] == 1
    (finding,) = payload["findings"]
    assert set(finding) == {"path", "line", "col", "rule", "severity",
                            "message"}
    assert finding["rule"] == "R1" and finding["line"] == 3


def test_cli_unknown_rule_is_usage_error():
    assert _cli(["--select", "R99", "moco_tpu"]).returncode == 2


@pytest.mark.parametrize("extra", [[], ["--baseline",
                                        "tools/mocolint/baseline.json"]])
def test_repo_gate_zero_unsuppressed_findings(extra):
    """THE tier-1 gate: the whole repo is clean under every rule, with
    and without the committed (empty) baseline, inside the ~5 s budget
    the single-parse engine promises."""
    t0 = time.monotonic()
    proc = _cli([*extra, "moco_tpu", "tools", "bench.py"])
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "mocolint clean" in proc.stdout
    # generous CI headroom over the observed ~1.2 s; the contract is
    # "one parse per file", not a loaded-runner microbenchmark
    assert elapsed < 20.0, f"mocolint took {elapsed:.1f}s"


# -- incremental cache (ISSUE 9 satellite) ----------------------------------


R1_BODY = "try:\n    x = 1\nexcept:\n    pass\n"


def test_cache_warm_run_replays_findings_without_parsing(tmp_path):
    cache = str(tmp_path / "cache")
    a = _write(tmp_path, "tree/a.py", R1_BODY)
    _write(tmp_path, "tree/b.py", "x = 1\n")
    eng = Engine(DEFAULT_CONFIG)
    cold = eng.run([str(tmp_path / "tree")], cache_dir=cache)
    assert cold.files_cached == 0 and cold.files_scanned == 2
    warm = Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")],
                                      cache_dir=cache)
    assert warm.files_cached == 2
    assert [(f.path, f.line, f.rule, f.message) for f in warm.findings] == \
           [(f.path, f.line, f.rule, f.message) for f in cold.findings]
    assert any(f.rule == "R1" and f.path == a for f in warm.findings)


def test_cache_invalidates_only_the_edited_file(tmp_path):
    cache = str(tmp_path / "cache")
    _write(tmp_path, "tree/a.py", R1_BODY)
    b = _write(tmp_path, "tree/b.py", "x = 1\n")
    Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")], cache_dir=cache)
    _write(tmp_path, "tree/b.py", R1_BODY)  # b now violates R1 too
    warm = Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")],
                                      cache_dir=cache)
    assert warm.files_cached == 1  # a served from cache, b re-parsed
    assert sum(1 for f in warm.findings if f.rule == "R1") == 2
    assert any(f.path == b and f.rule == "R1" for f in warm.findings)


def test_cache_cross_file_chains_recompute_on_warm_runs(tmp_path):
    """The R11 transitive boundary walk must see a NEW violation in an
    UNCHANGED file: serve/a.py (cached) imports helper.py; when helper
    grows a module-level optax import, the chain finding lands in a.py
    on the warm run — proof that finalize() is never served from cache."""
    cache = str(tmp_path / "cache")
    a = _write(tmp_path, "moco_tpu/serve/a.py", "import moco_tpu.helper\n")
    _write(tmp_path, "moco_tpu/helper.py", "import os\n")
    cold = Engine(DEFAULT_CONFIG).run([str(tmp_path / "moco_tpu")],
                                      cache_dir=cache)
    assert not any(f.rule == "R11" for f in cold.findings)
    _write(tmp_path, "moco_tpu/helper.py", "import optax\n")
    warm = Engine(DEFAULT_CONFIG).run([str(tmp_path / "moco_tpu")],
                                      cache_dir=cache)
    assert warm.files_cached == 1  # a.py unchanged, helper re-parsed
    chains = [f for f in warm.findings if f.rule == "R11" and f.path == a]
    assert chains and "optax" in chains[0].message


def test_cache_keyed_on_rule_selection(tmp_path):
    """A --select subset must not poison the full-run cache: the engine
    fingerprint folds in the active rule set."""
    cache = str(tmp_path / "cache")
    _write(tmp_path, "tree/a.py", R1_BODY)
    r = Engine(DEFAULT_CONFIG, select=("R9",)).run(
        [str(tmp_path / "tree")], cache_dir=cache)
    assert r.files_cached == 0
    full = Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")],
                                      cache_dir=cache)
    assert full.files_cached == 0  # different fingerprint: cache miss
    assert any(f.rule == "R1" for f in full.findings)


def test_cache_cold_warm_timing(tmp_path):
    """The satellite's pin: the warm path must stay cheaper than the
    cold parse+walk as the tree grows (here: 60 files of real-ish code,
    warm run serves all of them from cache and beats the cold run)."""
    cache = str(tmp_path / "cache")
    body = "import os\n" + "\n".join(
        f"def f{i}(x):\n"
        f"    y = x + {i}\n"
        f"    for j in range(10):\n"
        f"        y += j * {i}\n"
        f"    return y\n"
        for i in range(40)
    )
    for n in range(60):
        _write(tmp_path, f"tree/m{n:02d}.py", body)
    t0 = time.monotonic()
    cold = Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")],
                                      cache_dir=cache)
    cold_s = time.monotonic() - t0
    t0 = time.monotonic()
    warm = Engine(DEFAULT_CONFIG).run([str(tmp_path / "tree")],
                                      cache_dir=cache)
    warm_s = time.monotonic() - t0
    assert cold.files_cached == 0 and warm.files_cached == 60
    assert warm_s < cold_s, (
        f"warm {warm_s:.3f}s not faster than cold {cold_s:.3f}s"
    )


def test_repo_gate_warm_cache(tmp_path):
    """The tier-1 gate with the cache: cold run populates, warm run
    serves every file and stays clean — the 'gate stays ~1 s as the tree
    grows' contract."""
    cache = str(tmp_path / "cache")
    cold = _cli(["--cache", cache, "moco_tpu", "tools", "bench.py"])
    assert cold.returncode == 0, cold.stdout + cold.stderr
    t0 = time.monotonic()
    warm = _cli(["--cache", cache, "moco_tpu", "tools", "bench.py"])
    elapsed = time.monotonic() - t0
    assert warm.returncode == 0, warm.stdout + warm.stderr
    assert "cached" in warm.stdout
    assert elapsed < 10.0, f"warm gate took {elapsed:.1f}s"


# -- R13: bank artifact writes are atomic (ISSUE 16) -------------------------


def test_r13_flags_in_place_artifact_writes(tmp_path):
    """A bare np.savez / json.dump / open-for-write inside the bank
    builder reintroduces the torn-artifact window the atomic helpers
    close — a crash mid-write leaves a promotable-looking file."""
    body = """\
import json
import numpy as np


def merge(path, feats, manifest):
    np.savez(path, features=feats)              # in place: flagged
    with open(path + ".json", "w") as f:        # in place: flagged
        json.dump(manifest, f)                  # in place: flagged
"""
    findings = run_on(tmp_path, "moco_tpu/serve/bankbuild.py", body,
                      select=("R13",))
    assert rules_of(findings) == ["R13", "R13", "R13"]
    assert "temp+rename" in findings[0].message


def test_r13_atomic_helpers_and_reads_are_exempt(tmp_path):
    """The atomic_* helpers ARE the temp+rename machinery (their inner
    writes are the point); reads, default-mode opens, and undotted
    calls never trip the rule."""
    body = """\
import json
import os

import numpy as np


def atomic_write_json(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:                   # inside the helper: fine
        json.dump(obj, f)
    os.replace(tmp, path)


def _atomic_save(path, arrays):
    np.savez(path + ".tmp", **arrays)           # inside the helper: fine
    os.replace(path + ".tmp", path)


def load(path):
    with open(path) as f:                       # a read: fine
        return json.load(f)


def dump(x):
    return x


def passthrough(x):
    return dump(x)                              # undotted call: fine
"""
    assert run_on(tmp_path, "moco_tpu/serve/bankbuild.py", body,
                  select=("R13",)) == []


def test_r13_scope_is_the_bank_builder_only(tmp_path):
    """R13 guards the bank artifacts, not every npz in the repo — a
    checkpoint writer outside the builder scope stays unflagged."""
    body = """\
import numpy as np


def save(path, arrays):
    np.savez(path, **arrays)
"""
    assert run_on(tmp_path, "moco_tpu/checkpoint.py", body,
                  select=("R13",)) == []


def test_bank_build_cli_is_train_free_boundary(tmp_path):
    """The R6 boundary pins tools/bank_build.py out of the train stack:
    a bank builder that imports the training loop would drag jax + the
    optimizer into the (lint-enforced jax-free) batch lane."""
    body = """\
from moco_tpu.train import train_loop
"""
    findings = run_on(tmp_path, "tools/bank_build.py", body,
                      select=("R6",))
    assert "R6" in rules_of(findings)


# -- ISSUE 20: the sharded-ANN lint surface ----------------------------------


def test_ann_module_is_jax_free_boundary(tmp_path):
    """The ann-jax-free boundary (R6): the IVF index builder runs inside
    bank_build's batch lane and inside serve replicas — a jax import
    there would drag the train runtime into both."""
    findings = run_on(tmp_path, "moco_tpu/serve/ann.py",
                      "import jax\n", select=("R6",))
    assert "R6" in rules_of(findings)


def test_ann_module_numpy_is_fine(tmp_path):
    # numpy IS the index's substrate; only jax/flax/train are banned
    body = """\
import json
import numpy as np


def centroids(x):
    return np.zeros((4, x.shape[1]), dtype=np.float32)
"""
    assert run_on(tmp_path, "moco_tpu/serve/ann.py", body,
                  select=("R6",)) == []


def test_r13_covers_ann_index_writes(tmp_path):
    """R13's scope now includes serve/ann.py: a bare np.savez of
    ann.npz reopens the torn-artifact window next to a good bank —
    index writes must go through the atomic_* helpers, manifest last."""
    body = """\
import numpy as np


def write_index(path, centroids):
    np.savez(path, centroids=centroids)          # in place: flagged


def atomic_save_npz(path, arrays):
    import os
    np.savez(path + ".tmp", **arrays)            # inside helper: fine
    os.replace(path + ".tmp", path)
"""
    findings = run_on(tmp_path, "moco_tpu/serve/ann.py", body,
                      select=("R13",))
    assert rules_of(findings) == ["R13"]
    assert findings[0].line == 5


def test_r9_covers_ann_kmeans_determinism(tmp_path):
    """ann.py is a bit-identity module (R9): an unseeded RNG in the
    k-means init would make the 1-shard and N-shard index builds
    diverge — the byte-identical artifact contract."""
    body = """\
import numpy as np


def init(x, k):
    return x[np.random.permutation(len(x))[:k]]  # global rng: flagged
"""
    findings = run_on(tmp_path, "moco_tpu/serve/ann.py", body,
                      select=("R9",))
    assert "R9" in rules_of(findings)


def test_fleet_router_cannot_import_the_ann_module(tmp_path):
    """The router merges fan-out candidates in pure python BECAUSE the
    fleet is stdlib-only (R11): reaching into serve/ann.py would pull
    numpy into the last process standing."""
    (tmp_path / "moco_tpu" / "serve").mkdir(parents=True)
    (tmp_path / "moco_tpu" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "serve" / "__init__.py").write_text("")
    (tmp_path / "moco_tpu" / "serve" / "ann.py").write_text(
        "import numpy as np\n"
    )
    (tmp_path / "moco_tpu" / "serve" / "fleet.py").write_text(
        "from moco_tpu.serve.ann import vote\n"
    )
    found = Engine(DEFAULT_CONFIG, select=("R11",)).run(
        [str(tmp_path / "moco_tpu")]).findings
    assert any(f.path.endswith("fleet.py") and "numpy" in f.message
               for f in found), [f.human() for f in found]
