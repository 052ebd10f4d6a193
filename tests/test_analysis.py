"""Static invariant analyzer (paddle_tpu/analysis/; docs/analysis.md).

Every rule is proven IN REVERSE against the seeded-violation fixtures
(analysis/fixtures/) — the analytic-gate discipline: a detector that
never fires is no detector — plus clean controls, the committed-tree
rc-0 acceptance gate, baseline round-trip, JSON schema, and the
JIT_ROOTS drift test (every registered root still names a real function
and real parameters).

The retrace rules also get a RUNTIME confirmation: the statically
flagged fixture shape really retraces per value under jit, its
data-fed twin doesn't (testing/trace.forbid_retrace both ways).

No jax import at module level — the analyzer itself must never need
one; only the runtime-confirmation test pays it.  The real-subprocess
CLI drive rides the slow lane (the in-process calls here cover the
same code at fast-lane cost).
"""

import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.analysis import baseline as baseline_mod
from paddle_tpu.analysis import callgraph, locks, purity, retrace
from paddle_tpu.analysis.__main__ import main as analysis_main
from paddle_tpu.analysis.roots import Root, TRACE_TIME_FLAGS, all_roots

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_JIT_FIXTURE = "paddle_tpu.analysis.fixtures.jit_impure"
_RETRACE_FIXTURE = "paddle_tpu.analysis.fixtures.retrace_hazards"
_LOCK_FIXTURE = "paddle_tpu/analysis/fixtures/lock_disorder.py"


@pytest.fixture(scope="module")
def project():
    """ONE parsed AST index shared by every test here (the parse is the
    expensive part; the passes are milliseconds)."""
    return callgraph.Project(_ROOT)


def _rules(findings):
    return {f.rule for f in findings}


def _keys(findings):
    return {f.key for f in findings}


# ------------------------------------------------------- reverse gates

def test_jit_purity_catches_every_seeded_violation(project):
    found = purity.run(project, [Root("fx", f"{_JIT_FIXTURE}:bad_step")])
    assert "jit-forbidden-call" in _rules(found)
    assert "jit-flags-read" in _rules(found)
    hit_targets = {f.key.rsplit(":", 1)[1] for f in found
                   if f.rule == "jit-forbidden-call"}
    # one per forbidden namespace, incl. the transitive helper reach
    assert {"time.perf_counter", "random.random",
            "threading.get_ident",
            "paddle_tpu.resilience.faults.hit",
            "paddle_tpu.serving.metrics.ServingMetrics",
            "paddle_tpu.obs.trace.enable",
            "paddle_tpu.utils.logging.get_logger",
            "time.sleep"} <= hit_targets
    # the transitive one is attributed to the helper, with the chain
    transitive = [f for f in found if f.key.endswith("time.sleep")]
    assert transitive and len(transitive[0].chain) == 2
    # the non-trace-time FLAGS read names the flag
    assert any(f.key.endswith(":serving_gen_slots") for f in found
               if f.rule == "jit-flags-read")


def test_jit_purity_clean_control(project):
    found = purity.run(project,
                       [Root("fx", f"{_JIT_FIXTURE}:clean_step")])
    assert found == []


def test_jit_purity_visits_every_qualname_sharing_variant(project):
    """Regression (review finding): both fixture `variant_step` defs
    share one qualname and only the SECOND is impure — the walk must
    not dedupe variants away (the DecodeEngine _step_fn situation)."""
    found = purity.run(project,
                       [Root("fx", f"{_JIT_FIXTURE}:variant_step")])
    assert any(f.key.endswith("time.sleep") for f in found), found


def test_retrace_catches_every_seeded_violation(project):
    found = retrace.run(project,
                        [Root("fx", f"{_RETRACE_FIXTURE}:hazard_step")])
    assert {"retrace-data-branch", "retrace-host-sync",
            "retrace-shape-key", "retrace-unordered-iter"} \
        <= _rules(found)
    details = _keys(found)
    assert any("if:positions" in k for k in details)        # if on data
    assert any("while:lengths" in k for k in details)       # while on data
    assert any("int:" in k for k in details)                # int(tracer)
    assert any("item()" in k for k in details)              # .item()
    assert any("fstring:" in k for k in details)            # shape key
    # member-side membership is a VALUE comparison (review finding):
    # `tokens[1] in (0, 1)` must flag (the clean control pins that
    # container-side `"ks" in params` still launders)
    assert any("if:tokens" in k for k in details), details
    # the transitive hazard is found INSIDE the helper via taint
    assert any("_hazard_helper" in k for k in details), details


def test_retrace_clean_control(project):
    found = retrace.run(project,
                        [Root("fx", f"{_RETRACE_FIXTURE}:clean_step")])
    assert found == []


def test_missing_root_is_a_finding_in_every_rooted_pass(project):
    """A drifted root ref must never make a pass vacuously green
    (review finding): purity AND retrace both report it."""
    ghost = [Root("ghost", "no.such.module:nope")]
    assert {f.rule for f in purity.run(project, ghost)} \
        == {"jit-root-missing"}
    assert {f.rule for f in retrace.run(project, ghost)} \
        == {"retrace-root-missing"}


def test_malformed_root_arg_is_a_usage_error(capsys):
    """--root without MOD:QUALNAME shape -> documented rc 2, not a
    traceback (review finding)."""
    assert analysis_main(["--check", "retrace", "--root", "foo",
                          *_FIXTURE_SCAN]) == 2


def test_stale_detection_is_scoped_to_the_selected_check(tmp_path,
                                                         capsys):
    """Regression (review finding): a still-valid LOCKS baseline entry
    must not read as stale under `--check jit --strict` — staleness is
    judged only against the passes that ran."""
    bl = str(tmp_path / "bl.json")
    baseline_mod.dump(bl, {
        "locks:lock-mixed-guard:some.Class.attr": "other pass's entry"})
    rc = analysis_main(["--check", "jit", "--strict", "--baseline", bl,
                        "--root", f"{_JIT_FIXTURE}:clean_step",
                        *_FIXTURE_SCAN])
    assert rc == 0, "locks entry misread as stale by a jit-only run"
    # ...but the SAME entry is honestly stale for a locks run (scanned
    # against a lock-free file, so rc 1 comes from staleness alone)
    rc = analysis_main(["--check", "locks", "--strict", "--baseline",
                        bl, "--lock-paths",
                        "paddle_tpu/analysis/fixtures/__init__.py",
                        *_FIXTURE_SCAN])
    assert rc == 1


def test_locks_catch_cycle_reacquire_and_mixed_guard(project):
    found = locks.run(project, [_LOCK_FIXTURE])
    assert {"lock-order-cycle", "lock-reacquire", "lock-mixed-guard"} \
        <= _rules(found)
    cyc = [f for f in found if f.rule == "lock-order-cycle"]
    keys = {f.key for f in cyc}
    assert any("LockA._lock" in k and "LockB._lock" in k for k in keys)
    # regression (review finding): the acquisition hidden behind the
    # a<->b CALL cycle still produces the _lh -> _la edge even though
    # the driver forces the memo-poisoning computation order first —
    # the CycleHolder ordering cycle must be reported
    assert any("CycleInner._la" in k and "CycleHolderH._lh" in k
               for k in keys), keys
    assert cyc[0].chain            # provenance: the edges
    reacq = {f.key for f in found if f.rule == "lock-reacquire"}
    assert any("Reacquirer._lock" in k for k in reacq)
    mixed = [f for f in found if f.rule == "lock-mixed-guard"]
    assert any("MixedGuard.count" in f.key for f in mixed)
    # the *_locked-suffix helper counted as guarded, racy_inc did not
    assert "racy_inc" in mixed[0].message
    assert "_bump_locked" not in mixed[0].message


def test_locks_real_scan_set_is_not_polluted_by_fixtures(project):
    """The committed gate never sees the seeded lock violations: the
    default scan set excludes analysis/fixtures entirely."""
    found = locks.run(project)
    assert not any("lock_disorder" in f.path for f in found)


# ------------------------------------------- the gate on the real tree

@pytest.mark.slow       # whole-tree parse x all three passes: the
#                         heavy run rides the slow lane; the subprocess
#                         CLI test below gates the same thing
def test_clean_tree_exits_zero():
    """Acceptance: `python -m paddle_tpu.analysis --check all` exits 0
    on HEAD — every finding fixed or baselined with a reason."""
    assert analysis_main(["--check", "all"]) == 0


_FIXTURE_SCAN = ["--scan-package",
                 os.path.join("paddle_tpu", "analysis", "fixtures")]


def test_each_pass_exits_nonzero_on_its_fixture(capsys):
    """Acceptance: EACH of the three passes exits non-zero through the
    real entry point on its seeded violation fixture.  The scan is
    restricted to the fixtures subtree — same passes, same rc path,
    ~30 ms instead of a whole-tree parse per call."""
    assert analysis_main(["--check", "retrace", "--no-baseline",
                          "--root", f"{_RETRACE_FIXTURE}:hazard_step",
                          *_FIXTURE_SCAN]) == 1
    assert analysis_main(["--check", "locks", "--no-baseline",
                          "--lock-paths", _LOCK_FIXTURE,
                          *_FIXTURE_SCAN]) == 1
    capsys.readouterr()                       # drop the text reports
    # jit last, --json: doubles as the output-schema pin
    rc = analysis_main(["--check", "jit", "--no-baseline", "--json",
                        "--root", f"{_JIT_FIXTURE}:bad_step",
                        *_FIXTURE_SCAN])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == 1 and doc["check"] == "jit"
    assert doc["new"] == len(doc["findings"]) > 0
    assert doc["baselined"] == 0 and doc["stale_baseline_keys"] == []
    f0 = doc["findings"][0]
    assert {"check", "rule", "key", "path", "line", "func", "message",
            "chain", "baselined", "reason"} <= set(f0)
    assert doc["roots"] == [f"{_JIT_FIXTURE}:bad_step"]
    assert isinstance(doc["counts"], dict) and doc["counts"]


# ------------------------------------------------- baseline round-trip

def test_baseline_roundtrip_and_validation(tmp_path):
    p = str(tmp_path / "bl.json")
    entries = {"locks:lock-mixed-guard:a.B.c": "single-threaded by X",
               "jit:jit-forbidden-call:m:f:time.sleep": "trace-time"}
    baseline_mod.dump(p, entries)
    assert baseline_mod.load(p) == entries
    # empty reason rejected
    doc = json.load(open(p))
    doc["entries"][0]["reason"] = "  "
    json.dump(doc, open(p, "w"))
    with pytest.raises(ValueError, match="non-empty reason"):
        baseline_mod.load(p)
    # duplicate keys rejected
    doc["entries"][0]["reason"] = "ok"
    doc["entries"].append(dict(doc["entries"][0]))
    json.dump(doc, open(p, "w"))
    with pytest.raises(ValueError, match="duplicate"):
        baseline_mod.load(p)
    # wrong schema rejected
    json.dump({"schema": 99, "entries": []}, open(p, "w"))
    with pytest.raises(ValueError, match="schema"):
        baseline_mod.load(p)


def test_baseline_apply_marks_and_reports_stale():
    f1 = baseline_mod.Finding("jit", "r", "k1", "p", 1, "f", "m")
    f2 = baseline_mod.Finding("jit", "r", "k2", "p", 2, "f", "m")
    new, stale = baseline_mod.apply([f1, f2],
                                    {"k1": "why", "gone": "old"})
    assert new == [f2]
    assert f1.baselined and f1.reason == "why" and not f2.baselined
    assert stale == ["gone"]


def test_committed_baseline_loads_and_is_justified():
    entries = baseline_mod.load(os.path.join(
        _ROOT, "paddle_tpu", "analysis", "baseline.json"))
    for key, reason in entries.items():
        assert len(reason) > 20, (key, "a real reason, not a stub")


# ------------------------------------------------------- registry drift

def test_every_root_resolves_with_real_static_args(project):
    """A rename cannot drop a jitted step out of the analysis: every
    root ref must resolve in the AST index with its static_args naming
    real parameters."""
    for root in all_roots():
        infos = project.function(root.ref)
        assert infos, f"root {root.name}: {root.ref} not found in AST"
        params = set(infos[0].params())
        missing = set(root.static_args) - params
        assert not missing, (
            f"root {root.name}: static_args {sorted(missing)} are not "
            f"parameters of {root.ref} (has {sorted(params)})")


def test_trace_time_flags_are_real_flags():
    import dataclasses
    from paddle_tpu.utils.flags import Flags
    fields = {f.name for f in dataclasses.fields(Flags)}
    assert TRACE_TIME_FLAGS <= fields


# ------------------------------------ runtime confirmation (jax lane)

def test_flagged_shape_really_retraces_and_data_twin_does_not():
    """The static retrace-data-branch rule describes a REAL retrace:
    fixtures' branchy_step (flagged) compiles one program per value of
    its branched arg, while masked_step (the data-fed fix) warms in one
    trace and never retraces — forbid_retrace pins both directions."""
    import jax
    import numpy as np
    from paddle_tpu.analysis.fixtures import retrace_hazards as fx
    from paddle_tpu.testing import counting, forbid_retrace

    x = np.ones(4, np.float32)

    bad = counting(fx.branchy_step)
    jbad = jax.jit(bad, static_argnums=(1,))
    jbad(x, 1)                                   # warm-up trace
    with pytest.raises(AssertionError, match="traced"):
        with forbid_retrace(bad, what="branch-on-data step"):
            jbad(x, 2)                           # new value -> new trace
            jbad(x, 3)

    good = counting(fx.masked_step)
    jgood = jax.jit(good)
    jgood(x, np.float32(1.0))                    # warm-up trace
    assert good.trace_count == 1
    with forbid_retrace(good, what="data-masked step"):
        for keep in (0.0, 1.0, 0.0):
            jgood(x, np.float32(keep))           # variation as data
    # and the two agree where the branch says they should
    np.testing.assert_allclose(
        np.asarray(jbad(x, 1)),
        np.asarray(jgood(x, np.float32(1.0))))


def test_forbid_retrace_accepts_engines_and_callables():
    from paddle_tpu.testing import forbid_retrace

    class FakeEngine:
        step_trace_count = 0
    eng = FakeEngine()
    box = [0]
    with forbid_retrace(eng, lambda: box[0], what="fake"):
        pass                                     # nothing moved: fine
    with pytest.raises(AssertionError, match="fake"):
        with forbid_retrace(eng, lambda: box[0], what="fake"):
            box[0] += 1
    with pytest.raises(TypeError):
        with forbid_retrace():
            pass


# ------------------------------------------------ real CLI (slow lane)

@pytest.mark.slow
def test_cli_subprocess_rc_strict_and_write_baseline(tmp_path):
    """The real command line end to end: rc 0 on HEAD, rc 1 on the
    seeded fixture, --write-baseline round-trips into a passing gate,
    and --strict turns a stale entry into rc 1."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*args):
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis", *args],
            cwd=_ROOT, env=env, capture_output=True, text=True,
            timeout=300)

    assert run("--check", "all").returncode == 0
    r = run("--check", "retrace", "--no-baseline",
            "--root", f"{_RETRACE_FIXTURE}:hazard_step")
    assert r.returncode == 1 and "retrace-data-branch" in r.stdout
    # bootstrap a baseline covering the fixture -> gate passes with it
    bl = str(tmp_path / "fixture_bl.json")
    r = run("--check", "retrace", "--root",
            f"{_RETRACE_FIXTURE}:hazard_step", "--write-baseline", bl)
    assert r.returncode == 0
    doc = json.load(open(bl))
    for e in doc["entries"]:
        e["reason"] = "fixture: seeded on purpose"
    json.dump(doc, open(bl, "w"))
    r = run("--check", "retrace", "--baseline", bl,
            "--root", f"{_RETRACE_FIXTURE}:hazard_step")
    assert r.returncode == 0, r.stdout + r.stderr
    # a stale IN-SCOPE entry: warns by default, fails under --strict
    # (an out-of-scope prefix would be ignored — see the scoped-stale
    # test above)
    doc["entries"].append({"key": "retrace:gone:x:y:z",
                           "reason": "stale"})
    json.dump(doc, open(bl, "w"))
    r = run("--check", "retrace", "--baseline", bl,
            "--root", f"{_RETRACE_FIXTURE}:hazard_step")
    assert r.returncode == 0 and "stale" in r.stderr
    r = run("--check", "retrace", "--baseline", bl, "--strict",
            "--root", f"{_RETRACE_FIXTURE}:hazard_step")
    assert r.returncode == 1
