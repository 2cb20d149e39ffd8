"""hvdlint + lockorder: the static-analysis tier-1 gate.

Four layers (docs/static-analysis.md):

1. **The gate** — the whole ``horovod_tpu`` package lints clean against
   the checked-in baseline (``.hvdlint-baseline.json``, ≤ 10 entries).
   Any NEW finding fails tier-1, which is what keeps the rounds-7..9
   fault-tolerance/tracing invariants true as the codebase grows.
2. **Rule proofs** — per-rule bad/good fixtures under
   ``tests/lint_fixtures/``: every rule demonstrably fires on its bad
   snippet and stays silent on the good one.
3. **Framework contracts** — suppression pragmas, baseline round-trip,
   reporters, CLI exit codes.
4. **Lock-order detector** — a seeded A->B/B->A inversion must be
   reported as a cycle with both acquisition stacks; a real 3-rank run
   under ``HOROVOD_LOCKCHECK=1`` must produce valid, acyclic
   ``lockgraph.json`` artifacts with real edges on the coordinator.
"""

import json
import os
import sys
import threading

import pytest

from horovod_tpu.analysis import (
    baseline_key,
    get_rule,
    lint_source,
    load_baseline,
    render_json,
    render_text,
    run_lint,
    write_baseline,
)
from horovod_tpu.analysis.lockorder import LockGraph, TrackedLock, make_lock
from horovod_tpu.analysis.rules import ALL_RULES
from mp_harness import LAUNCH_LIMIT, child_env, run_cmd, run_ranks

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
PKG = os.path.join(REPO, "horovod_tpu")
FIXTURES = os.path.join(HERE, "lint_fixtures")
BASELINE = os.path.join(REPO, ".hvdlint-baseline.json")
MAX_BASELINE_ENTRIES = 10


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


# ---------------------------------------------------------------------------
# 1. The gate


def test_package_lints_clean_against_baseline():
    """THE tier-1 gate: zero non-baselined findings over the package."""
    baseline = load_baseline(BASELINE)
    assert len(baseline) <= MAX_BASELINE_ENTRIES, (
        f"baseline grew to {len(baseline)} entries (max "
        f"{MAX_BASELINE_ENTRIES}); fix findings instead of grandfathering "
        "them")
    result = run_lint([PKG], root=REPO, baseline=baseline)
    assert not result.parse_errors, result.parse_errors
    assert result.files_scanned > 50, "package scan looks truncated"
    assert not result.findings, (
        "NEW hvdlint findings (fix them, add a justified inline "
        "suppression, or — last resort — baseline them):\n"
        + "\n".join(f.render() for f in result.findings))


def test_baseline_entries_still_exist():
    """A baseline entry whose finding no longer fires is stale — shrink
    the file (the workflow's ratchet direction)."""
    baseline = load_baseline(BASELINE)
    result = run_lint([PKG], root=REPO, baseline=baseline)
    live = {baseline_key(f.as_dict()) for f in result.baselined}
    stale = [e for e in baseline if baseline_key(e) not in live]
    assert not stale, f"stale baseline entries (remove them): {stale}"


# ---------------------------------------------------------------------------
# 2. Per-rule fixture proofs


_RELPATHS = {"HVD002": "horovod_tpu/controller/_fixture.py",
             # HVD008 is scoped to the protocol surface; the fixture is
             # linted AS the real wire module path.
             "HVD008": "horovod_tpu/common/wire.py",
             "HVD009": "horovod_tpu/controller/_epochs.py",
             # The cross-language rules are scoped to the two seam
             # modules; their fixtures lint AS those paths (the real
             # C++ sources are still read from the repo).
             "HVD010": "horovod_tpu/core/bindings.py",
             "HVD011": "horovod_tpu/metrics/__init__.py"}


@pytest.mark.parametrize("code", [cls.code for cls in ALL_RULES])
def test_rule_fires_on_bad_fixture(code):
    src = _fixture(f"{code.lower()}_bad.py")
    relpath = _RELPATHS.get(code, f"horovod_tpu/{code.lower()}_fixture.py")
    findings = lint_source(src, relpath, rules=[get_rule(code)()])
    assert findings, f"{code} failed to fire on its bad fixture"
    assert all(f.rule == code for f in findings)


@pytest.mark.parametrize("code", [cls.code for cls in ALL_RULES])
def test_rule_silent_on_good_fixture(code):
    src = _fixture(f"{code.lower()}_good.py")
    relpath = _RELPATHS.get(code, f"horovod_tpu/{code.lower()}_fixture.py")
    findings = lint_source(src, relpath, rules=[get_rule(code)()])
    assert not findings, (
        f"{code} false positive on its good fixture:\n"
        + "\n".join(f.render() for f in findings))


def test_hvd002_is_scoped_to_controller_paths():
    """The same unordered walk outside controller/ is not a finding."""
    src = _fixture("hvd002_bad.py")
    findings = lint_source(src, "horovod_tpu/utils/elsewhere.py",
                           rules=[get_rule("HVD002")()])
    assert not findings


def test_hvd002_all_paths_mode_for_the_aux_scan():
    src = _fixture("hvd002_bad.py")
    findings = lint_source(src, "tests/anywhere.py",
                           rules=[get_rule("HVD002")(all_paths=True)])
    assert findings and all(f.rule == "HVD002" for f in findings)


# ---------------------------------------------------------------------------
# 2b. Interprocedural HVD001 (call graph + rank taint, ISSUE 8)


def test_interprocedural_hvd001_catches_two_calls_deep():
    """The acceptance fixture: the collective sits two helper calls
    below the rank conditional; the upgraded rule must flag the call
    site under the conditional and name the chain down to the
    collective."""
    src = _fixture("hvd001_interproc_bad.py")
    findings = lint_source(src, "horovod_tpu/x.py",
                           rules=[get_rule("HVD001")()])
    assert len(findings) == 2, "\n".join(f.render() for f in findings)
    by_msg = sorted(f.message for f in findings)
    assert "warm_up -> _sync -> barrier" in by_msg[1]
    assert "_sync -> barrier" in by_msg[0]


def test_lexical_hvd001_misses_interprocedural_fixture():
    """Pin of the round-10 rule's blindness: the SAME fixture produces
    zero findings for the lexical-only mode — the regression this PR
    closes, kept visible."""
    src = _fixture("hvd001_interproc_bad.py")
    findings = lint_source(
        src, "horovod_tpu/x.py",
        rules=[get_rule("HVD001")(interprocedural=False)])
    assert findings == []


def test_interprocedural_hvd001_rank_taint_reaches_renamed_test():
    """``is_root = local_rank == 0; if is_root: _sync()`` — the taint
    pass marks is_root rank-derived, so the conditional counts."""
    src = _fixture("hvd001_interproc_bad.py")
    findings = lint_source(src, "horovod_tpu/x.py",
                           rules=[get_rule("HVD001")()])
    lines = {f.line for f in findings}
    tainted_call_line = src.splitlines().index(
        "        _sync()                  # one call deep, renamed test: "
        "HVD001") + 1
    assert tainted_call_line in lines


def test_interprocedural_hvd001_respects_suppressed_collectives():
    """A collective already justified inline (subgroup == conditional)
    must not re-flag its callers through the closure."""
    src = ("def cross_ring():\n"
           "    ring.allreduce_(buf)  # hvdlint: disable=HVD001 subgroup\n"
           "\n"
           "def maybe(rank):\n"
           "    if rank == 0:\n"
           "        cross_ring()\n")
    findings = lint_source(src, "horovod_tpu/x.py",
                           rules=[get_rule("HVD001")()])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_hvd008_names_missing_and_drifted_branches():
    src = _fixture("hvd008_bad.py")
    findings = lint_source(src, "horovod_tpu/common/wire.py",
                           rules=[get_rule("HVD008")()])
    messages = "\n".join(f.message for f in findings)
    assert "missing transition" in messages
    assert "'reshape'" in messages
    assert "handler drift" in messages and "sneaky_dispatch" in messages


def test_hvd009_is_scoped_to_the_protocol_surface():
    src = _fixture("hvd009_bad.py")
    findings = lint_source(src, "horovod_tpu/run/launch.py",
                           rules=[get_rule("HVD009")()])
    assert findings == []  # restart/training epochs are out of scope


def test_hvd007_counts_duplicates_and_bad_names():
    findings = lint_source(_fixture("hvd007_bad.py"),
                           "horovod_tpu/x.py", rules=[get_rule("HVD007")()])
    messages = "\n".join(f.message for f in findings)
    assert "requests_total" in messages        # missing prefix
    assert "hvd_CamelCase" in messages         # not snake_case
    assert "more than one call site" in messages  # duplicate owner
    assert len(findings) == 3


# ---------------------------------------------------------------------------
# 3. Framework contracts


def test_suppression_comment_silences_findings():
    findings = lint_source(_fixture("suppressed.py"), "horovod_tpu/s.py")
    assert not findings, "\n".join(f.render() for f in findings)


def test_suppression_is_rule_specific():
    src = ("import os, time\n"
           "t = os.environ.get('X')  # hvdlint: disable=HVD004\n")
    findings = lint_source(src, "horovod_tpu/s.py")
    # HVD004 pragma does NOT cover the HVD003 (env read at import time
    # also trips HVD006) findings on that line.
    assert {f.rule for f in findings} == {"HVD003", "HVD006"}


def test_baseline_roundtrip(tmp_path):
    bad = os.path.join(FIXTURES, "hvd004_bad.py")
    first = run_lint([bad], root=FIXTURES)
    assert first.findings
    path = str(tmp_path / "baseline.json")
    write_baseline(path, first.findings)
    entries = load_baseline(path)
    assert len(entries) == len(first.findings)
    # With the baseline applied the same findings are grandfathered...
    second = run_lint([bad], root=FIXTURES, baseline=entries)
    assert not second.findings
    assert len(second.baselined) == len(first.findings)
    # ...and a NEW finding (different file) still fails.
    third = run_lint([bad, os.path.join(FIXTURES, "hvd005_bad.py")],
                     root=FIXTURES, baseline=entries)
    assert third.findings and all(f.rule == "HVD005"
                                  for f in third.findings)


def test_baseline_is_a_multiset_not_a_blanket(tmp_path):
    """One grandfathered entry absorbs exactly ONE finding: adding a
    second violation of the same rule to the same file (identical
    file-invariant message) must still be reported as new."""
    one = "import time\n\ndef f():\n    return time.time()\n"
    entries = [f.as_dict() for f in lint_source(one, "x.py")]
    assert len(entries) == 1
    two = one + "\n\ndef g():\n    return time.time()\n"
    result_findings = []
    # Reuse run_lint's budget semantics through lint files on disk.
    p = tmp_path / "x.py"
    p.write_text(two)
    result = run_lint([str(p)], root=str(tmp_path), baseline=entries)
    assert len(result.baselined) == 1
    assert len(result.findings) == 1, (
        "the second time.time() hid behind the first one's baseline "
        f"entry: {result_findings}")


def test_hvd003_flags_env_read_inside_store_target():
    """A value read used as a subscript KEY of an assignment target is
    still a read: ``x[os.environ['K']] = 1`` must fire."""
    src = ("import os\n"
           "def f(x):\n"
           "    x[os.environ['K']] = 1\n")
    findings = lint_source(src, "horovod_tpu/x.py",
                           rules=[get_rule("HVD003")()])
    assert len(findings) == 1 and findings[0].rule == "HVD003"


def test_baseline_survives_line_drift(tmp_path):
    """Baseline matching keys on (rule, path, message), not line numbers:
    prepending code to the file must not resurrect grandfathered
    findings."""
    src = _fixture("hvd004_bad.py")
    findings = lint_source(src, "x.py")
    entries = [f.as_dict() for f in findings]
    drifted = "# a new comment line\nVERSION = 3\n" + src
    shifted = lint_source(drifted, "x.py")
    assert [f.line for f in shifted] != [f.line for f in findings]
    keys = {baseline_key(e) for e in entries}
    assert all(baseline_key(f.as_dict()) in keys for f in shifted)


def test_reporters_render(tmp_path):
    result = run_lint([os.path.join(FIXTURES, "hvd004_bad.py")],
                      root=FIXTURES)
    text = render_text(result)
    assert "HVD004" in text and "finding(s)" in text
    payload = json.loads(render_json(result))
    assert payload["findings"] and payload["findings"][0]["rule"] == "HVD004"
    assert payload["files_scanned"] == 1


def test_cli_json_and_exit_codes(tmp_path):
    """The CLI contract the acceptance criteria name: ``python -m
    horovod_tpu.tools.lint --format json --baseline ...`` — exit 1 on a
    dirty tree, 0 once the findings are baselined."""
    bad = tmp_path / "pkgdir" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(_fixture("hvd005_bad.py"))
    env = child_env()
    base = [sys.executable, "-m", "horovod_tpu.tools.lint",
            str(bad.parent), "--format", "json"]
    res = run_cmd(base + ["--baseline", "none"], timeout=180, env=env)
    assert res.returncode == 1, res.stdout + res.stderr
    payload = json.loads(res.stdout)
    assert {f["rule"] for f in payload["findings"]} == {"HVD005"}
    # Grandfather them; the same invocation now exits 0.
    bl = str(tmp_path / "bl.json")
    res = run_cmd(
        base + ["--write-baseline", "--baseline", bl],
        timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    res = run_cmd(base + ["--baseline", bl], timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr


def test_fix_autofixes_mechanical_rules_idempotently(tmp_path):
    """--fix satellite: HVD002 gets its sorted() wrap, HVD005 its
    name=/daemon= kwargs; a second --fix changes NOTHING (idempotence:
    --fix twice == once), and the fixed files lint clean."""
    pkg = tmp_path / "controller"
    pkg.mkdir()
    f2 = pkg / "walks.py"
    f2.write_text(_fixture("hvd002_bad.py"))
    f5 = pkg / "threads.py"
    f5.write_text(_fixture("hvd005_bad.py"))
    env = child_env()
    cmd = [sys.executable, "-m", "horovod_tpu.tools.lint", str(pkg),
           "--fix", "--select", "HVD002,HVD005", "--baseline", "none"]
    res = run_cmd(cmd, timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "applied" in res.stdout
    once = f2.read_text(), f5.read_text()
    assert "sorted(ticks.items())" in once[0]
    assert 'name="hvd-worker"' in once[1] and "daemon=True" in once[1]
    res = run_cmd(cmd, timeout=180, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "applied 0 fix(es)" in res.stdout
    assert (f2.read_text(), f5.read_text()) == once  # twice == once
    from horovod_tpu.analysis.rules import get_rule as _gr

    assert not lint_source(once[0], "horovod_tpu/controller/walks.py",
                           rules=[_gr("HVD002")()])
    assert not lint_source(once[1], "horovod_tpu/threads.py",
                           rules=[_gr("HVD005")()])


def test_fix_leaves_suppressed_sites_alone(tmp_path):
    from horovod_tpu.analysis.autofix import fix_source

    src = ("def f(d, wire):\n"
           "    for k, v in d.items():  # hvdlint: disable=HVD002 why\n"
           "        wire.send((k, v))\n")
    fixed, n = fix_source(src, "horovod_tpu/controller/x.py")
    assert n == 0 and fixed == src


def test_fix_handles_trailing_comma_and_stays_parseable():
    """A multi-line Thread(...) that already ends with a trailing comma
    must not grow a second one — and any fix whose output does not
    parse is refused outright rather than written to disk."""
    import ast

    from horovod_tpu.analysis.autofix import fix_source

    src = ("import threading\n"
           "t = threading.Thread(\n"
           "    target=print,\n"
           ")\n")
    fixed, n = fix_source(src, "horovod_tpu/x.py")
    assert n == 1
    ast.parse(fixed)  # the corruption mode: ',\n, name=...' SyntaxError
    assert 'name="hvd-worker"' in fixed and "daemon=True" in fixed


def test_fix_respects_select():
    from horovod_tpu.analysis.autofix import fix_source

    src = ("import threading\n"
           "def f(d, t):\n"
           "    for k in d.items():\n"
           "        threading.Thread(target=print).start()\n")
    fixed, n = fix_source(src, "horovod_tpu/controller/x.py",
                          select=["HVD002"])
    assert n == 1
    assert "sorted(d.items())" in fixed
    assert "daemon" not in fixed  # HVD005 not selected: untouched


# ---------------------------------------------------------------------------
# 3b. Aux coverage: tests/ + examples/ under the scoped rule-set


AUX_BASELINE = os.path.join(REPO, ".hvdlint-aux-baseline.json")


def _aux_scan(baseline):
    from horovod_tpu.analysis.rules import aux_rules

    return run_lint([os.path.join(REPO, "tests"),
                     os.path.join(REPO, "examples")],
                    rules=aux_rules(), root=REPO, baseline=baseline,
                    exclude_dirs=("__pycache__", "lint_fixtures"))


def test_aux_scan_tests_and_examples_clean_against_baseline():
    """New test/example code can't reintroduce unordered-dict (HVD002,
    unscoped — mp scenario bodies run on every rank), anonymous-thread
    (HVD005), or import-time-side-effect (HVD006) bugs: pre-existing
    findings are grandfathered in .hvdlint-aux-baseline.json (48
    entries at introduction, a ratchet — shrink it, never grow it)."""
    baseline = load_baseline(AUX_BASELINE)
    result = _aux_scan(baseline)
    assert not result.parse_errors, result.parse_errors
    assert result.files_scanned > 80, "aux scan looks truncated"
    assert not result.findings, (
        "NEW aux findings in tests/ or examples/ (fix them or suppress "
        "with a rationale — do not grow the aux baseline):\n"
        + "\n".join(f.render() for f in result.findings))


def test_aux_baseline_entries_still_exist():
    baseline = load_baseline(AUX_BASELINE)
    result = _aux_scan(baseline)
    live = {baseline_key(f.as_dict()) for f in result.baselined}
    stale = [e for e in baseline if baseline_key(e) not in live]
    assert not stale, f"stale aux baseline entries (remove): {stale}"


def test_cli_refuses_partial_rewrite_of_default_baseline(tmp_path):
    """--write-baseline on the DEFAULT baseline from a partial scan
    (--select / explicit paths) would silently drop out-of-scope
    entries; the CLI must refuse (exit 2, usage error) and leave the
    checked-in file untouched."""
    before = open(BASELINE).read()
    env = child_env()
    res = run_cmd(
        [sys.executable, "-m", "horovod_tpu.tools.lint",
         "--select", "HVD004", "--write-baseline"],
        timeout=180, env=env)
    assert res.returncode == 2, res.stdout + res.stderr
    assert "full default scan" in res.stderr
    assert open(BASELINE).read() == before


# ---------------------------------------------------------------------------
# 4. Lock-order detector


def test_tracked_lock_is_a_lock():
    g = LockGraph()
    lock = TrackedLock("t.a", graph_=g)
    with lock:
        assert lock.locked()
    assert not lock.locked()
    assert lock.acquire(blocking=False)
    lock.release()
    # A failed try-acquire records nothing and needs no release.
    holder = TrackedLock("t.b", graph_=g)
    holder.acquire()
    assert not (holder._inner.acquire(blocking=False))
    holder.release()


def test_seeded_lock_inversion_reports_cycle_with_both_stacks():
    """The acceptance-criteria unit: acquire A->B on one code path and
    B->A on another; the detector must report the cycle and attach the
    acquisition stacks of BOTH edges."""
    g = LockGraph()
    a = TrackedLock("seed.a", graph_=g)
    b = TrackedLock("seed.b", graph_=g)

    def path_one():     # A then B
        with a:
            with b:
                pass

    def path_two():     # B then A — the inversion
        with b:
            with a:
                pass

    t1 = threading.Thread(target=path_one, name="inv-1", daemon=True)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=path_two, name="inv-2", daemon=True)
    t2.start()
    t2.join()

    cycles = g.cycles()
    assert cycles, "inversion not detected"
    assert sorted(cycles[0][:-1]) == ["seed.a", "seed.b"]
    report = g.report()
    assert not report["acyclic"]
    (cyc,) = report["cycles"]
    assert len(cyc["edges"]) == 2
    for edge in cyc["edges"]:
        # Both stacks per edge: where the held lock was taken and where
        # the second acquisition happened — the actionable part.
        assert edge["stack_held"], edge
        assert edge["stack_acquired"], edge
        assert any("path_one" in fr or "path_two" in fr
                   for fr in edge["stack_acquired"])
    assert {cyc["edges"][0]["thread"], cyc["edges"][1]["thread"]} == \
        {"inv-1", "inv-2"}


def test_no_false_cycle_on_consistent_order():
    g = LockGraph()
    a = TrackedLock("ok.a", graph_=g)
    b = TrackedLock("ok.b", graph_=g)
    for _ in range(3):
        with a:
            with b:
                pass
    assert g.cycles() == []
    assert g.report()["acyclic"]
    assert g.edges()[("ok.a", "ok.b")]["count"] == 3


def test_same_name_reacquisition_is_not_an_edge():
    """Many lock instances share one graph node (e.g. every metric's
    child lock); nesting two of them must not fabricate a self-cycle."""
    g = LockGraph()
    a1 = TrackedLock("m.metric", graph_=g)
    a2 = TrackedLock("m.metric", graph_=g)
    with a1:
        with a2:
            pass
    assert g.edges() == {}


def test_make_lock_gated_by_env(monkeypatch):
    from horovod_tpu.analysis import lockorder

    monkeypatch.delenv("HOROVOD_LOCKCHECK", raising=False)
    monkeypatch.setattr(lockorder, "_enabled", None)
    assert isinstance(make_lock("x"), type(threading.Lock()))
    monkeypatch.setenv("HOROVOD_LOCKCHECK", "1")
    monkeypatch.setattr(lockorder, "_enabled", None)
    assert isinstance(make_lock("x"), TrackedLock)
    monkeypatch.setenv("HOROVOD_LOCKCHECK", "0")  # repo knob semantics
    monkeypatch.setattr(lockorder, "_enabled", None)
    assert isinstance(make_lock("x"), type(threading.Lock()))
    monkeypatch.setattr(lockorder, "_enabled", None)


def test_write_graph_artifact(tmp_path, monkeypatch):
    from horovod_tpu.analysis import lockorder

    monkeypatch.setenv("HOROVOD_LOCKCHECK", "1")
    monkeypatch.setattr(lockorder, "_enabled", None)
    g = lockorder.graph()
    a = TrackedLock("art.a", graph_=g)
    b = TrackedLock("art.b", graph_=g)
    with a:
        with b:
            pass
    out = tmp_path / "lockgraph.json"
    assert lockorder.write_graph(str(out)) == str(out)
    payload = json.loads(out.read_text())
    assert payload["acyclic"] in (True, False)
    assert any(e["from"] == "art.a" and e["to"] == "art.b"
               for e in payload["edges"])
    monkeypatch.setattr(lockorder, "_enabled", None)


# ---------------------------------------------------------------------------
# 5. 3-rank acceptance: real controller under HOROVOD_LOCKCHECK=1


def test_lockcheck_three_rank_run_produces_acyclic_graph(tmp_path):
    """Acceptance criterion: a 3-rank eager job under
    ``HOROVOD_LOCKCHECK=1`` completes and every rank writes a valid
    ``lockgraph.json`` with no cycles. Telemetry + rank-0 timeline are
    on so the run exercises the real nested acquisitions (the
    timeline-emit-under-pids-lock path the detector exists to watch)."""
    size = 3
    out = str(tmp_path / "lockgraph.json")
    run_ranks("allreduce", size, timeout=120, extra_env={
        "HOROVOD_LOCKCHECK": "1",
        "HOROVOD_LOCKCHECK_OUTPUT": out,
        "HOROVOD_METRICS": "1",
    }, per_rank_env={0: {"HOROVOD_TIMELINE": str(tmp_path / "tl.json")}},
        protocheck=False)
    edges_seen = 0
    reports = []
    for rank in range(size):
        path = f"{out}.rank{rank}"
        assert os.path.exists(path), f"rank {rank} wrote no lock graph"
        payload = json.loads(open(path).read())
        reports.append(payload)
        assert payload["acyclic"] is True, (
            f"rank {rank} lock-order CYCLE: {payload['cycles']}")
        edges_seen += len(payload["edges"])
    # The coordinator's timeline/metrics nesting guarantees real
    # observations — an all-empty graph would mean the factory isn't
    # actually wired into the runtime locks.
    assert edges_seen > 0, "no lock-order edges recorded on any rank"
    # Static×runtime join (ISSUE 8 acceptance): the AST-extracted
    # potential lock-order graph must be a SUPERSET of every runtime
    # graph this real job just produced — otherwise "statically possible
    # cycles never observed" would be a hollow claim.
    from horovod_tpu.analysis import lockorder

    join = lockorder.join_reports(lockorder.static_graph(), reports)
    assert join["superset"], (
        "runtime lock edges missing from the static graph: "
        f"{join['uncovered_runtime_edges']}")


# ------------------------------------------------ the suite's own launches

# What starts a process: of ``subprocess`` and of ``os`` the calls that
# fork, spawn, exec or shell out.
_LAUNCHERS = {
    "subprocess": ("run", "Popen", "call", "check_call", "check_output",
                   "getoutput", "getstatusoutput"),
    "os": ("system", "popen", "fork", "forkpty", "posix_spawn",
           "posix_spawnp", "startfile"),
    "multiprocessing": ("Process", "Pool"),
}


def _launch_findings(source, path):
    """What ``tests/mp_harness.py`` asks of a test file, as findings: no
    process started but through the harness, and no literal ``timeout=``
    above ``LAUNCH_LIMIT`` in a call or a default. The suite runs under
    one clock (``timeout 1470``); a launch's limit is a hang's price."""
    import ast

    found = []
    for node in ast.walk(ast.parse(source, path)):
        if isinstance(node, ast.Call):
            fn = node.func
            owner = (fn.value.id if isinstance(fn, ast.Attribute)
                     and isinstance(fn.value, ast.Name) else None)
            if owner and (fn.attr in _LAUNCHERS.get(owner, ()) or (
                    owner == "os" and fn.attr.startswith(("spawn", "exec")))):
                found.append(f"{path}:{node.lineno}: {owner}.{fn.attr}() "
                             "starts a process outside tests/mp_harness.py")
            limits = [(kw.value, node.lineno) for kw in node.keywords
                      if kw.arg == "timeout"]
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            named = list(zip((args.posonlyargs + args.args)[::-1],
                             args.defaults[::-1]))
            named += [(a, d) for a, d in zip(args.kwonlyargs,
                                             args.kw_defaults) if d]
            limits = [(d, node.lineno) for a, d in named
                      if a.arg == "timeout"]
        else:
            continue
        for value, line in limits:
            if (isinstance(value, ast.Constant)
                    and isinstance(value.value, (int, float))
                    and value.value > LAUNCH_LIMIT):
                found.append(f"{path}:{line}: timeout={value.value} is "
                             f"above the {LAUNCH_LIMIT:.0f} s a hang may "
                             "cost")
    return found


@pytest.mark.parametrize("source,findings", [
    ("import subprocess\nsubprocess.run(['x'], timeout=560)\n", 2),
    ("import subprocess as sp, os\nos.system('x')\nos.execv('x', [])\n", 2),
    ("def _run(cmd, timeout=300):\n    return run_cmd(cmd, timeout)\n", 1),
    ("def _run(cmd, *, timeout=420.0):\n    pass\n", 1),
    ("run_ranks('x', timeout=240.0)\nhandle.result(timeout=181)\n", 2),
    ("run_cmd(cmd, timeout=180)\nrun_ranks('x', timeout=limit * 4)\n"
     "body = 'subprocess.Popen([1])'\nos.path.join('a')\n", 0),
], ids=["subprocess_run_and_its_limit", "os_system_and_exec",
        "default_limit", "keyword_only_default", "keyword_limits", "clean"])
def test_launch_rule_finds_what_it_names(source, findings):
    assert len(_launch_findings(source, "t.py")) == findings


def test_tests_launch_processes_only_through_the_harness():
    """Every ``tests/*.py`` outside ``tests/benchmark/`` (the benchmark's
    own, not this gate's) but the harness itself and the workers it
    starts."""
    exempt = {"mp_harness.py", "mp_worker.py", "spmd_worker.py",
              "fake_pyspark.py"}
    found = []
    for name in sorted(os.listdir(HERE)):
        if name.endswith(".py") and name not in exempt:
            with open(os.path.join(HERE, name), encoding="utf-8") as f:
                found += _launch_findings(f.read(), f"tests/{name}")
    assert not found, "\n".join(found)


# The modules that ``tests/conftest.py`` lets compile more XLA programs a
# test than ``MAX_XLA_PROGRAMS_A_TEST`` (rule 1 beside rule 2 above), each
# by a module-level ``EAGER_BY_DESIGN = "<why>"``. A new name here is a
# reviewer's decision, and five are the most there may be.
EAGER_MODULES = set()


def test_the_modules_excepted_from_the_compile_cap_are_listed_here():
    import ast

    found = set()
    for name in sorted(os.listdir(HERE)):
        if name.endswith(".py"):
            with open(os.path.join(HERE, name), encoding="utf-8") as f:
                body = ast.parse(f.read(), name).body
            found |= {name for node in body if isinstance(node, ast.Assign)
                      and any(getattr(t, "id", "") == "EAGER_BY_DESIGN"
                              for t in node.targets)}
    assert found == EAGER_MODULES and len(found) <= 5
