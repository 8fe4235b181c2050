"""swarmlint self-tests (ISSUE 1 tentpole).

Each check family must detect its seeded fixture violation with the right
rule id on the right line (``# EXPECT: <rule>`` annotations in
tests/fixtures/lint/), the clean fixture must be clean, suppression and
baseline machinery must round-trip, and — the CI contract — the package
tree itself must be clean against the committed ``analysis/baseline.json``.
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from swarmdb_tpu.analysis import analyze_file
from swarmdb_tpu.analysis.cli import main

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "lint"

EXPECT_RE = re.compile(r"#\s*EXPECT:\s*(SWL[0-9]+(?:\s*,\s*SWL[0-9]+)*)")


def expected_findings(path: Path):
    out = set()
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        m = EXPECT_RE.search(line)
        if m:
            for rule in m.group(1).split(","):
                out.add((lineno, rule.strip()))
    return out


@pytest.mark.parametrize("name", [
    "hot_sync_bad.py",          # host-sync family (SWL101/SWL102)
    "hot_sync_loop_bad.py",     # host-sync-in-loop family (SWL105)
    "recompile_bad.py",         # recompile family (SWL201/202/203)
    "ragged_shape_bad.py",      # descriptor shape math in hot code (SWL205)
    "lock_bad.py",              # lock-discipline family (SWL301)
    "tracer_leak_bad.py",       # tracer-leak family (SWL401)
    "span_bad.py",              # span-discipline family (SWL501/502)
    "phase_bad.py",             # the phase pair's balance (SWL501)
    "metrics_bad.py",           # histogram discipline (SWL503)
    "exemplar_bad.py",          # exemplar/sentinel allocation (SWL504)
    "profile_bad.py",           # compile-time introspection in hot code (SWL506)
    "memprof_bad.py",           # memprof record-path allocation (SWL507)
    "heartbeat_bad.py",         # heartbeat-safety family (SWL601/602)
    "fence_bad.py",             # fencing discipline (SWL603)
    "retry_bad.py",             # retry-discipline family (SWL701)
    "deadlock_bad.py",          # lock-order inversion (SWL302)
    "guarded_bad.py",           # inferred guarded-by (SWL303)
    "callback_lock_bad.py",     # callback-under-lock (SWL305)
    "lockwait_snapshot.py",     # wait-not-in-while (SWL304)
    "pageleak_bad.py",          # page-leak incl. exception paths (SWL801)
    "page_uaf_bad.py",          # page use-after-free (SWL802)
    "page_doublefree_bad.py",   # double-free + write-before-alloc (SWL803/805)
    "pin_bad.py",               # pin-discipline (SWL804)
    "pagelife_snapshot.py",     # pre-fix engine/allocator leaks (SWL801)
    "kernel_oob_bad.py",        # kernel-check: OOB index maps (SWL901)
    "kernel_race_bad.py",       # kernel-check: output write race (SWL902)
    "kernel_vmem_bad.py",       # kernel-check: VMEM budget (SWL903)
    "kernel_tile_bad.py",       # kernel-check: tiling misalignment (SWL904)
    "kernel_unwritten_bad.py",  # kernel-check: unwritten output (SWL905)
])
def test_each_family_detects_seeded_violations(name):
    path = FIXTURES / name
    expected = expected_findings(path)
    assert expected, f"fixture {name} carries no EXPECT annotations"
    actual = {(f.line, f.rule) for f in analyze_file(str(path))}
    assert actual == expected, (
        f"{name}: reported {sorted(actual)} != seeded {sorted(expected)}")


def test_prefix_replica_snapshot_reproduces_advice_finding():
    """The pre-fix ``_serve`` shape (ADVICE r5: mirror-map read outside
    the lock its ack thread takes) must be re-detected — the checker
    would have caught the original finding before review did."""
    path = FIXTURES / "replica_prefix_snapshot.py"
    findings = analyze_file(str(path))
    assert [(f.rule, f.line) for f in findings] == [
        ("SWL301", next(iter(expected_findings(path)))[0])]
    assert "appended" in findings[0].message
    # ...and the FIXED in-tree _serve no longer trips it
    fixed = analyze_file(str(REPO / "swarmdb_tpu" / "broker" / "replica.py"))
    assert [f for f in fixed if f.rule == "SWL301"] == []


def test_clean_fixture_has_zero_findings():
    assert analyze_file(str(FIXTURES / "clean.py")) == []


def test_deadlock_ok_twin_is_clean():
    """Same locks, same call-graph shape as deadlock_bad.py, but a
    consistent acquisition order — the graph is acyclic, zero
    findings."""
    assert analyze_file(str(FIXTURES / "deadlock_ok.py")) == []


def test_lockwait_snapshot_reproduces_prefix_finding():
    """The pre-fix ``LocalBroker.wait_for_data`` shape (single
    ``cond.wait`` under an ``if``) must be re-detected as SWL304 — and
    the FIXED in-tree broker/local.py (deadline while loop) stays
    clean of the rule."""
    path = FIXTURES / "lockwait_snapshot.py"
    findings = analyze_file(str(path))
    assert [(f.rule, f.line) for f in findings] == [
        ("SWL304", next(iter(expected_findings(path)))[0])]
    assert "while" in findings[0].message
    fixed = analyze_file(str(REPO / "swarmdb_tpu" / "broker" / "local.py"))
    assert [f for f in fixed if f.rule == "SWL304"] == []


def test_pagelife_snapshot_reproduces_real_findings():
    """The pre-fix shapes of the two REAL SWL801 findings this pass
    surfaced — Engine._admit's reclaim and PageAllocator.flush_frees
    both freeing a drained retirement batch across an unprotected
    raising dispatch — must be re-detected, and the FIXED in-tree code
    (requeue_pending on the exception path) must stay clean."""
    path = FIXTURES / "pagelife_snapshot.py"
    findings = analyze_file(str(path))
    assert {(f.rule, f.line) for f in findings} == {
        ("SWL801", ln) for ln, _ in expected_findings(path)}
    assert all("exception path" in f.message for f in findings)
    for fixed in ("swarmdb_tpu/backend/engine.py",
                  "swarmdb_tpu/ops/paged_kv.py"):
        clean = analyze_file(str(REPO / fixed))
        assert [f for f in clean if f.rule.startswith("SWL80")] == []


def test_owns_borrows_directives_shape_ownership(tmp_path):
    """owns[page] transfers ownership INTO the callee (caller reuse is
    use-after-transfer); borrows[page] keeps the caller responsible
    (an unannotated escape would silently discharge)."""
    target = tmp_path / "owns_mod.py"
    target.write_text(
        "# swarmlint: owns[page]: pages\n"
        "def consume(pages):\n"
        "    free_all(pages)\n"
        "\n"
        "\n"
        "def free_all(pages):\n"
        "    pass\n"
        "\n"
        "\n"
        "def caller(alloc):\n"
        "    pages = alloc.reserve(2)\n"
        "    consume(pages)\n"
        "    return pages          # use-after-transfer\n")
    findings = analyze_file(str(target))
    assert [f.rule for f in findings] == ["SWL802"]
    assert "freed" in findings[0].message


def test_parsed_ast_cache_reuses_source_objects(tmp_path):
    """The shared parse cache (tooling-perf satellite): two analyses
    of an unchanged file reuse one SourceFile; rewriting the file
    invalidates the entry."""
    from swarmdb_tpu.analysis.core import _parse_source

    target = tmp_path / "cached.py"
    target.write_text("x = 1\n")
    first = _parse_source(str(target))
    assert _parse_source(str(target)) is first
    target.write_text("x = 2  # rewritten\n")
    again = _parse_source(str(target))
    assert again is not first
    assert "rewritten" in again.text


def test_swl302_cycle_joined_only_across_files(tmp_path):
    """The interprocedural case per-file analysis CANNOT see: the two
    halves of an AB-BA living in different modules, joined by an
    import edge. Each file alone is clean; the project pass over both
    reports the inversion."""
    from swarmdb_tpu.analysis.core import analyze_paths

    (tmp_path / "store_mod.py").write_text(
        "import threading\n"
        "from log_mod import grab_log\n"
        "\n"
        "\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "\n"
        "    def flush(self):\n"
        "        with self._mu:\n"
        "            grab_log(self)\n")
    (tmp_path / "log_mod.py").write_text(
        "import threading\n"
        "\n"
        "LOG = threading.Lock()\n"
        "\n"
        "\n"
        "def grab_log(store):\n"
        "    with LOG:\n"
        "        pass\n"
        "\n"
        "\n"
        "def snapshot(store: \"Store\"):\n"
        "    with LOG:\n"
        "        store.flush()\n"
        "\n"
        "\n"
        "from store_mod import Store\n")
    # each half alone: no resolvable cross-module edge, no finding
    assert analyze_file(str(tmp_path / "store_mod.py")) == []
    assert analyze_file(str(tmp_path / "log_mod.py")) == []
    findings = analyze_paths([str(tmp_path)])
    rules = {f.rule for f in findings}
    assert rules == {"SWL302"}
    msgs = " ".join(f.message for f in findings)
    assert "Store._mu" in msgs and "LOG" in msgs
    # a finding lands on each edge of the cycle: one per file
    assert {f.path.split("/")[-1] for f in findings} == {
        "store_mod.py", "log_mod.py"}


def test_inline_disable_suppresses(tmp_path):
    bad = (FIXTURES / "hot_sync_bad.py").read_text()
    patched = bad.replace(
        "    jax.block_until_ready(logits)  # EXPECT: SWL101",
        "    jax.block_until_ready(logits)  # swarmlint: disable=host-sync")
    assert patched != bad
    target = tmp_path / "suppressed.py"
    target.write_text(patched)
    supp_line = next(i for i, l in enumerate(patched.splitlines(), 1)
                     if "disable=host-sync" in l)
    lines = {f.line for f in analyze_file(str(target))}
    # the suppressed line is gone; every other seeded line survives
    assert supp_line not in lines
    assert lines == {ln for ln, _ in expected_findings(target)}
    assert lines  # the patch must not have silenced the whole fixture


def test_baseline_accepts_old_fails_new(tmp_path, capsys):
    target = str(FIXTURES / "lock_bad.py")
    baseline = tmp_path / "baseline.json"
    assert main([target, "--update-baseline",
                 "--baseline", str(baseline)]) == 0
    data = json.loads(baseline.read_text())
    assert data["version"] == 1 and len(data["findings"]) == 4
    # same tree, same baseline: clean
    assert main([target, "--baseline", str(baseline)]) == 0
    # a new violation elsewhere: exit 1, and ONLY the new one is reported
    extra = tmp_path / "fresh_violation.py"
    extra.write_text((FIXTURES / "tracer_leak_bad.py").read_text())
    capsys.readouterr()
    assert main([target, str(extra), "--baseline", str(baseline)]) == 1
    out = capsys.readouterr().out
    assert "SWL401" in out and "SWL301" not in out
    # --no-baseline surfaces everything again
    assert main([target, "--no-baseline"]) == 1


def test_select_restricts_families():
    target = str(FIXTURES / "hot_sync_bad.py")
    assert main([target, "--no-baseline", "--select", "lock-discipline"]) == 0
    assert main([target, "--no-baseline", "--select", "host-sync"]) == 1


def test_repo_tree_clean_against_committed_baseline():
    """The acceptance invocation (matches CI's lint job, which since
    ISSUE 12 also scans scripts/ and bench.py): exits 0 against the
    committed baseline."""
    assert main([str(REPO / "swarmdb_tpu"), str(REPO / "scripts"),
                 str(REPO / "bench.py"),
                 "--baseline", str(REPO / "analysis" / "baseline.json")]) == 0


def test_explain_covers_every_rule(capsys):
    from swarmdb_tpu.analysis.core import RULES
    from swarmdb_tpu.analysis.explain import EXPLAIN

    assert set(EXPLAIN) == set(RULES), (
        "every rule needs an --explain entry (doc + bad/good example)")
    assert main(["--explain", "SWL303"]) == 0
    out = capsys.readouterr().out
    assert "BAD:" in out and "GOOD:" in out and "inferred" in out.lower()
    # family names expand to every member
    assert main(["--explain", "lock-discipline"]) == 0
    out = capsys.readouterr().out
    for rid in ("SWL301", "SWL302", "SWL303", "SWL304", "SWL305"):
        assert rid in out
    assert main(["--explain", "SWL999"]) == 2


def test_prune_baseline_reports_then_writes(tmp_path, capsys):
    """--prune-baseline: entries whose finding is gone (file deleted or
    code fixed) are reported; only --write rewrites the file."""
    victim = tmp_path / "victim.py"
    victim.write_text((FIXTURES / "guarded_bad.py").read_text())
    keeper = tmp_path / "keeper.py"
    keeper.write_text((FIXTURES / "callback_lock_bad.py").read_text())
    baseline = tmp_path / "baseline.json"
    assert main([str(victim), str(keeper), "--update-baseline",
                 "--baseline", str(baseline)]) == 0
    before = json.loads(baseline.read_text())
    assert len(before["findings"]) == 2

    # fix one finding by deleting its file
    victim.unlink()
    capsys.readouterr()
    # report-only: stale named, file untouched
    assert main([str(keeper), "--prune-baseline",
                 "--baseline", str(baseline)]) == 0
    out = capsys.readouterr().out
    assert "stale:" in out and "victim.py" in out
    assert "report-only" in out
    assert len(json.loads(baseline.read_text())["findings"]) == 2

    # --write prunes, keeping the live entry
    assert main([str(keeper), "--prune-baseline", "--write",
                 "--baseline", str(baseline)]) == 0
    after = json.loads(baseline.read_text())
    assert len(after["findings"]) == 1
    assert after["findings"][0]["path"].endswith("keeper.py")
    # and the pruned baseline still accepts the surviving finding
    assert main([str(keeper), "--baseline", str(baseline)]) == 0


def test_cli_module_smoke():
    """`python -m swarmdb_tpu.analysis` end-to-end (module entry point)."""
    proc = subprocess.run(
        [sys.executable, "-m", "swarmdb_tpu.analysis", "--list-rules"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for rule in ("SWL101", "SWL203", "SWL301", "SWL302", "SWL303",
                 "SWL304", "SWL305", "SWL401", "SWL501",
                 "SWL502", "SWL503", "SWL504", "SWL506", "SWL507",
                 "SWL601", "SWL602",
                 "SWL603", "SWL801", "SWL802", "SWL803", "SWL804",
                 "SWL805"):
        assert rule in proc.stdout
