"""graftcheck: the analyzer's own contract tests.

Each pass is pinned to its fixture pair under
``tests/graftcheck_fixtures/`` — known-bad files assert the EXACT rule
ids and line numbers, known-good files assert silence. The suite also
runs the analyzer over the real package (which wires graftcheck into
tier-1 CI: a new finding fails these tests) and checks the CLI, the
baseline workflow, and the <10s speed budget.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from tools.graftcheck import (
    ALL_PASSES,
    Context,
    analyze_paths,
    load_baseline,
    new_findings,
)
from tools.graftcheck.core import write_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "graftcheck_fixtures")


def run_on(*names: str, root: str = REPO):
    paths = [os.path.join(FIXTURES, name) for name in names]
    ctx = Context(root=root, docs_dir=os.path.join(root, "docs"))
    return analyze_paths(paths, ALL_PASSES, ctx)


def rule_lines(findings, rule):
    return sorted(
        f.line for f in findings if f.rule == rule
    )


# ---- per-pass fixture contracts -------------------------------------


def test_lock_discipline_bad():
    findings = run_on("lock_bad.py")
    assert rule_lines(findings, "GC101") == [23, 27, 31, 36, 45]
    assert {f.rule for f in findings} == {"GC101"}


def test_lock_discipline_good():
    assert run_on("lock_good.py") == []


def test_host_sync_bad():
    findings = run_on("hostsync_bad.py")
    assert rule_lines(findings, "GC201") == [10, 11, 12, 18, 19]
    assert rule_lines(findings, "GC202") == [28, 29]
    assert {f.rule for f in findings} == {"GC201", "GC202"}


def test_host_sync_good():
    assert run_on("hostsync_good.py") == []


def test_env_registry_bad():
    findings = run_on("env_bad.py")
    assert rule_lines(findings, "GC301") == [9, 13, 17, 21, 25, 42]
    assert rule_lines(findings, "GC302") == [29, 33]
    assert {f.rule for f in findings} == {"GC301", "GC302"}


def test_env_registry_good():
    assert run_on("env_good.py") == []


def test_collective_axis_bad():
    findings = run_on("axis_bad.py")
    assert rule_lines(findings, "GC401") == [15, 19, 23]
    assert {f.rule for f in findings} == {"GC401"}


def test_collective_axis_good():
    assert run_on("axis_good.py") == []


def test_mesh_topology_construction_bad():
    """Literals outside the module's bound axes still flag when the
    only mesh is an explicit create_mesh without those names."""
    findings = run_on("meshtopo_bad.py")
    assert rule_lines(findings, "GC401") == [13, 19]
    assert {f.rule for f in findings} == {"GC401"}


def test_mesh_topology_construction_good():
    """The mesh-shape construction path (create_mesh axes dicts and
    create_mesh_from_topology's canonical names) resolves collective
    literals — a reshaped job's module needs no suppressions."""
    assert run_on("meshtopo_good.py") == []


def test_checkpoint_protocol_bad():
    findings = run_on("ckptproto_bad.py")
    assert rule_lines(findings, "GC501") == [8, 16, 33]
    assert rule_lines(findings, "GC502") == [25, 26]
    assert {f.rule for f in findings} == {"GC501", "GC502"}


def test_checkpoint_protocol_good():
    assert run_on("ckptproto_good.py") == []


def test_fault_rpc_bad():
    findings = run_on("faultrpc_bad.py")
    assert rule_lines(findings, "GC601") == [3, 4, 10, 14]
    assert rule_lines(findings, "GC602") == [19, 23]
    assert {f.rule for f in findings} == {"GC601", "GC602"}


def test_fault_rpc_good():
    assert run_on("faultrpc_good.py") == []


def test_journal_discipline_bad():
    findings = run_on("journaled_bad.py")
    assert rule_lines(findings, "GC603") == [14]
    assert rule_lines(findings, "GC604") == [21]
    assert {f.rule for f in findings} == {"GC603", "GC604"}


def test_journal_discipline_good():
    assert run_on("journaled_good.py") == []


def test_replay_purity_bad():
    findings = run_on("replay_bad.py")
    assert rule_lines(findings, "GC901") == [18, 22, 24, 29, 33]
    assert rule_lines(findings, "GC902") == [28]
    assert rule_lines(findings, "GC903") == [35]
    # The unannotated journal append is also a GC604 (both catalogs
    # are honest about the same sneaky method).
    assert {f.rule for f in findings} == {
        "GC901", "GC902", "GC903", "GC604",
    }


def test_replay_purity_good():
    assert run_on("replay_good.py") == []


def test_replay_purity_transitive_finding_names_path():
    findings = run_on("replay_bad.py")
    via = [f for f in findings if f.line == 33]
    assert len(via) == 1
    assert "_helper" in via[0].message
    assert "_apply_commit_locked" in via[0].message


def test_sim_replay_purity_bad():
    """graftsim's determinism contract: wall clocks, env reads, RNG
    construction, and file I/O on `# replay-pure` sim plumbing are
    caught at the exact line (a hidden time.time() would silently
    break trace determinism)."""
    findings = run_on("simpure_bad.py")
    assert rule_lines(findings, "GC901") == [14, 17, 25, 30, 34]
    assert {f.rule for f in findings} == {"GC901"}


def test_sim_replay_purity_good():
    assert run_on("simpure_good.py") == []


def test_spmd_divergence_bad():
    """The acceptance gate: a deliberately rank-divergent collective
    is caught at the exact line — including the equal-multiset,
    different-ORDER form (rank 0 at psum, the rest at pmean)."""
    findings = run_on("spmd_bad.py")
    assert rule_lines(findings, "GC801") == [12, 19, 26, 34]
    assert {f.rule for f in findings} == {"GC801"}


def test_spmd_divergence_good():
    assert run_on("spmd_good.py") == []


def test_stage_seq_bad():
    findings = run_on("stageseq_bad.py")
    assert rule_lines(findings, "GC802") == [13]
    assert {f.rule for f in findings} == {"GC802"}


def test_stage_seq_good_sees_through_helpers():
    assert run_on("stageseq_good.py") == []


def test_axis_flow_bad():
    findings = run_on("axisflow_bad.py")
    assert rule_lines(findings, "GC803") == [16, 20, 23]
    assert {f.rule for f in findings} == {"GC803"}


def test_axis_flow_good():
    assert run_on("axisflow_good.py") == []


def test_lock_flow_bad():
    findings = run_on("lockflow_bad.py")
    assert rule_lines(findings, "GC103") == [14]
    assert rule_lines(findings, "GC101") == [23]
    assert {f.rule for f in findings} == {"GC101", "GC103"}


def test_lock_flow_good_infers_helper_locks():
    """v1 flagged _drain's unannotated access; the interprocedural
    lock-set must prove it held from its (all-locked) call sites."""
    assert run_on("lockflow_good.py") == []


def test_wire_contract_bad():
    """The wire-contract acceptance gate: a producer's undeclared key
    and a deliberately misspelled consumer key ('alocation') are each
    caught at the exact line, and a typo'd family name fails at the
    def instead of silently disabling the function's checks."""
    findings = run_on("wire_bad.py")
    assert rule_lines(findings, "GC1001") == [15]
    assert rule_lines(findings, "GC1002") == [20, 25]
    assert {f.rule for f in findings} == {"GC1001", "GC1002"}
    misspelled = [f for f in findings if f.line == 20]
    assert "alocation" in misspelled[0].message


def test_wire_contract_good():
    assert run_on("wire_good.py") == []


def test_wire_compat_bad():
    """A journal-record consumer subscripting a version-optional key
    without a default (breaks replay of pre-upgrade journals) is
    caught at the exact line."""
    findings = run_on("compat_bad.py")
    assert rule_lines(findings, "GC1004") == [12]
    assert {f.rule for f in findings} == {"GC1004"}
    assert "slots" in findings[0].message


def test_wire_compat_good():
    """Required-since-v1 subscripts, .get defaults, and guarded
    subscripts are all compat-safe."""
    assert run_on("compat_good.py") == []


def test_endpoint_conformance_bad():
    """Orphan route, client call to an unregistered path, missing
    idempotency annotation on a retried PUT, and a handler with no
    registered fault point — each at its exact line."""
    findings = run_on("endpoint_bad.py")
    assert rule_lines(findings, "GC1101") == [36]
    assert rule_lines(findings, "GC1102") == [56]
    assert rule_lines(findings, "GC1103") == [24]
    assert rule_lines(findings, "GC1104") == [24]
    assert {f.rule for f in findings} == {
        "GC1101", "GC1102", "GC1103", "GC1104",
    }


def test_endpoint_conformance_good():
    """Every route called, mutating handlers annotated, fault points
    registered — and the externally-probed /healthz route is exempt
    via wire.EXTERNAL_ROUTES."""
    assert run_on("endpoint_good.py") == []


def test_timing_discipline_bad():
    findings = run_on("timing_bad.py")
    assert rule_lines(findings, "GC701") == [11, 21]
    assert rule_lines(findings, "GC702") == [15]
    assert {f.rule for f in findings} == {"GC701", "GC702"}


def test_timing_discipline_good():
    assert run_on("timing_good.py") == []


def test_timing_discipline_only_binds_instrumented_modules(tmp_path):
    """A module with wall-clock duration math but NO adaptdl_tpu.trace
    import is outside the discipline — the pass must not fire on
    arbitrary code."""
    plain = tmp_path / "plain.py"
    plain.write_text(
        "import time\n\n\n"
        "def f():\n"
        "    start = time.time()\n"
        "    return time.time() - start\n"
    )
    ctx = Context(root=str(tmp_path))
    assert analyze_paths([str(plain)], ALL_PASSES, ctx) == []


def test_trace_instrumented_modules_stay_instrumented():
    """The GC7xx discipline only has teeth while the rescale-lifecycle
    modules keep importing trace: a refactor that silently drops the
    instrumentation (and with it the spans AND the timing lint) must
    fail here."""
    from tools.graftcheck.core import parse_file
    from tools.graftcheck.passes.timing_discipline import (
        _imports_trace,
    )

    for rel in (
        "adaptdl_tpu/rpc.py",
        "adaptdl_tpu/checkpoint.py",
        "adaptdl_tpu/aot_cache.py",
        "adaptdl_tpu/bootstrap.py",
        "adaptdl_tpu/metrics.py",
        "adaptdl_tpu/sched/journal.py",
        "adaptdl_tpu/sched/state.py",
        "adaptdl_tpu/sched/allocator.py",
        "adaptdl_tpu/sched/supervisor.py",
    ):
        sf = parse_file(os.path.join(REPO, rel), REPO)
        assert _imports_trace(sf), f"{rel} no longer imports trace"


def test_fault_rpc_catalog_tracks_faults_module(tmp_path):
    """GC602 judges against the REAL faults.py catalog: a root with no
    faults module yields no (unjudgeable) findings, and a root whose
    catalog contains the fixture's 'typo' name accepts it."""
    fixtures = os.path.join(tmp_path, "tests", "graftcheck_fixtures")
    os.makedirs(fixtures)
    import shutil

    shutil.copy(
        os.path.join(FIXTURES, "faultrpc_bad.py"),
        os.path.join(fixtures, "faultrpc_bad.py"),
    )
    # No faults module under this root: GC601 still fires, GC602 not.
    ctx = Context(root=str(tmp_path))
    findings = analyze_paths(
        [os.path.join(fixtures, "faultrpc_bad.py")], ALL_PASSES, ctx
    )
    assert rule_lines(findings, "GC601") == [3, 4, 10, 14]
    assert rule_lines(findings, "GC602") == []
    # A catalog registering the names makes them legal.
    pkg = os.path.join(tmp_path, "adaptdl_tpu")
    os.makedirs(pkg)
    with open(os.path.join(pkg, "faults.py"), "w") as f:
        f.write(
            "INJECTION_POINTS = {\n"
            '    "ckpt.write.pre_renam": "x",\n'
            '    "made.up.point": "y",\n'
            "}\n"
        )
    findings = analyze_paths(
        [os.path.join(fixtures, "faultrpc_bad.py")], ALL_PASSES, ctx
    )
    assert rule_lines(findings, "GC602") == []


def test_lock_order_bad():
    """The deliberate ABBA is reported at BOTH second-acquisition
    sites — each direction of the cycle names the exact line that
    closes it."""
    findings = run_on("lockorder_bad.py")
    assert rule_lines(findings, "GC1201") == [25, 31]
    assert rule_lines(findings, "GC1202") == [37, 43]
    assert rule_lines(findings, "GC1203") == [15, 17, 20, 48]
    assert {f.rule for f in findings} == {
        "GC1201", "GC1202", "GC1203",
    }


def test_lock_order_good():
    assert run_on("lockorder_good.py") == []


def test_event_loop_bad():
    findings = run_on("eventloop_bad.py")
    assert rule_lines(findings, "GC1301") == [18, 22]
    assert rule_lines(findings, "GC1302") == [27]
    assert rule_lines(findings, "GC1303") == [35]
    assert {f.rule for f in findings} == {
        "GC1301", "GC1302", "GC1303",
    }


def test_event_loop_good():
    assert run_on("eventloop_good.py") == []


def test_lifecycle_bad():
    findings = run_on("lifecycle_bad.py")
    assert rule_lines(findings, "GC1401") == [11, 15, 19]
    assert rule_lines(findings, "GC1402") == [24]
    assert rule_lines(findings, "GC1403") == [30]
    assert rule_lines(findings, "GC1404") == [38]
    assert {f.rule for f in findings} == {
        "GC1401", "GC1402", "GC1403", "GC1404",
    }


def test_lifecycle_good():
    assert run_on("lifecycle_good.py") == []


def test_lifecycle_detached_registry_resolves_real_entries():
    """GC1402 judges ``# detached:`` names against the REAL
    concurrency.DETACHED_SPAWNS registry — the good fixture's
    'warm-successor' passes only because the package registers it, and
    an empty-registry root flags it."""
    from tools.graftcheck.passes.lifecycle import _load_registry

    registry = _load_registry(
        os.path.join(REPO, "adaptdl_tpu", "concurrency.py")
    )
    assert registry is not None
    assert "warm-successor" in registry
    assert "handoff-child-server" in registry


def test_file_level_suppression():
    findings = run_on("suppress_file.py")
    assert rule_lines(findings, "GC302") == [16]
    assert rule_lines(findings, "GC301") == []


# ---- findings carry actionable metadata -----------------------------


def test_findings_have_location_rule_and_hint():
    for finding in run_on("lock_bad.py", "env_bad.py"):
        assert finding.file.endswith(".py")
        assert finding.line > 0
        assert finding.rule.startswith("GC")
        assert finding.message
        assert finding.hint
        rendered = finding.render()
        assert f":{finding.line}:" in rendered
        assert finding.rule in rendered


# ---- the real package stays clean (tier-1 wiring) -------------------


def test_package_is_clean_or_baselined():
    """THE gate: ``adaptdl_tpu/`` must produce no findings beyond the
    committed baseline — and the cold run that proves it must fit the
    <8s budget (re-pinned with the GC12xx/GC13xx/GC14xx whole-program
    passes aboard) that keeps graftcheck in `make lint` and CI on
    every push (one timed analysis serves both assertions; the suite
    pays for a full-package run exactly once). The budget is on the
    analysing thread's CPU time, as ``tests/test_watchgate.py`` reads
    its own: what the analysis costs, not how long the suite's other
    workers kept this thread off a core."""
    ctx = Context(root=REPO, docs_dir=os.path.join(REPO, "docs"))
    start = time.thread_time()
    findings = analyze_paths(
        [os.path.join(REPO, "adaptdl_tpu")], ALL_PASSES, ctx
    )
    elapsed = time.thread_time() - start
    baseline = load_baseline(
        os.path.join(REPO, "graftcheck_baseline.json")
    )
    fresh = new_findings(findings, baseline)
    assert fresh == [], "\n".join(f.render() for f in fresh)
    assert elapsed < 8.0


def test_package_annotations_are_present():
    """The race-lint only has teeth while the shared writer-thread
    fields stay annotated — a refactor silently dropping the
    guarded-by markers must fail, not pass vacuously."""
    from tools.graftcheck.passes.lock_discipline import _collect_guards
    from tools.graftcheck.core import parse_file

    expected = {
        "adaptdl_tpu/metrics.py": {"profile", "num_retunes"},
        "adaptdl_tpu/checkpoint.py": {"per_state"},
        "adaptdl_tpu/aot_cache.py": {"_writers"},
        "adaptdl_tpu/sched/state.py": {"_jobs", "_completions"},
    }
    for rel, fields in expected.items():
        sf = parse_file(os.path.join(REPO, rel), REPO)
        guards, _ = _collect_guards(sf)
        declared = {g.field for g in guards}
        assert fields <= declared, (rel, declared)


def test_cluster_state_mutators_stay_journaled():
    """The durable-state contract only has teeth while the mutator
    set stays annotated: a refactor that silently drops `# journaled`
    from a ClusterState mutator (making part of the cluster state
    volatile again) must fail here, not in a crash."""
    from tools.graftcheck.core import parse_file
    from tools.graftcheck.passes.journal_discipline import (
        JournalDisciplinePass,
    )

    sf = parse_file(
        os.path.join(REPO, "adaptdl_tpu", "sched", "state.py"), REPO
    )
    annotated = JournalDisciplinePass().journaled_methods(sf)
    expected = {
        "create_job",
        "remove_job",
        "update",
        "publish_retune",
        "register_worker",
        "renew_lease",
        "expire_stale_leases",
        "expire_overdue_allocations",
        "_maybe_commit_locked",
        "_recover",
    }
    assert expected <= annotated, annotated


# The <6s cold speed budget is asserted inside
# test_package_is_clean_or_baselined (same timed run); the <1s warm
# budget lives in test_graftcheck_program.py.


# ---- baseline workflow ----------------------------------------------


def test_baseline_allowlists_only_listed_findings(tmp_path):
    findings = run_on("env_bad.py")
    assert findings
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings[:-1])
    baseline = load_baseline(str(path))
    fresh = new_findings(findings, baseline)
    assert fresh == [findings[-1]]


def test_baseline_roundtrip_is_json(tmp_path):
    findings = run_on("lock_bad.py")
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings)
    payload = json.loads(path.read_text())
    assert len(payload["findings"]) == len(findings)
    assert load_baseline(str(path)) == {
        f.baseline_key() for f in findings
    }


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == set()


# ---- the committed baseline stays honest ----------------------------


def test_committed_baseline_is_empty():
    """Every real violation the passes surfaced was FIXED, not
    baselined — keep it that way (delete this test only with a
    deliberate, reviewed deferral)."""
    path = os.path.join(REPO, "graftcheck_baseline.json")
    payload = json.loads(open(path).read())
    assert payload["findings"] == []


# ---- CLI ------------------------------------------------------------


def _run_cli(*args: str):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftcheck", *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_clean_input_exits_zero():
    """Exit-0 semantics on clean input (the real-package gate runs
    in-process in test_package_is_clean_or_baselined — no need to pay
    a second full cold CLI analysis here)."""
    proc = _run_cli(
        os.path.join("tests", "graftcheck_fixtures", "lock_good.py")
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_findings_exit_one():
    proc = _run_cli(
        os.path.join("tests", "graftcheck_fixtures", "env_bad.py"),
        "--baseline",
        "does-not-exist.json",
    )
    assert proc.returncode == 1
    assert "GC301" in proc.stdout


def test_cli_unknown_path_exits_two():
    proc = _run_cli("no/such/dir")
    assert proc.returncode == 2


def test_cli_json_format():
    proc = _run_cli(
        os.path.join("tests", "graftcheck_fixtures", "lock_bad.py"),
        "--format",
        "json",
        "--baseline",
        "does-not-exist.json",
    )
    assert proc.returncode == 1
    parsed = json.loads(proc.stdout)
    assert {item["rule"] for item in parsed} == {"GC101"}


def test_cli_rules_filter():
    proc = _run_cli(
        os.path.join("tests", "graftcheck_fixtures", "env_bad.py"),
        "--rules",
        "GC302",
        "--baseline",
        "does-not-exist.json",
    )
    assert proc.returncode == 1
    assert "GC301" not in proc.stdout
    assert "GC302" in proc.stdout


def test_cli_fast_mode_caches(tmp_path):
    """--fast reuses per-file results for unchanged files: second run
    must agree with the first (and not crash on the cache). Runs in a
    tmp cwd so the cache file never touches the repo root."""
    fixture = os.path.join(FIXTURES, "hostsync_bad.py")
    env = dict(os.environ, PYTHONPATH=REPO)

    def run():
        return subprocess.run(
            [
                sys.executable, "-m", "tools.graftcheck", fixture,
                "--fast", "--baseline", "nope.json",
            ],
            cwd=str(tmp_path),
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )

    first, second = run(), run()
    assert first.returncode == second.returncode == 1
    assert first.stdout == second.stdout
    assert (tmp_path / ".graftcheck_cache.json").is_file()


def test_syntax_error_is_reported_not_fatal(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    ctx = Context(root=str(tmp_path))
    findings = analyze_paths([str(bad)], ALL_PASSES, ctx)
    assert [f.rule for f in findings] == ["GC001"]


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
