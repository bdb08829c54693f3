"""graftscope contract tests: trace context, spans, ring buffer,
exporters (JSONL / Perfetto / Prometheus), the supervisor's /trace
endpoints + /metrics exposition conformance, the CLI waterfall, the
end-to-end stitched-rescale acceptance test, and the CI gates
(tracing overhead < 1% of step time; ring buffer bounded under a
hammer)."""

from __future__ import annotations

import io
import json
import threading
import time
from contextlib import redirect_stdout

import pytest
import requests

from adaptdl_tpu import checkpoint, trace
from tests.promcheck import (
    ConformanceError,
    validate_exposition,
)

# ---- trace context ---------------------------------------------------


def test_traceparent_roundtrip():
    header = trace.new_traceparent()
    parsed = trace.parse_traceparent(header)
    assert parsed is not None
    trace_id, span_id = parsed
    assert len(trace_id) == 32 and len(span_id) == 16
    assert trace.format_traceparent(trace_id, span_id) == header


@pytest.mark.parametrize(
    "bad",
    [
        None,
        "",
        "junk",
        "00-short-span-01",
        "00-" + "g" * 32 + "-" + "1" * 16 + "-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # all-zero span
    ],
)
def test_malformed_traceparent_rejected(bad):
    assert trace.parse_traceparent(bad) is None
    assert trace.set_traceparent(bad) is False


def test_process_context_inherited_from_env(monkeypatch):
    header = trace.new_traceparent()
    monkeypatch.setenv("ADAPTDL_TRACEPARENT", header)
    trace._reset_state()
    assert trace.current_traceparent() == header
    with trace.span("inherit.phase"):
        pass
    (rec,) = trace.snapshot_spans()
    trace_id, span_id = trace.parse_traceparent(header)
    assert rec["trace"] == trace_id
    assert rec["parent"] == span_id


def test_span_nesting_parent_child():
    with trace.span("outer"):
        outer_tp = trace.current_traceparent()
        with trace.span("inner"):
            pass
    inner, outer = trace.snapshot_spans()
    assert inner["name"] == "inner"
    assert outer["name"] == "outer"
    assert inner["trace"] == outer["trace"]
    assert inner["parent"] == outer["span"]
    _, outer_span = trace.parse_traceparent(outer_tp)
    assert outer_span == outer["span"]


def test_span_with_explicit_traceparent_and_attrs():
    header = trace.new_traceparent()
    with trace.span("pinned", traceparent=header, job="ns/j") as attrs:
        attrs["outcome"] = "ok"
    (rec,) = trace.snapshot_spans()
    trace_id, span_id = trace.parse_traceparent(header)
    assert rec["trace"] == trace_id
    assert rec["parent"] == span_id
    assert rec["attrs"] == {"job": "ns/j", "outcome": "ok"}
    assert rec["dur"] >= 0


def test_span_records_on_exception_with_error_flag():
    with pytest.raises(RuntimeError):
        with trace.span("boom"):
            raise RuntimeError("x")
    (rec,) = trace.snapshot_spans()
    assert rec["attrs"]["error"] is True


def test_events_bump_counters():
    trace.event("rpc.retry", endpoint="hints/j")
    trace.event("rpc.retry", endpoint="hints/j")
    trace.event("aot.hit")
    text = trace.prometheus_lines()
    assert (
        'adaptdl_trace_events_total{event="rpc.retry"} 2' in text
    )
    assert 'adaptdl_trace_events_total{event="aot.hit"} 1' in text


def test_disabled_tracing_records_nothing(monkeypatch):
    monkeypatch.setenv("ADAPTDL_TRACE", "off")
    trace._reset_state()
    with trace.span("off.phase"):
        trace.event("off.event")
    trace.record_span("off.direct", 0.5)
    trace.begin_pending("off.pending")
    assert trace.end_pending("off.pending") is False
    assert trace.snapshot_spans() == []


def test_pending_span_bridges_callsites():
    trace.begin_pending("restart.first_step", restarts=2)
    time.sleep(0.01)
    assert trace.end_pending("restart.first_step", atomic_bsz=32)
    assert not trace.end_pending("restart.first_step")
    (rec,) = trace.snapshot_spans()
    assert rec["name"] == "restart.first_step"
    assert rec["dur"] >= 0.01
    assert rec["attrs"] == {"restarts": 2, "atomic_bsz": 32}


# ---- ring buffer -----------------------------------------------------


def test_ring_buffer_stays_bounded_under_hammer(monkeypatch):
    monkeypatch.setattr(trace, "BUFFER_SIZE", 512)
    trace._reset_state()
    threads = [
        threading.Thread(
            target=lambda: [
                trace.record_span("hammer.span", 0.001)
                for _ in range(2000)
            ]
        )
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = trace.snapshot_spans()
    assert len(spans) == 512  # bounded: maxlen, not 16000
    assert trace.buffer_seq() == 16000  # ...but every span was counted
    # The histogram saw every observation even though the ring evicted.
    text = trace.prometheus_lines()
    assert (
        'adaptdl_trace_phase_seconds_count{phase="hammer.span"} '
        "16000" in text
    )


# ---- exporter: JSONL journal -----------------------------------------


def test_journal_appends_and_reads_back(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/journal-job")
    trace._reset_state()
    with trace.span("j.one"):
        pass
    trace.event("j.event")
    path = trace.journal_path()
    assert path is not None and path.endswith(
        "trace-ns-journal-job.jsonl"
    )
    records = trace.read_journal(path)
    assert [r["name"] for r in records] == ["j.one", "j.event"]
    assert records[0]["trace"] == records[1]["trace"]


def test_journal_survives_torn_lines(tmp_path, monkeypatch):
    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/torn")
    trace._reset_state()
    with trace.span("before.kill"):
        pass
    path = trace.journal_path()
    # Simulate a mid-append kill: a partial record with no newline.
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"name": "torn.par')
    trace._reset_state()
    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/torn")
    # The successor incarnation appends after the torn tail...
    with trace.span("after.restart"):
        pass
    records = trace.read_journal(path)
    names = [r["name"] for r in records]
    # ...and both sides read back; the torn record is dropped.
    assert "before.kill" in names
    assert "after.restart" in names
    assert not any(n.startswith("torn") for n in names)


# ---- exporter: Perfetto trace_event JSON -----------------------------


def _validate_trace_event_schema(payload: dict) -> None:
    """The trace_event contract chrome://tracing actually enforces."""
    assert set(payload) >= {"traceEvents"}
    assert isinstance(payload["traceEvents"], list)
    for ev in payload["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert "name" in ev["args"]
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float))
            assert ev["dur"] >= 0
        assert isinstance(ev["args"], dict)
    json.dumps(payload)  # must be serializable as-is


def test_perfetto_export_validates_against_trace_event_schema():
    with trace.span("p.outer", job="ns/p"):
        with trace.span("p.inner"):
            pass
    trace.event("p.event")
    payload = trace.to_perfetto(trace.snapshot_spans())
    _validate_trace_event_schema(payload)
    names = [ev["name"] for ev in payload["traceEvents"]]
    assert "p.outer" in names and "p.inner" in names
    assert "p.event" in names
    assert "thread_name" in names  # metadata present
    inner = next(
        ev for ev in payload["traceEvents"] if ev["name"] == "p.inner"
    )
    assert inner["args"]["trace_id"]
    assert inner["cat"] == "adaptdl"


# ---- exporter: Prometheus --------------------------------------------


def test_trace_prometheus_lines_are_conformant():
    with trace.span("c.phase"):
        pass
    trace.event("c.event")
    parsed = validate_exposition(trace.prometheus_lines())
    families = parsed["families"]
    assert families["adaptdl_trace_phase_seconds"]["type"] == "histogram"
    assert families["adaptdl_trace_events_total"]["type"] == "counter"


def test_rpc_phase_gets_finer_buckets():
    trace.record_span("rpc.request", 0.002)
    trace.record_span("ckpt.write", 0.002)
    text = trace.prometheus_lines()
    assert (
        'adaptdl_trace_phase_seconds_bucket{phase="rpc.request",'
        'le="0.0005"}' in text
    )
    assert (
        'adaptdl_trace_phase_seconds_bucket{phase="ckpt.write",'
        'le="0.0005"}' not in text
    )


def test_prom_builder_escapes_label_values():
    b = trace.PromBuilder()
    b.family("t_metric", "gauge", "test")
    b.sample("t_metric", {"job": 'we"ird\\job\nname'}, 1)
    text = b.render()
    assert r'job="we\"ird\\job\nname"' in text
    parsed = validate_exposition(text)
    ((_, labels, value),) = parsed["families"]["t_metric"]["samples"]
    assert labels["job"] == 'we"ird\\job\nname'
    assert value == 1


def test_prom_builder_rejects_undeclared_family():
    b = trace.PromBuilder()
    with pytest.raises(ValueError):
        b.sample("undeclared_metric", value=1)


def test_conformance_parser_catches_violations():
    with pytest.raises(ConformanceError):  # sample without TYPE
        validate_exposition("orphan_metric 1\n")
    with pytest.raises(ConformanceError):  # no trailing newline
        validate_exposition("# TYPE m gauge\n# HELP m h\nm 1")
    with pytest.raises(ConformanceError):  # raw quote in label
        validate_exposition(
            '# HELP m h\n# TYPE m gauge\nm{a="b"c"} 1\n'
        )
    with pytest.raises(ConformanceError):  # missing HELP
        validate_exposition("# TYPE m gauge\nm 1\n")
    with pytest.raises(ConformanceError):  # non-cumulative buckets
        validate_exposition(
            "# HELP h x\n# TYPE h histogram\n"
            'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n'
            "h_sum 1\nh_count 3\n"
        )
    with pytest.raises(ConformanceError):  # +Inf != _count
        validate_exposition(
            "# HELP h x\n# TYPE h histogram\n"
            'h_bucket{le="1"} 1\nh_bucket{le="+Inf"} 3\n'
            "h_sum 1\nh_count 4\n"
        )


# ---- supervisor: /trace intake + /metrics conformance ----------------


@pytest.fixture
def cluster():
    from adaptdl_tpu.sched.state import ClusterState
    from adaptdl_tpu.sched.supervisor import Supervisor

    state = ClusterState()
    state.create_job("test/traced", spec={"max_replicas": 8})
    supervisor = Supervisor(state)
    url = supervisor.start()
    yield state, supervisor, url
    supervisor.stop()


def test_trace_intake_roundtrip(cluster):
    _state, _sup, url = cluster
    with trace.span("w.phase", job="test/traced"):
        pass
    spans = trace.snapshot_spans()
    r = requests.put(
        f"{url}/trace/test/traced", json={"spans": spans}, timeout=5
    )
    assert r.status_code == 200 and r.json()["accepted"] == 1
    got = requests.get(f"{url}/trace/test/traced", timeout=5).json()
    assert [s["name"] for s in got["spans"]].count("w.phase") == 1
    # Unknown job and malformed bodies are rejected.
    assert (
        requests.put(
            f"{url}/trace/test/nope", json={"spans": spans}, timeout=5
        ).status_code
        == 404
    )
    assert (
        requests.get(f"{url}/trace/test/nope", timeout=5).status_code
        == 404
    )
    assert (
        requests.put(
            f"{url}/trace/test/traced", json={"spans": "x"}, timeout=5
        ).status_code
        == 400
    )


def test_supervisor_metrics_exposition_is_conformant(cluster):
    """THE /metrics conformance gate: a live scrape (jobs, lifecycle,
    rollback gauges, trace histograms, worker-absorbed spans) parses
    under the strict exposition grammar — HELP/TYPE for every series,
    escaped labels, histogram invariants."""
    state, _sup, url = cluster
    state.update(
        "test/traced",
        allocation=["slice-0"] * 2,
        hints={"initBatchSize": 128},
    )
    state.create_job("test/done")
    state.update("test/done", status="Succeeded")
    # Worker-side spans absorbed through the intake path.
    trace.record_span("ckpt.snapshot", 0.01)
    trace.event("aot.miss")
    requests.put(
        f"{url}/trace/test/traced",
        json={"spans": trace.snapshot_spans()},
        timeout=5,
    )
    text = requests.get(f"{url}/metrics", timeout=5).text
    parsed = validate_exposition(text)
    families = parsed["families"]
    # Every pre-existing series family now carries HELP/TYPE...
    for name in (
        "adaptdl_jobs",
        "adaptdl_job_replicas",
        "adaptdl_job_batch_size",
        "adaptdl_job_submissions_total",
        "adaptdl_job_completion_seconds",
        "adaptdl_alloc_epoch",
        "adaptdl_alloc_pending",
        "adaptdl_journal_torn_records_total",
    ):
        assert name in families, name
        assert families[name]["help"], name
    # ...and the graftscope families ride the same exposition.
    assert families["adaptdl_trace_phase_seconds"]["type"] == "histogram"
    phases = {
        labels.get("phase")
        for _, labels, _ in families["adaptdl_trace_phase_seconds"][
            "samples"
        ]
    }
    assert "ckpt.snapshot" in phases


def test_trace_intake_is_idempotent_and_validated(cluster):
    """A worker whose flush response was lost re-sends the same batch
    — the store and the histograms must not double-count; poison
    records (non-numeric dur/ts) bounce as 400 at intake instead of
    500-ing every later GET."""
    _state, _sup, url = cluster
    trace.record_span("idem.phase", 0.01)
    spans = trace.snapshot_spans()
    first = requests.put(
        f"{url}/trace/test/traced", json={"spans": spans}, timeout=5
    )
    assert first.json()["accepted"] == 1
    second = requests.put(
        f"{url}/trace/test/traced", json={"spans": spans}, timeout=5
    )
    assert second.status_code == 200
    assert second.json()["accepted"] == 0  # retry deduplicated
    got = requests.get(f"{url}/trace/test/traced", timeout=5).json()
    assert (
        len([s for s in got["spans"] if s["name"] == "idem.phase"]) == 1
    )
    text = requests.get(f"{url}/metrics", timeout=5).text
    assert (
        'adaptdl_trace_phase_seconds_count{phase="idem.phase"} 1'
        in text
    )
    for poison in (
        {"name": "x", "dur": None},
        {"name": "x", "ts": "later"},
        {"name": ""},
        {"dur": 1.0},
    ):
        r = requests.put(
            f"{url}/trace/test/traced",
            json={"spans": [poison]},
            timeout=5,
        )
        assert r.status_code == 400, poison
    # The job's GET endpoint still works after the poison attempts.
    assert (
        requests.get(f"{url}/trace/test/traced", timeout=5).status_code
        == 200
    )


def test_config_fetch_adopts_decision_traceparent(
    cluster, monkeypatch
):
    """The product path for the doomed incarnation: polling /config
    adopts the current decision's trace context, so the final save
    before the restart records in the rescale's trace."""
    from adaptdl_tpu import sched_hints

    state, _sup, url = cluster
    header = trace.new_traceparent()
    state.update(
        "test/traced",
        allocation=["slice-0"],
        trace_parent=header,
    )
    monkeypatch.setenv("ADAPTDL_SUPERVISOR_URL", url)
    monkeypatch.setenv("ADAPTDL_JOB_ID", "test/traced")
    payload = sched_hints.fetch_job_config()
    assert payload is not None
    assert payload["traceParent"] == header
    assert trace.current_traceparent() == header
    with trace.span("final.save"):
        pass
    (rec,) = [
        r
        for r in trace.snapshot_spans()
        if r["name"] == "final.save"
    ]
    assert rec["trace"] == trace.parse_traceparent(header)[0]


def test_span_ids_are_fork_safe():
    """Forked replicas (the elastic harness launches them with
    os.fork) must not replay the parent's id sequence — identical
    span ids would be deduplicated into span loss at the
    supervisor."""
    import os as _os

    trace.new_traceparent()  # seed the parent's thread-local PRNG
    read_fd, write_fd = _os.pipe()
    pid = _os.fork()
    if pid == 0:  # child
        _os.close(read_fd)
        with _os.fdopen(write_fd, "w") as f:
            f.write(trace.new_traceparent())
        _os._exit(0)
    _os.close(write_fd)
    with _os.fdopen(read_fd) as f:
        child_header = f.read()
    _os.waitpid(pid, 0)
    parent_header = trace.new_traceparent()
    assert trace.parse_traceparent(child_header) is not None
    assert child_header != parent_header


def test_initialize_job_rearm_is_once_per_incarnation(monkeypatch):
    """initialize_job is idempotent: a second call must not re-open
    the restart.first_step window (it would 'measure' an arbitrary
    mid-training interval at the next profiled step)."""
    from adaptdl_tpu import bootstrap

    monkeypatch.setattr(bootstrap, "_restart_span_armed", False)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    monkeypatch.setenv("ADAPTDL_COMPILE_CACHE", "off")
    bootstrap.initialize_job()
    assert trace.end_pending("restart.first_step")
    bootstrap.initialize_job()  # documented-idempotent second call
    assert not trace.end_pending("restart.first_step")


# ---- end-to-end: one rescale = one stitched trace --------------------


class _BlobState(checkpoint.State):
    def __init__(self, name, payload=b"x" * 4096):
        super().__init__(name)
        self.payload = payload

    def save(self, fileobj):
        fileobj.write(self.payload)

    def load(self, fileobj):
        self.payload = fileobj.read()


def test_single_rescale_produces_one_stitched_trace(
    cluster, tmp_path, monkeypatch
):
    """The acceptance path: allocator decision -> epoch prepare ->
    worker save -> restore -> first step, all under ONE trace id,
    retrievable via GET /trace/{job}, rendered by `adaptdl-tpu
    trace`, Perfetto-exportable, with per-phase durations summing to
    within 10% of the observed wall-clock rescale time."""
    import jax
    import jax.numpy as jnp

    from adaptdl_tpu.sched.allocator import Allocator
    from adaptdl_tpu.sched.policy import NodeInfo, PolluxPolicy

    state, _sup, url = cluster
    allocator = Allocator(
        state,
        {"slice-0": NodeInfo(resources={"tpu": 8})},
        policy=PolluxPolicy(pop_size=16, generations=10),
    )
    allocator.optimize_once()
    record = state.get_job("test/traced")
    assert record.allocation, "allocator placed the job"
    assert record.trace_parent, "rescale decision minted a trace"
    trace_id, _ = trace.parse_traceparent(record.trace_parent)
    # /config serves the decision's trace context to the live worker.
    got = requests.get(f"{url}/config/test/traced", timeout=5).json()
    assert got["traceParent"] == record.trace_parent

    # ---- worker side: adopt the context, rescale ----
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_SUPERVISOR_URL", url)
    monkeypatch.setenv("ADAPTDL_JOB_ID", "test/traced")
    assert trace.set_traceparent(got["traceParent"])
    blob = _BlobState("e2e-model")
    wall_start = time.monotonic()
    checkpoint.save_all_states(wait=True)  # ckpt.snapshot + ckpt.write
    blob.unregister()
    blob2 = _BlobState("e2e-model", payload=b"")
    assert checkpoint.load_state(blob2)  # ckpt.restore
    with trace.span("restart.first_step"):
        y = jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64)))
        jax.block_until_ready(y)
    wall = time.monotonic() - wall_start
    assert blob2.payload == blob.payload
    assert trace.flush_to_supervisor()

    # ---- the stitched view ----
    payload = requests.get(f"{url}/trace/test/traced", timeout=5).json()
    spans = payload["spans"]
    by_name = {}
    for rec in spans:
        by_name.setdefault(rec["name"], []).append(rec)
    # Worker spans and supervisor spans share ONE trace id.
    for name in (
        "ckpt.snapshot",
        "ckpt.write",
        "ckpt.restore",
        "restart.first_step",
        "alloc.publish",
        "epoch.prepare",
    ):
        assert name in by_name, (name, sorted(by_name))
        for rec in by_name[name]:
            assert rec["trace"] == trace_id, name
    # Per-phase durations account for the observed wall-clock rescale.
    phase_sum = sum(
        rec["dur"]
        for name in (
            "ckpt.snapshot",
            "ckpt.write",
            "ckpt.restore",
            "restart.first_step",
        )
        for rec in by_name[name]
    )
    assert phase_sum <= wall * 1.10, (phase_sum, wall)
    assert phase_sum >= wall * 0.90, (phase_sum, wall)

    # ---- the CLI renders it and writes a valid Perfetto file ----
    from adaptdl_tpu import cli

    out = tmp_path / "trace.perfetto.json"
    stdout = io.StringIO()
    with redirect_stdout(stdout):
        rc = cli.main(
            [
                "trace",
                "test/traced",
                "--supervisor",
                url,
                "--perfetto",
                str(out),
            ]
        )
    assert rc == 0
    rendered = stdout.getvalue()
    assert trace_id in rendered
    assert "ckpt.restore" in rendered
    assert "per-phase medians" in rendered
    perfetto = json.loads(out.read_text())
    _validate_trace_event_schema(perfetto)
    assert any(
        ev["name"] == "restart.first_step"
        for ev in perfetto["traceEvents"]
    )


# ---- CI gates --------------------------------------------------------


def test_trace_overhead_gate_under_one_percent(monkeypatch):
    """Tracing enabled on the CPU harness step loop: < 1% step-time
    overhead.

    Production's step loop crosses the trace layer exactly once per
    step (the ``end_pending`` restart-span hook in
    ``metrics.profile_step``); spans themselves fire per rescale
    PHASE, never per step. The gate therefore bounds (a) the per-step
    hook cost with tracing enabled against the measured step time —
    the enabled-vs-disabled delta of the real loop — and (b) the
    absolute per-span recording cost, so a regression that makes span
    recording syscall-heavy (urandom per id, fsync per record, env
    reads per record) fails here even though no span sits on the step
    path. Min-of-windows isolates cost floors from scheduler noise; a
    direct A/B wall-clock comparison of the full loop would drown a
    sub-1% effect in multi-percent load noise on a shared box."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("ADAPTDL_TRACE", "on")
    trace._reset_state()

    # (a) the per-step tracing surface, tracing enabled.
    def hook_window(n: int = 20000) -> float:
        start = time.monotonic()
        for _ in range(n):
            trace.end_pending("restart.first_step")
        return (time.monotonic() - start) / n

    hook_cost = min(hook_window() for _ in range(5))

    # (b) span recording cost (the per-PHASE price). ~20us on an idle
    # box; the 500us bound leaves headroom for a contended CI core
    # while still catching the real regression class — per-record
    # syscalls (fsync, urandom), env reads, O(buffer) scans.
    def span_window(n: int = 1500) -> float:
        start = time.monotonic()
        for _ in range(n):
            with trace.span("gate.step"):
                pass
        return (time.monotonic() - start) / n

    span_cost = min(span_window() for _ in range(8))
    assert span_cost < 500e-6, (
        f"span recording costs {span_cost * 1e6:.1f}us"
    )

    # The CPU harness step the hook rides in.
    step = jax.jit(lambda x: x @ x / jnp.linalg.norm(x))
    x = jnp.ones((384, 384), jnp.float32)
    jax.block_until_ready(step(x))

    def step_window(steps: int = 30) -> float:
        y = x
        start = time.monotonic()
        for _ in range(steps):
            y = step(y)
        jax.block_until_ready(y)
        return (time.monotonic() - start) / steps

    step_time = min(step_window() for _ in range(5))
    overhead = hook_cost / step_time
    assert overhead < 0.01, (
        f"per-step tracing overhead {overhead * 100:.4f}% >= 1% "
        f"(hook={hook_cost * 1e6:.2f}us step={step_time * 1e3:.3f}ms)"
    )


# ---- summaries / waterfall -------------------------------------------


def test_phase_summary_medians():
    for dur in (0.1, 0.3, 0.2):
        trace.record_span("s.phase", dur)
    trace.record_span("s.other", 1.0)
    trace.event("s.event")
    summary = trace.phase_summary(trace.snapshot_spans())
    assert summary["s.phase"] == pytest.approx(0.2)
    assert summary["s.other"] == pytest.approx(1.0)
    assert "s.event" not in summary


def test_render_waterfall_orders_and_scales():
    trace.record_span("w.first", 0.2, ts=100.0)
    trace.record_span("w.second", 0.1, ts=100.3)
    text = trace.render_waterfall(trace.snapshot_spans())
    lines = text.splitlines()
    assert lines[0].startswith("PHASE")
    assert lines[1].split()[0] == "w.first"
    assert lines[2].split()[0] == "w.second"
    assert "#" in lines[1]
    assert trace.render_waterfall([]) == "(no spans)"


def test_self_time_is_duration_less_what_children_cover():
    """Two children that overlap each other, one sticking out past
    the parent's end, one grandchild: every span's self time is its
    duration less the union of its children's intervals inside it,
    and the waterfall indents children and prints the column."""
    tp = trace.new_traceparent()
    trace_id, root = trace.parse_traceparent(tp)

    def rec(span, parent, ts, dur):
        return {
            "name": f"self.{span}", "trace": trace_id, "span": span,
            "parent": parent, "ts": ts, "dur": dur, "pid": 1, "inc": 0,
        }

    records = [
        rec("a", root, 100.0, 10.0),
        rec("b", "a", 101.0, 4.0),  # covers [101, 105]
        rec("c", "a", 103.0, 4.0),  # adds (105, 107]
        rec("d", "a", 109.0, 5.0),  # clipped to [109, 110]
        rec("e", "b", 102.0, 1.0),
        {**rec("x", "a", 100.5, 0.0), "kind": "event"},
    ]
    own = trace.self_times(records)
    assert own == pytest.approx(
        {"a": 3.0, "b": 3.0, "c": 4.0, "d": 5.0, "e": 1.0}
    )
    lines = trace.render_waterfall(records).splitlines()
    assert "SELF(ms)" in lines[0]
    assert lines[1].startswith("self.a ")
    assert lines[2].startswith("  self.b ")
    assert lines[3].startswith("    self.e ")
    assert lines[1].split()[2:5] == ["0.00", "10000.00", "3000.00"]


def test_cli_trace_reads_a_journal(tmp_path, monkeypatch):
    """``adaptdl-tpu trace --journal FILE``: a worker's own journal
    renders without a supervisor; with neither source it refuses."""
    from adaptdl_tpu import cli

    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/journaled")
    trace._reset_state()
    with trace.span("journal.outer"):
        trace.record_span("journal.inner", 0.002)
    path = trace.journal_path()
    stdout = io.StringIO()
    with redirect_stdout(stdout):
        rc = cli.main(["trace", "ns/journaled", "--journal", path])
    rendered = stdout.getvalue()
    assert rc == 0
    assert "journal.outer" in rendered and "  journal.inner" in rendered
    assert "x1" in rendered and "total" in rendered
    assert cli.main(["trace", "ns/journaled"]) == 2


def test_cli_trace_counts_the_journals_events(tmp_path, monkeypatch):
    """Point events have no bar in the waterfall; the CLI counts them
    by name under it, so a journal shows how a restart got its
    calibration: ``step.calibrate_reused`` beside ``step.calibrate``."""
    from adaptdl_tpu import cli

    monkeypatch.setenv("ADAPTDL_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_JOB_ID", "ns/events")
    trace._reset_state()
    with trace.span("step.calibrate", atomic_bsz=8):
        pass
    trace.event("step.calibrate_reused", atomic_bsz=16)
    trace.event("step.calibrate_reused", atomic_bsz=32)
    stdout = io.StringIO()
    with redirect_stdout(stdout):
        rc = cli.main(
            ["trace", "ns/events", "--journal", trace.journal_path()]
        )
    assert rc == 0
    spans, _, events = stdout.getvalue().partition("\nevents:\n")
    assert "step.calibrate " in spans
    assert "step.calibrate_reused" not in spans
    assert events.split() == ["step.calibrate_reused", "x2"]


# ---- the restart's dark half: jit.* bridge + set-up spans ------------


def _spans(name):
    return [r for r in trace.snapshot_spans() if r["name"] == name]


def test_record_span_nests_under_the_open_span():
    """An already-measured span parents like ``span`` and ``event``:
    under the thread's innermost open span, so that self time (a
    span's duration less its children's) comes out right."""
    with trace.span("nest.outer"):
        trace.record_span("nest.measured", 0.01)
    trace.record_span("nest.alone", 0.01)
    (outer,) = _spans("nest.outer")
    (measured,) = _spans("nest.measured")
    (alone,) = _spans("nest.alone")
    assert measured["parent"] == outer["span"]
    assert measured["trace"] == outer["trace"]
    assert alone["parent"] == outer["parent"]  # the process root


def test_record_span_explicit_traceparent_wins_over_open_span():
    header = trace.new_traceparent()
    with trace.span("nest.outer"):
        trace.record_span("nest.foreign", 0.01, traceparent=header)
    (foreign,) = _spans("nest.foreign")
    assert (foreign["trace"], foreign["parent"]) == (
        trace.parse_traceparent(header)
    )


def test_pending_span_parents_to_root_not_to_the_closing_span():
    """A pending span was opened at another callsite: the span that
    happens to be open where it is closed (and is shorter than it) is
    not its parent."""
    trace.begin_pending("nest.pending")
    with trace.span("nest.closer"):
        assert trace.end_pending("nest.pending")
    (pending,) = _spans("nest.pending")
    (closer,) = _spans("nest.closer")
    assert pending["parent"] == closer["parent"] != closer["span"]


def test_jax_bridge_records_first_call_phases_once():
    """A jitted function's first call leaves jit.trace, jit.lower and
    jit.compile with ``fun`` set, nested under the open span; the
    functions it calls while tracing (every jnp operation is a jitted
    function) leave nothing; a second call leaves nothing at all."""
    import jax
    import jax.numpy as jnp

    trace.install_jax_bridge()

    @jax.jit
    def bridged_fn(x):
        return jax.nn.gelu(x) + jnp.where(x > 0, x, 0).sum()

    x = jnp.ones((3, 5))  # its own programs: before the bracket
    before = trace.buffer_seq()
    with trace.span("bridge.outer"):
        jax.block_until_ready(bridged_fn(x))
    new = [r for r in trace.snapshot_spans() if r["seq"] > before]
    (outer,) = [r for r in new if r["name"] == "bridge.outer"]
    # (jit.cache_hit / jit.cache_miss events also appear when an
    # earlier test left the persistent compile cache on.)
    phases = [
        r
        for r in new
        if r["name"].startswith("jit.") and r.get("kind") != "event"
    ]
    assert [r["name"] for r in phases] == [
        "jit.trace", "jit.lower", "jit.compile"
    ]
    for rec in phases:
        assert "bridged_fn" in rec["attrs"]["fun"]
        assert rec["parent"] == outer["span"]
        assert rec["dur"] > 0
        assert outer["ts"] <= rec["ts"]
        assert rec["ts"] + rec["dur"] <= outer["ts"] + outer["dur"] + 0.05
    before = trace.buffer_seq()
    jax.block_until_ready(bridged_fn(x))
    assert trace.buffer_seq() == before


def test_jax_bridge_is_idempotent():
    """Installed twice, each phase is still recorded once."""
    import jax
    import jax.numpy as jnp

    trace.install_jax_bridge()
    trace.install_jax_bridge()
    x = jnp.ones(7)  # its own programs: before the bracket
    before = trace.buffer_seq()
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(x))
    names = [
        r["name"]
        for r in trace.snapshot_spans()
        if r["seq"] > before and r.get("kind") != "event"
    ]
    assert names == ["jit.trace", "jit.lower", "jit.compile"]


def test_jax_bridge_off_records_nothing_but_still_counts_cache_hits(
    monkeypatch,
):
    """ADAPTDL_TRACE=off: no span, no event — but the persistent-cache
    hit count is a plain integer kept either way, because
    aot_cache.load_or_compile decides from it whether an executable
    may be serialized."""
    import jax
    import jax.monitoring
    import jax.numpy as jnp

    trace.install_jax_bridge()
    monkeypatch.setenv("ADAPTDL_TRACE", "off")
    trace._reset_state()
    jax.block_until_ready(jax.jit(lambda x: x * 5 - 2)(jnp.ones(9)))
    hits = trace.jax_cache_hits()
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert trace.snapshot_spans() == []
    assert trace.jax_cache_hits() == hits + 1


def test_jax_bridge_cache_events_become_counters():
    import jax.monitoring

    trace.install_jax_bridge()
    hits = trace.jax_cache_hits()
    with trace.span("bridge.outer"):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert trace.jax_cache_hits() == hits + 1
    events = [
        r for r in trace.snapshot_spans() if r.get("kind") == "event"
    ]
    assert [r["name"] for r in events] == [
        "jit.cache_hit", "jit.cache_miss"
    ]
    (outer,) = _spans("bridge.outer")
    assert events[0]["parent"] == outer["span"]
    text = trace.prometheus_lines()
    assert 'adaptdl_trace_events_total{event="jit.cache_hit"} 1' in text


def test_trace_module_imports_without_jax():
    """The control plane imports adaptdl_tpu.trace; the bridge's jax
    import lives inside install_jax_bridge."""
    import subprocess
    import sys

    code = (
        "import sys; import adaptdl_tpu.trace; "
        "assert 'jax' not in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def _tiny_trainer():
    from tests.test_compile_cache import _linear_trainer

    return _linear_trainer()[0]


def _tiny_dataset(n=256):
    import numpy as np

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    return {"x": x, "y": (x @ np.arange(4.0)).astype(np.float32)}


def test_init_state_span_counts_what_it_placed(monkeypatch):
    import jax

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    state = _tiny_trainer().init_state()
    (rec,) = _spans("trainer.init_state")
    leaves = jax.tree.leaves(state)
    assert rec["attrs"]["leaves"] == len(leaves)
    assert rec["attrs"]["bytes"] == sum(x.nbytes for x in leaves) > 0


def test_calibrate_span_times_its_first_call_and_its_best(monkeypatch):
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    trace.install_jax_bridge()
    trainer = _tiny_trainer()
    state = trainer.init_state()
    batch = {k: v[:8] for k, v in _tiny_dataset().items()}
    best = trainer.calibrate_accum_time(state, batch, 8)
    (rec,) = _spans("step.calibrate")
    attrs = rec["attrs"]
    assert attrs["atomic_bsz"] == 8
    assert attrs["best_s"] == best
    assert 0 < attrs["best_s"] <= attrs["first_call_s"] <= rec["dur"]
    # Its program's trace / lower / compile are its children.
    children = [
        r
        for r in trace.snapshot_spans()
        if r["parent"] == rec["span"] and r["name"].startswith("jit.")
    ]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= {
        r["name"] for r in children
    }


def test_goodput_fit_span_counts_its_points(monkeypatch):
    from adaptdl_tpu import metrics

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    metrics._reset_state()
    assert metrics._fit() is None  # nothing profiled: no fit, no span
    assert _spans("goodput.fit") == []
    metrics.profile_accum_time(8, 0.01)
    metrics.profile_step(8, 0, 0.02)
    metrics.profile_step(16, 0, 0.03)
    # profile_step's cadence may have started the adaptdl-fit thread,
    # which leaves a span of its own, on its own thread.
    if metrics._fit_thread is not None:
        metrics._fit_thread.join(60)
    before = trace.buffer_seq()
    assert metrics._fit() is not None
    (rec,) = [r for r in _spans("goodput.fit") if r["seq"] > before]
    assert rec["attrs"]["points"] == 2
    assert rec["tid"] == threading.current_thread().name
    metrics._reset_state()


def test_policy_optimize_span_carries_the_decision(monkeypatch):
    from adaptdl_tpu.data import AdaptiveDataLoader

    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    loader = AdaptiveDataLoader(
        _tiny_dataset(), batch_size=8, name="trace-policy"
    )
    loader._optimize_batch_size()
    (rec,) = _spans("policy.optimize")
    assert rec["attrs"] == {
        "atomic_bsz": loader.current_atomic_bsz,
        "accum_steps": loader.current_accum_steps,
    }
    assert rec["attrs"]["atomic_bsz"] == 8


def test_restore_is_preceded_by_its_verify_span(tmp_path, monkeypatch):
    """load_state hashes the payload against the manifest before it
    deserializes it: that time has its own span, ahead of
    ckpt.restore, with the verdict."""
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    state = _BlobState("verified", b"v" * 8192)
    checkpoint.save_all_states()
    state.payload = b""
    assert checkpoint.load_state(state)
    assert state.payload == b"v" * 8192
    (verify,) = _spans("ckpt.verify")
    (restore,) = _spans("ckpt.restore")
    assert verify["attrs"] == {"state": "verified", "verdict": "ok"}
    assert verify["seq"] < restore["seq"]
    assert verify["parent"] == restore["parent"]


def test_aot_compile_reads_cache_hits_from_the_bridge(
    tmp_path, monkeypatch
):
    """load_or_compile installs the bridge itself when nobody has, its
    aot.compile span says whether jax's persistent cache served the
    compile, and the program's jit.* phases are that span's children."""
    import jax
    import numpy as np

    from adaptdl_tpu import aot_cache

    monkeypatch.setenv("ADAPTDL_AOT_CACHE", str(tmp_path))
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    trainer = _tiny_trainer()
    batch = trainer.shard_batch(
        {k: v[:8] for k, v in _tiny_dataset().items()}
    )
    _, m = trainer.train_step(8, 0)(trainer.init_state(), batch)
    jax.block_until_ready(m["loss"])
    aot_cache.wait_for_writes()
    (rec,) = _spans("aot.compile")
    children = {
        r["name"]
        for r in trace.snapshot_spans()
        if r["parent"] == rec["span"]
    }
    assert {"jit.trace", "jit.lower", "jit.compile"} <= children
    # True only if the cache served it (an earlier test may have left
    # the persistent cache on): the bridge's own event says which.
    assert rec["attrs"]["persistent_cache_hit"] is (
        "jit.cache_hit" in children
    )
    assert np.isfinite(float(m["loss"]))


def test_worker_prologue_lies_inside_restart_first_step(
    tmp_path, monkeypatch
):
    """The worker's path on CPU, in process: initialize_job, a tiny
    trainer, init_state, a loader, steps until restart.first_step
    closes. The set-up spans start and end inside its interval and
    carry its trace id: containment is by time, since a pending span
    is nobody's parent."""
    import jax

    from adaptdl_tpu import bootstrap, epoch, metrics
    from adaptdl_tpu.data import AdaptiveDataLoader

    header = trace.new_traceparent()
    monkeypatch.setenv("ADAPTDL_TRACEPARENT", header)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))
    monkeypatch.setattr(bootstrap, "_restart_span_armed", False)
    trace._reset_state()
    metrics._reset_state()
    bootstrap.initialize_job()
    try:
        trainer = _tiny_trainer()
        state = trainer.init_state()
        loader = AdaptiveDataLoader(
            _tiny_dataset(), batch_size=8, name="trace-prologue"
        )
        for _ in epoch.remaining_epochs_until(1):
            for batch in loader:
                state, m = trainer.run_step(state, batch, loader)
                if _spans("restart.first_step"):
                    break
        jax.block_until_ready(m["loss"])
    finally:
        bootstrap.stop_heartbeat()
        metrics._reset_state()
    (umbrella,) = _spans("restart.first_step")
    start, end = umbrella["ts"], umbrella["ts"] + umbrella["dur"]
    inside = {
        name: _spans(name)
        for name in (
            "bootstrap.init", "trainer.init_state", "step.calibrate",
            "policy.optimize", "jit.lower",
        )
    }
    for name, recs in inside.items():
        assert recs, name
        for rec in recs:
            assert rec["trace"] == umbrella["trace"], name
            assert start - 0.05 <= rec["ts"], name
            assert rec["ts"] + rec["dur"] <= end + 0.05, name
    assert umbrella["trace"] == trace.parse_traceparent(header)[0]


def test_restored_worker_reuses_calibration_inside_restart_first_step(
    tmp_path, monkeypatch
):
    """The twin of the prologue test for a RESTORED job: a second
    incarnation under the same layout registers the metrics state,
    which restores the predecessor's profile, and its first step finds
    the calibration on record. ``step.calibrate_reused`` lies inside
    ``restart.first_step`` and there is no ``step.calibrate`` span."""
    import jax

    from adaptdl_tpu import bootstrap, epoch, metrics
    from adaptdl_tpu.data import AdaptiveDataLoader

    header = trace.new_traceparent()
    monkeypatch.setenv("ADAPTDL_TRACEPARENT", header)
    monkeypatch.setenv("ADAPTDL_NUM_REPLICAS", "1")
    monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(tmp_path))

    def incarnation(restarts):
        monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", str(restarts))
        monkeypatch.setattr(bootstrap, "_restart_span_armed", False)
        checkpoint._reset_registry()
        trace._reset_state()
        metrics._reset_state()
        epoch._reset_state()
        bootstrap.initialize_job()
        try:
            trainer = _tiny_trainer()
            state = trainer.init_state()
            metrics.ensure_checkpoint_registered()
            loader = AdaptiveDataLoader(
                _tiny_dataset(), batch_size=8, name="trace-restored"
            )
            for _ in epoch.remaining_epochs_until(1):
                for batch in loader:
                    state, m = trainer.run_step(state, batch, loader)
                    if _spans("restart.first_step"):
                        checkpoint.save_all_states()
                        break
            jax.block_until_ready(m["loss"])
        finally:
            bootstrap.stop_heartbeat()

    try:
        incarnation(0)
        (measured,) = _spans("step.calibrate")
        assert not _spans("step.calibrate_reused")
        incarnation(1)
    finally:
        metrics._reset_state()
        epoch._reset_state()
    (umbrella,) = _spans("restart.first_step")
    assert umbrella["inc"] == 1
    start, end = umbrella["ts"], umbrella["ts"] + umbrella["dur"]
    assert not _spans("step.calibrate")
    (reused,) = _spans("step.calibrate_reused")
    assert reused["attrs"]["atomic_bsz"] == 8
    assert reused["attrs"]["accum_time_s"] == measured["attrs"]["best_s"]
    assert reused["attrs"]["observations"] == 1
    restored = [r["attrs"]["state"] for r in _spans("ckpt.restore")]
    assert "adaptdl_metrics" in restored
    for rec in [reused, *_spans("ckpt.restore")]:
        assert rec["trace"] == umbrella["trace"], rec["name"]
        assert start - 0.05 <= rec["ts"], rec["name"]
        assert rec["ts"] + rec.get("dur", 0.0) <= end + 0.05, rec["name"]
