"""The watchgate (``make watchgate`` / the watchgate CI job):
graftwatch's acceptance bar from docs/observability.md.

Fast tier: (a) watch sampling costs < 1% of allocator cycle time on
the CPU harness, (b) the committed smoke trace replayed through the
REAL scheduler emits a bit-identical per-tenant fairness/drift
summary across two fixed-seed runs. Slow tier: the same
bit-identicality on the committed 1k-job / 10k-slot trace.
"""

from __future__ import annotations

import os
import time

import pytest

from adaptdl_tpu.sched.allocator import Allocator
from adaptdl_tpu.sched.policy import NodeInfo, PolluxPolicy
from adaptdl_tpu.sched.state import ClusterState
from adaptdl_tpu.sim import load_trace, run_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "traces", "smoke-32.jsonl")
TRACE_1K = os.path.join(REPO, "traces", "pollux-1k.jsonl")

HINTS = {
    "initBatchSize": 128,
    "localBszBounds": [64, 256],
    "maxBatchSize": 1280,
    "maxProfiledReplicas": 4,
    "gradientAccumulation": True,
    "gradParams": {"sqr": 0.00136, "var": 0.000502},
    "perfParams": {
        "alpha_c": 0.121,
        "beta_c": 0.00568,
        "alpha_n": 0.0236,
        "beta_n": 0.00634,
        "alpha_r": 0.0118,
        "beta_r": 0.00317,
        "gamma": 1.14,
    },
}


def test_watch_sampling_overhead_under_one_percent(monkeypatch):
    """The per-cycle goodput sample (predicted/ideal evaluations,
    tenant aggregation, ring appends) must cost < 1% of the allocator
    cycle it rides on — observability that taxes the decision loop
    is observability that gets turned off. Priced in this thread's
    CPU time: beside five other workers a wall clock also counts the
    time the thread was not running, and twelve ~5 ms samples are few
    enough for one long wait to read as overhead."""
    state = ClusterState()
    for i in range(6):
        key = f"t{i % 3}/job{i}"
        state.create_job(
            key, spec={"max_replicas": 8, "requested": 4}
        )
        state.update(key, status="Running", hints=dict(HINTS))
        state.observe_measured(key, 40.0 + i)
    nodes = {
        f"slice-{i:02d}": NodeInfo(resources={"tpu": 4})
        for i in range(8)
    }
    allocator = Allocator(
        state,
        nodes,
        policy=PolluxPolicy(pop_size=32, generations=20),
        interval=1000.0,
        # Every cycle runs the REAL full Pollux search: the gate
        # prices sampling against decision work, not against
        # incremental pass-through cycles that decide nothing.
        full_every=1,
    )
    cpu = {"sample": 0.0, "cycle": 0.0}

    def on_this_thread(part, fn):
        def timed(*args, **kwargs):
            start = time.thread_time()
            try:
                return fn(*args, **kwargs)
            finally:
                cpu[part] += time.thread_time() - start

        return timed

    # The same two intervals the store's own (wall) counters cover:
    # ``sample_cycle`` whole, and the cycle up to the sample.
    monkeypatch.setattr(
        state.watch, "sample_cycle",
        on_this_thread("sample", state.watch.sample_cycle),
    )
    monkeypatch.setattr(
        allocator, "_optimize_once_traced",
        on_this_thread("cycle", allocator._optimize_once_traced),
    )
    for _ in range(12):
        allocator.optimize_once()
    overhead = state.watch.snapshot()["overhead"]
    assert overhead["cycleS"] > 0 and overhead["sampleS"] > 0
    assert cpu["cycle"] > 0 and cpu["sample"] > 0
    ratio = cpu["sample"] / cpu["cycle"]
    assert ratio < 0.01, (
        f"watch sampling cost {ratio:.2%} of allocator cycle time "
        f"(sample {cpu['sample']:.4f}s over cycle {cpu['cycle']:.4f}s "
        f"of this thread's CPU time; on the wall clock "
        f"{overhead['sampleS']:.4f}s over {overhead['cycleS']:.4f}s)"
    )


@pytest.fixture(scope="module")
def smoke_runs():
    records = load_trace(SMOKE)
    kwargs = dict(
        slices=8, chips_per_slice=8, seed=7, interval=30.0
    )
    return (
        run_trace(records, **kwargs),
        run_trace(records, **kwargs),
    )


def test_smoke_fairness_drift_summary_bit_identical(smoke_runs):
    first, second = smoke_runs
    assert first.watch_summary_json() == second.watch_summary_json()


def test_smoke_watch_summary_has_tenant_curves(smoke_runs):
    first, _ = smoke_runs
    summary = first.watch_summary()
    assert summary["samples"] > 0
    # Tenants are workload categories; the smoke trace carries
    # several, each with share/rho/burn aggregates.
    assert len(summary["tenants"]) >= 2
    for agg in summary["tenants"].values():
        assert 0.0 <= agg["shareMean"] <= 1.0
        assert agg["samples"] > 0
    assert summary["cluster"]["utilMax"] <= 1.0
    assert summary["drift"]["jobsTracked"] > 0


def test_smoke_explain_stream_covers_jobs(smoke_runs):
    """The sim's allocator cycles leave provenance for the simulated
    jobs — the identical record stream a live cluster emits."""
    first, _ = smoke_runs
    watch = first._sim.state.watch
    explained = [
        key
        for key in first.jobs
        if watch.explain_for(key) is not None
    ]
    assert len(explained) >= len(first.jobs) // 2
    record = watch.explain_for(explained[0])
    assert record["latest"]["mode"] in ("full", "incremental")


@pytest.mark.slow
def test_watchgate_1k_fairness_drift_bit_identical():
    """Acceptance: a fixed-seed 1k-job sim run emits a bit-identical
    per-tenant fairness/drift time series (summary form) across two
    runs."""
    records = load_trace(TRACE_1K)
    kwargs = dict(
        slices=1250, chips_per_slice=8, seed=42, interval=60.0
    )
    first = run_trace(records, **kwargs)
    second = run_trace(records, **kwargs)
    assert first.watch_summary_json() == second.watch_summary_json()
    summary = first.watch_summary()
    assert len(summary["tenants"]) >= 4
    assert summary["drift"]["jobsTracked"] > 100
