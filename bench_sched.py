"""bench_sched: the thousand-job control-plane benchmark.

Two phases, one JSON line (``python bench_sched.py``):

1. **Allocator decision latency at 1k-job steady state.** Builds an
   in-memory ClusterState with 1000 hint-posting jobs over 1250
   slices (10k chips), runs one COLD full Pollux cycle (the
   partitioned search), then measures the incremental path on the
   hints-changed-for-1%-of-jobs scenario: per-cycle p50/p99 plus the
   cold:incremental speedup ratio (the acceptance bar is >= 5x).

2. **Supervisor load.** Starts a real Supervisor over HTTP and
   hammers /heartbeat, /hints, and /discover from simulated worker
   PROCESSES, reporting per-endpoint p50/p99 against SLOs.

Latency numbers are wall-clock medians over enough iterations to be
stable on a noisy CI box; SLOs are deliberately generous for shared
hardware (the trend line across BENCH_r*.json files is the signal).
"""

from __future__ import annotations

import json
import multiprocessing
import sys
import time

from adaptdl_tpu.sched.allocator import Allocator
from adaptdl_tpu.sched.policy import NodeInfo, PolluxPolicy
from adaptdl_tpu.sched.state import ClusterState
from adaptdl_tpu.sim.workload import (
    generate_trace,
    hints_payload,
    percentile as _pct,
    resolve_job,
)

# Per-endpoint p99 SLOs (seconds) for the load phase. Generous for
# shared CI hardware; the supervisor offloads journaled mutations to
# an executor, so these hold with margin on an idle box.
SLOS = {"heartbeat": 0.25, "hints": 0.50, "discover": 0.50}


def bench_allocator(
    jobs: int = 1000,
    slices: int = 1250,
    chips_per_slice: int = 8,
    dirty_fraction: float = 0.01,
    iterations: int = 12,
    seed: int = 42,
) -> dict:
    """Cold full-cycle latency vs incremental-path p50/p99 at steady
    state with ``dirty_fraction`` of jobs posting changed hints."""
    state = ClusterState(state_dir="", alloc_commit_timeout=0.0)
    nodes = {
        f"slice-{i:05d}": NodeInfo(
            resources={"tpu": chips_per_slice}
        )
        for i in range(slices)
    }
    policy = PolluxPolicy(
        pop_size=16, generations=10, util_band=(0.0, 1.0)
    )
    allocator = Allocator(
        state,
        nodes,
        node_template=NodeInfo(resources={"tpu": chips_per_slice}),
        policy=policy,
        # The bench drives full-vs-incremental explicitly: disable
        # the periodic forced full cycle so the steady-state numbers
        # measure the incremental path alone.
        full_every=10**9,
        dirty_threshold=0.5,
    )
    specs = [
        resolve_job(record)
        for record in generate_trace(jobs, 3600.0, seed=seed)
    ]
    for spec in specs:
        state.create_job(
            spec.key,
            spec={
                "min_replicas": 0,
                "max_replicas": spec.max_replicas,
                "resources": {"tpu": 1},
            },
        )
        state.update(
            spec.key, status="Running", hints=hints_payload(spec, profiled=4)
        )
    # Cold: the full (partitioned) search over all 1k jobs.
    t0 = time.monotonic()
    allocator.optimize_once()
    cold_s = time.monotonic() - t0
    # Steady state: each cycle, 1% of jobs post changed hints.
    dirty_n = max(int(jobs * dirty_fraction), 1)
    latencies = []
    for it in range(iterations):
        for k in range(dirty_n):
            spec = specs[(it * dirty_n + k) % len(specs)]
            state.update(
                spec.key,
                hints=hints_payload(spec, profiled=4 + (it % 3)),
            )
        t0 = time.monotonic()
        allocator.optimize_once()
        latencies.append(time.monotonic() - t0)
    metrics = state.alloc_cycle_metrics()
    incr_cycles = metrics["modes"].get("incremental", {}).get(
        "count", 0
    )
    p50 = _pct(latencies, 0.5)
    return {
        "alloc_bench_jobs": jobs,
        "alloc_bench_slots": slices * chips_per_slice,
        "alloc_decide_cold_s": round(cold_s, 4),
        "alloc_decide_p50_s": round(p50, 4),
        "alloc_decide_p99_s": round(_pct(latencies, 0.99), 4),
        "alloc_incremental_cycles": incr_cycles,
        "alloc_incremental_speedup": round(cold_s / max(p50, 1e-9), 1),
    }


def _worker_main(url, job_keys, seconds, out_queue):
    """One simulated worker process: loops heartbeat + hints + a
    discover poll against the live supervisor, timing each request."""
    import requests

    session = requests.Session()
    lat = {"heartbeat": [], "hints": [], "discover": []}
    deadline = time.monotonic() + seconds
    i = 0
    hints = {
        "perfParams": None,
        "gradParams": None,
        "initBatchSize": 128,
    }
    while time.monotonic() < deadline:
        key = job_keys[i % len(job_keys)]
        i += 1
        t0 = time.monotonic()
        session.put(f"{url}/heartbeat/{key}/0?group=0", timeout=10)
        lat["heartbeat"].append(time.monotonic() - t0)
        t0 = time.monotonic()
        session.put(f"{url}/hints/{key}", json=hints, timeout=10)
        lat["hints"].append(time.monotonic() - t0)
        t0 = time.monotonic()
        session.get(
            f"{url}/discover/{key}/0?replicas=1", timeout=10
        )
        lat["discover"].append(time.monotonic() - t0)
    out_queue.put(lat)


def bench_supervisor(
    jobs: int = 50, workers: int = 8, seconds: float = 6.0
) -> dict:
    """Per-endpoint p50/p99 under concurrent simulated-worker load."""
    from adaptdl_tpu.sched.supervisor import Supervisor

    state = ClusterState(state_dir="", alloc_commit_timeout=0.0)
    job_keys = []
    for i in range(jobs):
        key = f"bench/j{i:04d}"
        state.create_job(key, spec={"max_replicas": 4})
        state.update(key, status="Running", allocation=["local"])
        # Pre-register rank 0 so /discover resolves instantly instead
        # of long-polling the whole load window.
        state.register_worker(key, 0, 0, "127.0.0.1:0")
        job_keys.append(key)
    supervisor = Supervisor(state, lease_ttl=60.0)
    url = supervisor.start()
    ctx = multiprocessing.get_context("fork")
    queue = ctx.Queue()
    procs = [
        ctx.Process(
            target=_worker_main,
            args=(url, job_keys[w::workers] or job_keys, seconds, queue),
            daemon=True,
        )
        for w in range(workers)
    ]
    for proc in procs:
        proc.start()
    merged = {"heartbeat": [], "hints": [], "discover": []}
    for _ in procs:
        lat = queue.get(timeout=seconds * 5 + 60)
        for endpoint, values in lat.items():
            merged[endpoint].extend(values)
    for proc in procs:
        proc.join(timeout=30)
    supervisor.stop()
    out = {"sched_load_workers": workers, "sched_load_seconds": seconds}
    slo_ok = True
    for endpoint, values in merged.items():
        p99 = _pct(values, 0.99)
        out[f"sched_{endpoint}_p50_s"] = round(_pct(values, 0.5), 5)
        out[f"sched_{endpoint}_p99_s"] = round(p99, 5)
        out[f"sched_{endpoint}_rps"] = round(
            len(values) / max(seconds, 1e-9), 1
        )
        slo_ok = slo_ok and p99 <= SLOS[endpoint]
    out["sched_slo_ok"] = slo_ok
    return out


def _sharded_worker_main(url, job_keys, seconds, out_queue):
    """One simulated worker process hammering the ROUTER: heartbeat +
    hints + config + discover, per-request latency recorded."""
    import requests

    session = requests.Session()
    lat = {"heartbeat": [], "hints": [], "config": [], "discover": []}
    deadline = time.monotonic() + seconds
    i = 0
    hints = {
        "perfParams": None,
        "gradParams": None,
        "initBatchSize": 128,
    }
    while time.monotonic() < deadline:
        key = job_keys[i % len(job_keys)]
        i += 1
        t0 = time.monotonic()
        session.put(f"{url}/heartbeat/{key}/0?group=0", timeout=10)
        lat["heartbeat"].append(time.monotonic() - t0)
        t0 = time.monotonic()
        session.put(f"{url}/hints/{key}", json=hints, timeout=10)
        lat["hints"].append(time.monotonic() - t0)
        t0 = time.monotonic()
        session.get(f"{url}/config/{key}", timeout=10)
        lat["config"].append(time.monotonic() - t0)
        t0 = time.monotonic()
        session.get(
            f"{url}/discover/{key}/0?replicas=1", timeout=10
        )
        lat["discover"].append(time.monotonic() - t0)
    out_queue.put(lat)


def bench_sharded(
    shard_counts: tuple = (1, 2, 4),
    jobs_per_shard: int = 25,
    workers: int = 8,
    seconds: float = 4.0,
) -> dict:
    """The graftshard scaling arm: per-endpoint p50/p99 through the
    router at 1, 2, and 4 supervisor shards, with TOTAL job count
    scaling with the shard count — the single-process ceiling is what
    sharding removes, so the signal is the per-endpoint p99 staying
    flat (<= 1.2x the single-shard p99) while the job count scales
    past it."""
    from adaptdl_tpu.sched.router import Router
    from adaptdl_tpu.sched.shard import ShardedCluster

    out: dict = {"sched_shard_counts": list(shard_counts)}
    p99s: dict[int, dict[str, float]] = {}
    for count in shard_counts:
        cluster = ShardedCluster(
            count,
            lease_ttl=60.0,
            sweep_interval=3600.0,
            state_kwargs={"alloc_commit_timeout": 0.0},
        )
        shard_map = cluster.start()
        router = Router(shard_map)
        url = router.start()
        job_keys = []
        for i in range(jobs_per_shard * count):
            key = f"t{i:04d}/j0"
            shard = cluster.shard_for(key)
            shard.state.create_job(key, spec={"max_replicas": 4})
            shard.state.update(
                key, status="Running", allocation=["local"]
            )
            shard.state.register_worker(key, 0, 0, "127.0.0.1:0")
            job_keys.append(key)
        ctx = multiprocessing.get_context("fork")
        queue = ctx.Queue()
        procs = [
            ctx.Process(
                target=_sharded_worker_main,
                args=(
                    url,
                    job_keys[w::workers] or job_keys,
                    seconds,
                    queue,
                ),
                daemon=True,
            )
            for w in range(workers)
        ]
        for proc in procs:
            proc.start()
        merged = {
            "heartbeat": [],
            "hints": [],
            "config": [],
            "discover": [],
        }
        for _ in procs:
            lat = queue.get(timeout=seconds * 5 + 60)
            for endpoint, values in lat.items():
                merged[endpoint].extend(values)
        for proc in procs:
            proc.join(timeout=30)
        router.stop()
        cluster.stop()
        p99s[count] = {}
        for endpoint, values in merged.items():
            p99 = _pct(values, 0.99)
            p99s[count][endpoint] = p99
            out[f"sched_shard{count}_{endpoint}_p50_s"] = round(
                _pct(values, 0.5), 5
            )
            out[f"sched_shard{count}_{endpoint}_p99_s"] = round(
                p99, 5
            )
            out[f"sched_shard{count}_{endpoint}_rps"] = round(
                len(values) / max(seconds, 1e-9), 1
            )
    # The acceptance bar: at the highest shard count (job count
    # scaled by the same factor), every endpoint's p99 stays within
    # 1.2x of the single-shard p99.  Sub-SLO tails are exempt from
    # the relative bound — with ~10^2 samples a p99 is nearly a max,
    # so a few-ms GC blip would flap the gate without the absolute
    # floor; a real serialization blowup still trips it.
    base = p99s.get(min(shard_counts), {})
    top = p99s.get(max(shard_counts), {})
    flat_ok = all(
        top[endpoint]
        <= max(1.2 * base[endpoint], SLOS.get(endpoint, 0.25))
        for endpoint in top
    )
    out["sched_shard_p99_flat_ok"] = flat_ok
    return out


def _reshard_worker_main(url, job_keys, seconds, out_queue):
    """One simulated worker hammering the router's hot path DURING a
    live migration: per-request latency plus a steps-lost counter —
    any request that doesn't come back 200 after the router's own
    stale-map/409 handling is a training step the worker would have
    lost."""
    import requests

    session = requests.Session()
    lat: list[float] = []
    errors = 0
    hints = {
        "perfParams": None,
        "gradParams": None,
        "initBatchSize": 128,
    }
    deadline = time.monotonic() + seconds
    i = 0
    while time.monotonic() < deadline:
        key = job_keys[i % len(job_keys)]
        i += 1
        for request_fn in (
            lambda: session.put(
                f"{url}/heartbeat/{key}/0?group=0", timeout=10
            ),
            lambda: session.put(
                f"{url}/hints/{key}", json=hints, timeout=10
            ),
            lambda: session.get(f"{url}/config/{key}", timeout=10),
        ):
            t0 = time.monotonic()
            try:
                ok = request_fn().status_code == 200
            except requests.RequestException:
                ok = False
            lat.append(time.monotonic() - t0)
            if not ok:
                errors += 1
    out_queue.put({"lat": lat, "errors": errors})


def bench_reshard(
    jobs: int = 20, workers: int = 4, seconds: float = 4.0
) -> dict:
    """The live-resharding arm: hammer the worker hot path through
    the router while tenants live-migrate between two shards, and
    compare the p99 against an identical no-migration run. The gate:
    migration-window p99 <= 1.5x the no-migration baseline (with the
    absolute SLO floor, same rationale as the sharded arm), plus the
    steps-lost count — requests the router could not land even after
    its stale-map/409 re-forwarding."""
    import os
    import shutil
    import tempfile

    from adaptdl_tpu import rpc
    from adaptdl_tpu.sched.router import Router
    from adaptdl_tpu.sched.shard import ShardedCluster, migrate_tenant

    arms: dict[str, dict] = {}
    for arm in ("baseline", "migrate"):
        tmp = tempfile.mkdtemp(prefix="adaptdl-bench-reshard-")
        map_path = os.path.join(tmp, "shardmap.json")
        cluster = ShardedCluster(
            2,
            lease_ttl=60.0,
            sweep_interval=3600.0,
            state_kwargs={"alloc_commit_timeout": 0.0},
            map_path=map_path,
        )
        shard_map = cluster.start()
        router = Router(shard_map, map_path=map_path)
        url = router.start()
        job_keys = []
        for i in range(jobs):
            key = f"t{i:04d}/j0"
            shard = cluster.shard_for(key)
            shard.state.create_job(key, spec={"max_replicas": 4})
            shard.state.update(
                key, status="Running", allocation=["local"]
            )
            shard.state.register_worker(key, 0, 0, "127.0.0.1:0")
            job_keys.append(key)
        ctx = multiprocessing.get_context("fork")
        queue = ctx.Queue()
        procs = [
            ctx.Process(
                target=_reshard_worker_main,
                args=(
                    url,
                    job_keys[w::workers] or job_keys,
                    seconds,
                    queue,
                ),
                daemon=True,
            )
            for w in range(workers)
        ]
        for proc in procs:
            proc.start()
        migrated = 0
        if arm == "migrate":
            # Let the hammer reach steady state, then live-migrate a
            # quarter of the tenants mid-window — each one streams,
            # fences, verifies, and flips while its own traffic is in
            # flight.
            time.sleep(seconds * 0.25)
            current = cluster.map
            for key in job_keys[: max(jobs // 4, 1)]:
                tenant = key.split("/", 1)[0]
                src = current.assign(key)
                current = migrate_tenant(
                    current,
                    tenant,
                    src,
                    1 - src,
                    map_path=map_path,
                    client=rpc.default_client(),
                )
                cluster.map = current
                migrated += 1
        lat: list[float] = []
        errors = 0
        for _ in procs:
            got = queue.get(timeout=seconds * 5 + 60)
            lat.extend(got["lat"])
            errors += got["errors"]
        for proc in procs:
            proc.join(timeout=30)
        router.stop()
        cluster.stop()
        shutil.rmtree(tmp, ignore_errors=True)
        arms[arm] = {
            "lat": lat, "errors": errors, "migrated": migrated,
        }
    base_p99 = _pct(arms["baseline"]["lat"], 0.99)
    mig_p99 = _pct(arms["migrate"]["lat"], 0.99)
    return {
        "sched_reshard_migrations": arms["migrate"]["migrated"],
        "sched_reshard_baseline_p99_s": round(base_p99, 5),
        "sched_reshard_p99_s": round(mig_p99, 5),
        "sched_reshard_steps_lost": arms["migrate"]["errors"],
        "sched_reshard_p99_ok": (
            mig_p99 <= max(1.5 * base_p99, SLOS["heartbeat"])
        ),
    }


def collect(quick: bool = False) -> dict:
    """Everything on one dict."""
    out = {}
    out.update(
        bench_allocator(jobs=200, slices=250, iterations=6)
        if quick
        else bench_allocator()
    )
    out.update(
        bench_supervisor(jobs=20, workers=4, seconds=3.0)
        if quick
        else bench_supervisor()
    )
    out.update(
        bench_sharded(
            shard_counts=(1, 2), jobs_per_shard=10, workers=4,
            seconds=2.0,
        )
        if quick
        else bench_sharded()
    )
    out.update(
        bench_reshard(jobs=8, workers=2, seconds=2.0)
        if quick
        else bench_reshard()
    )
    return out


if __name__ == "__main__":
    print(json.dumps(collect(quick="--quick" in sys.argv)))
