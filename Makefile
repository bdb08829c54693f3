# Dev loop (reference analog: Makefile build/push/deploy targets).

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test test-fast dryrun examples lint graftcheck chaos chaos-sched chaos-preempt guardgate trace-gate rescale-fast meshgate simgate watchgate warmgate shardgate bench-sched

test:
	$(PY) -m pytest tests/ -x -q

test-fast:
	$(PY) -m pytest tests/ -x -q --deselect tests/test_local_runner.py \
	    --deselect tests/test_multi_runner.py

dryrun:
	$(CPU_ENV) $(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); import __graft_entry__ as g; g.dryrun_multichip(8)"

examples:
	$(PY) examples/linear_regression.py --cpu --epochs 3
	$(PY) tutorial/mnist_step_5.py --cpu --epochs 2

# Full invariant lint: bytecode-compiles everything, then runs the
# graftcheck passes (docs/static-analysis.md) in --fast smoke mode
# (per-file cache; a warm run is sub-second, cold a few seconds —
# CI budget <8s with the whole-program GC12xx-GC14xx families aboard,
# see test_package_is_clean_or_baselined). The same analysis is also
# available as `adaptdl-tpu check`. The baseline must stay EMPTY:
# findings get fixed, not deferred.
lint:
	$(PY) -m compileall -q adaptdl_tpu examples tutorial tests __graft_entry__.py tools
	$(PY) -m tools.graftcheck --fast adaptdl_tpu
	$(PY) -c "import json,sys; b=json.load(open('graftcheck_baseline.json')); sys.exit('graftcheck_baseline.json must stay empty: fix findings instead of baselining them' if b.get('findings') else 0)"

# Cold, cache-free analysis (what CI's lint job runs).
graftcheck:
	$(PY) -m tools.graftcheck adaptdl_tpu

# The chaos suite (docs/robustness.md): seeded fault schedules through
# every injection point — kill-during-save, RPC drop/latency,
# supervisor blackout, payload corruption, runner retry budgets.
# Fixed seed so a failure replays exactly.
chaos:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_chaos.py -q --durations=10

# Durable-supervisor / transactional-rescale chaos: journal crash
# consistency (supervisor hard-killed mid-journal-write), recovery +
# worker reattach with zero job restarts, commit-timeout rollback,
# slot strikes/quarantine. Same fixed seed as `chaos`.
chaos-sched:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_chaos_sched.py -q --durations=10

# Preemption-survival chaos (docs/robustness.md "Preemption
# survival"): fault-injected reclaim notice through the real
# listener with loss equality vs the undisturbed run + one trace id
# across notice/drain/first-step, supervisor 500s on the report, VM
# killed mid-drain-save, supervisor hard-killed mid-drain. Same
# fixed seed as `chaos`.
chaos-preempt:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_chaos_preempt.py -q --durations=10

# graftguard gate (docs/robustness.md "Numeric-health guard"): an
# injected NaN gradient at a fixed step (seed 1234) must roll the run
# back to the last good-marked checkpoint and finish BIT-equal to an
# undisturbed run that skipped the poisoned batch; slot-pinned
# corruption must quarantine exactly the offending slot (same data
# across slots blames the data instead); incident records must
# survive a supervisor hard-kill + journal replay bit-identically;
# and the worker's incident report must retry through a supervisor
# 500. Same fixed seed as `chaos`.
guardgate:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_chaos_guard.py -q --durations=10

# graftscope gates (docs/observability.md): tracing on vs off on the
# CPU harness step loop must cost < 1% step time, the span ring
# buffer must stay bounded under a multi-threaded hammer, and the
# supervisor's /metrics must pass the exposition-format conformance
# parser.
trace-gate:
	$(CPU_ENV) $(PY) -m pytest tests/test_trace.py -q \
	    -k "overhead or bounded or conformant" --durations=5

# Sub-second-rescale gate (docs/checkpointing.md "Peer-to-peer
# handoff"): the planned-rescale path must restore entirely from the
# predecessor's shard server — handoff spans recorded, ZERO
# checkpoint-storage reads (no ckpt.restore span, empty storage dir)
# — and every delta-chain / fallback correctness property must hold.
rescale-fast:
	$(CPU_ENV) $(PY) -m pytest tests/test_delta_handoff.py \
	    -q --durations=5

# Mesh-shape elasticity gate (docs/checkpointing.md "Reshard-aware
# handoff", docs/scheduler.md "Mesh-shape search"): a sharded trainer
# rescaled across a parallelism change on the CPU harness restores
# BIT-identically (durable + peer-to-peer paths, incl. the slow e2e
# tier-1 skips), a range-pulling successor's handoff bytes ~ its
# shard fraction, the AOT cache never serves a wrong-shape
# executable, and dp-only policy outputs stay bit-identical.
meshgate:
	$(CPU_ENV) $(PY) -m pytest tests/test_meshgate.py \
	    tests/test_mesh_reshard.py tests/test_mesh_equivalence.py \
	    -q --durations=5

# graftsim gate (docs/simulator.md): the committed 1k-job / 10k-slot
# trace through the REAL scheduler under a virtual clock — the
# deterministic summary must be bit-identical across two same-seed
# runs and simulated-goodput retention vs the fixed-allocation
# baseline must hold >= 1.0, inside the wall budget.
simgate:
	$(CPU_ENV) $(PY) -m pytest tests/test_simgate.py -q --durations=5

# graftwatch gate (docs/observability.md "Goodput accounting &
# decision provenance"): watch sampling must cost < 1% of allocator
# cycle time on the CPU harness, ring stores stay bounded under a
# multi-threaded hammer, explain records are bit-identical across
# fixed-seed cycles (full AND incremental paths), and the sim-driven
# per-tenant fairness/drift summary is bit-identical across two
# fixed-seed runs (the 1k-job version rides the slow tier).
watchgate:
	$(CPU_ENV) $(PY) -m pytest tests/test_watch.py \
	    tests/test_watchgate.py -q --durations=5

# Zero-downtime-rescale gate (docs/scheduler.md "Speculative
# warm-up", docs/checkpointing.md "Differential shard encoding"): a
# fixed-seed planned rescale with warm-up ON must cut over to the
# pre-warmed successor with steps_lost == 0 and ZERO ckpt.restore
# storage spans (pure differential peer-pull), the differential pull
# must move strictly fewer bytes than a full pull, and every
# speculation failure (spawn fault, successor killed mid-warm-up,
# mispredicted/rolled-back candidate, incumbent crash before cutover)
# must fall back loss-equal to the cold planned path.
warmgate:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_warm_rescale.py -q --durations=10

# graftshard gate (docs/scheduler.md "Sharded control plane"): one
# supervisor shard hard-killed mid-traffic (fixed seed) — zero job
# restarts anywhere, sibling shards' endpoints never degrade, the
# recovered shard replays its exact acknowledged journal prefix, and
# the router's per-shard circuit isolates the dead shard without
# touching siblings. Also the live-resharding chaos suite
# (docs/scheduler.md "Live resharding"): 2→3 grow and 3→2 drain
# under live worker traffic with zero restarts, plus source /
# destination / coordinator killed at every registered reshard.*
# fault point — each either resumes from the destination's acked
# watermark or rolls back with the old shard authoritative.
shardgate:
	$(CPU_ENV) ADAPTDL_FAULT_SEED=1234 $(PY) -m pytest \
	    tests/test_chaos_shard.py -q --durations=10

# Thousand-job control-plane bench: allocator decide p50/p99 at 1k
# jobs / 10k slots + supervisor per-endpoint p99s under load.
bench-sched:
	$(CPU_ENV) $(PY) bench_sched.py
