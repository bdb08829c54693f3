"""Command-line interface: submit / ls / logs / cp / tensorboard.

The reference ships ``adaptdl`` with submit (docker build + CRD
create), logs, ls, cp, and tensorboard management against Kubernetes
(reference: cli/bin/adaptdl:133-396, cli/adaptdl_cli/*). This CLI
keeps the same verb surface with two backends:

- **local** (default, fully functional): jobs run under the
  :class:`~adaptdl_tpu.sched.local_runner.LocalElasticRunner` on this
  machine's chips; job state is queried from the runner's supervisor.
- **k8s**: ``submit --backend k8s`` emits an AdaptDLJob manifest for
  the GKE operator (see adaptdl_tpu/sched/k8s/) and applies it with
  kubectl when available — no in-cluster docker registry dance;
  images come from Artifact Registry. The data-plane verbs ride
  kubectl too: ``logs JOB`` streams every pod of a job by the
  operator's label selector, ``cp ns/job:path dst`` extracts files
  from the checkpoint PVC through a short-lived helper pod, and
  ``tensorboard attach`` port-forwards a managed instance locally
  (reference: cli/bin/adaptdl:234-318, cli/adaptdl_cli/
  tensorboard.py:24-120).

Usage:
    adaptdl-tpu submit train.py --checkpoint-dir /ckpt [--chips N]
    adaptdl-tpu ls --supervisor http://HOST:PORT
    adaptdl-tpu status --supervisor http://HOST:PORT
    adaptdl-tpu trace ns/job --supervisor http://HOST:PORT \
        --perfetto out.json
    adaptdl-tpu logs default/my-job -f        # cluster pods
    adaptdl-tpu logs --log-file /ckpt/job.log # local file
    adaptdl-tpu cp default/my-job:checkpoint-3.0 ./out   # from PVC
    adaptdl-tpu cp /ckpt/checkpoint-3.0/model ./model.bin
    adaptdl-tpu tensorboard attach --name exp1 --port 6006
    adaptdl-tpu tensorboard --logdir /shared
"""

from __future__ import annotations

import argparse
import collections
import json
import shutil
import subprocess
import sys


def _cmd_submit(args) -> int:
    from adaptdl_tpu.sched.validator import validate_job_spec

    validate_job_spec(
        {
            "min_replicas": args.min_replicas,
            "max_replicas": args.max_replicas or 8,
        }
    )
    if args.build is not None and args.backend != "k8s":
        print(
            "--build requires --backend k8s (local submit runs the "
            "script in place; no image is involved)",
            file=sys.stderr,
        )
        return 1
    if args.backend == "k8s":
        from adaptdl_tpu.sched.k8s import render_job_manifest

        image = args.image
        if args.build is not None:
            # One command from source tree to running job (reference:
            # cli/bin/adaptdl:133-231): build the context, push it,
            # and digest-pin the manifest.
            if not args.registry:
                print(
                    "--build requires --registry (e.g. "
                    "us-docker.pkg.dev/PROJECT/REPO)",
                    file=sys.stderr,
                )
                return 1
            from adaptdl_tpu.sched.k8s.images import (
                build_and_push,
                planned_ref,
            )

            if args.dry_run:
                # A dry run mutates NOTHING (no build, no push, no
                # registry state) — render with the content-addressed
                # ref the real submit would produce.
                image = planned_ref(
                    args.build,
                    args.registry,
                    args.name or "adaptdl-job",
                    dockerfile=args.dockerfile,
                )
                print(f"dry run: would push {image}", file=sys.stderr)
            else:
                image = build_and_push(
                    args.build,
                    args.registry,
                    args.name or "adaptdl-job",
                    dockerfile=args.dockerfile,
                )
                print(f"pushed {image}", file=sys.stderr)

        manifest = render_job_manifest(
            name=args.name or "adaptdl-job",
            script=args.script,
            image=image,
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas or 8,
            checkpoint_claim=args.checkpoint_claim,
        )
        if shutil.which("kubectl") and not args.dry_run:
            proc = subprocess.run(
                ["kubectl", "apply", "-f", "-"],
                input=manifest.encode(),
            )
            return proc.returncode
        print(manifest)
        return 0

    from adaptdl_tpu.sched.local_runner import (
        LocalElasticRunner,
        count_local_chips,
    )

    # The launcher itself stays off the JAX backend: the worker it
    # starts needs the chip, and a chip serves one process at a time.
    chips = args.chips if args.chips is not None else count_local_chips()
    extra_env = {}
    if args.log_file:
        # The runner inherits stdio; redirect ourselves when asked.
        log = open(args.log_file, "ab", buffering=0)
        import os

        os.dup2(log.fileno(), 1)
        os.dup2(log.fileno(), 2)
    runner = LocalElasticRunner(
        args.script,
        num_chips=chips,
        checkpoint_dir=args.checkpoint_dir,
        job_name=args.name or "default/cli-job",
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        extra_env=extra_env,
    )
    return runner.run()


def _age(creation_ts: str) -> str:
    """k8s-style humanized age from an ISO creationTimestamp."""
    import datetime

    try:
        created = datetime.datetime.fromisoformat(
            creation_ts.replace("Z", "+00:00")
        )
    except (ValueError, AttributeError):
        return "?"
    delta = (
        datetime.datetime.now(datetime.timezone.utc) - created
    ).total_seconds()
    if delta < 0:
        return "0s"
    for unit, width in (("d", 86400), ("h", 3600), ("m", 60)):
        if delta >= width:
            return f"{int(delta // width)}{unit}"
    return f"{int(delta)}s"


def _cmd_ls(args) -> int:
    if args.backend == "k8s":
        return _ls_k8s(args)
    if not args.supervisor:
        print(
            "ls: --supervisor URL required (or use --backend k8s)",
            file=sys.stderr,
        )
        return 2
    from adaptdl_tpu import rpc

    text = rpc.default_client().get(
        f"{args.supervisor}/metrics",
        endpoint="cli/metrics",
        timeout=10,
        attempts=3,
        deadline=30.0,
    ).text
    print(text, end="")
    return 0


def _ls_k8s(args) -> int:
    """Job table straight off the AdaptDLJob CRD — name / phase /
    replicas / restarts / age, the reference's ls columns (reference:
    cli/bin/adaptdl:321-396 renders the same fields from its CRD) —
    so cluster jobs are listable without supervisor reachability
    (the operator publishes status each reconcile,
    sched/k8s/operator.py Operator._publish_status)."""
    if not _require_kubectl():
        return 1
    proc = subprocess.run(
        [
            "kubectl", "get", "adaptdljobs",
            "-n", args.namespace, "-o", "json",
        ],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        print(proc.stderr.strip(), file=sys.stderr)
        return proc.returncode
    try:
        items = json.loads(proc.stdout or "{}").get("items", [])
    except json.JSONDecodeError:
        print("ls: unparseable kubectl output", file=sys.stderr)
        return 1
    rows = [("NAME", "PHASE", "REPLICAS", "RESTARTS", "AGE")]
    for obj in items:
        meta = obj.get("metadata", {})
        status = obj.get("status", {}) or {}
        rows.append(
            (
                meta.get("name", "?"),
                str(status.get("phase", "Pending")),
                str(status.get("replicas", 0)),
                str(status.get("restarts", 0)),
                _age(meta.get("creationTimestamp", "")),
            )
        )
    _print_table(rows)
    return 0


def _print_table(rows: list[tuple]) -> None:
    widths = [
        max(len(row[col]) for row in rows)
        for col in range(len(rows[0]))
    ]
    for row in rows:
        print(
            "  ".join(
                cell.ljust(width) for cell, width in zip(row, widths)
            ).rstrip()
        )


def _cmd_status(args) -> int:
    """Operator view of a live supervisor: per-job phase with the
    degraded/draining flags, allocation epoch/state (pending = a
    transactional rescale awaiting its commit quorum), and lease ages
    — plus slot strikes/quarantine, reclaim-notice drain state with
    per-kind hazard rates, and recovery info, so the reason an
    allocation was withdrawn, rolled back, or moved off spot is
    visible instead of implied."""
    from adaptdl_tpu import rpc

    payload = rpc.default_client().get(
        f"{args.supervisor}/status",
        endpoint="cli/status",
        timeout=10,
        attempts=3,
        deadline=30.0,
    ).json()
    rows = [
        (
            "JOB", "PHASE", "REPLICAS", "DEGRADED", "DRAIN", "ALLOC",
            "RESTARTS", "LEASES",
        )
    ]
    for key, job in sorted(payload.get("jobs", {}).items()):
        ages = job.get("leaseAgeS", {})
        leases = ",".join(
            f"{rank}:{int(age)}s"
            for rank, age in sorted(
                ages.items(), key=lambda kv: int(kv[0])
            )
        )
        drain = job.get("drainRemainingS")
        rows.append(
            (
                key,
                str(job.get("status", "?")),
                str(job.get("replicas", 0)),
                "yes" if job.get("degraded") else "no",
                f"{int(drain)}s left"
                if job.get("draining") and drain is not None
                else "-",
                f"{job.get('allocEpoch', 0)}/"
                f"{job.get('allocState', '?')}",
                str(job.get("restarts", 0)),
                leases or "-",
            )
        )
    _print_table(rows)
    draining_slots = payload.get("drainingSlots") or {}
    if draining_slots:
        print(
            "\ndraining slots (reclaim notice): "
            + ", ".join(
                f"{slot} ({int(remaining)}s left)"
                for slot, remaining in sorted(draining_slots.items())
            )
        )
    hazards = payload.get("hazardRates") or {}
    if any(rate > 0 for rate in hazards.values()):
        print(
            "reclaim hazard: "
            + ", ".join(
                f"{kind}={rate * 3600:.3f}/slot-hour"
                for kind, rate in sorted(hazards.items())
            )
        )
    incidents = payload.get("incidentsByKind") or {}
    if incidents:
        print(
            "numeric incidents: "
            + ", ".join(
                f"{kind}={count}"
                for kind, count in sorted(incidents.items())
            )
        )
    slot_blame = payload.get("incidentSlotBlame") or {}
    data_blame = payload.get("incidentDataBlame") or {}
    repeat_slots = {
        slot: datas
        for slot, datas in slot_blame.items()
        if len(datas) >= 2
    }
    repeat_data = {
        data: slots
        for data, slots in data_blame.items()
        if len(slots) >= 2
    }
    if repeat_slots:
        print(
            "incident blame (slot — same slot, different data): "
            + ", ".join(
                f"{slot} ({len(datas)} data ids)"
                for slot, datas in sorted(repeat_slots.items())
            )
        )
    if repeat_data:
        print(
            "incident blame (data — same data, different slots): "
            + ", ".join(
                f"{data} ({len(slots)} slots)"
                for data, slots in sorted(repeat_data.items())
            )
        )
    quarantined = payload.get("quarantinedSlots", {})
    strikes = payload.get("slotStrikes", {})
    if quarantined or strikes:
        print()
        rows = [("SLOT", "STRIKES", "QUARANTINED")]
        for slot in sorted(set(quarantined) | set(strikes)):
            remaining = quarantined.get(slot)
            rows.append(
                (
                    slot,
                    str(strikes.get(slot, 0)),
                    f"{int(remaining)}s left"
                    if remaining is not None
                    else "no",
                )
            )
        _print_table(rows)
    recovery = payload.get("recovery") or {}
    if recovery.get("recoveries"):
        print(
            f"\nsupervisor recoveries: {recovery['recoveries']} "
            f"(last replay {recovery.get('lastRecoveryS') or 0:.3f}s, "
            f"{recovery.get('tornRecords', 0)} torn records dropped)"
        )
    shards = payload.get("shards") or {}
    if shards:
        # Router-merged view (graftshard): one row per supervisor
        # shard so a sick shard is visible next to healthy siblings.
        print()
        rows = [("SHARD", "JOBS", "RECOVERIES", "TORN", "STATE")]
        for sid in sorted(shards, key=int):
            info = shards[sid]
            shard_recovery = info.get("recovery") or {}
            rows.append(
                (
                    str(sid),
                    str(info.get("jobs", 0)),
                    str(shard_recovery.get("recoveries", 0)),
                    str(shard_recovery.get("tornRecords", 0)),
                    "DOWN: " + str(info["error"])[:40]
                    if info.get("error")
                    else "up",
                )
            )
        _print_table(rows)
    return 0


def _fmt_rate(value) -> str:
    return f"{value:.1f}" if isinstance(value, (int, float)) else "-"


def _render_top(payload: dict) -> None:  # wire: consumes=watch
    """One frame of the live cluster view: cluster utilization, the
    per-tenant fairness table, and the per-job goodput table."""
    cluster = (payload.get("cluster") or [])
    latest = cluster[-1] if cluster else {}
    print(
        f"cluster: {latest.get('jobs', 0)} active job(s), "
        f"{latest.get('chipsAllocated', 0)}/"
        f"{latest.get('chipsTotal', 0)} chips allocated "
        f"(utilization {latest.get('utilization', 0.0):.2f}), "
        f"{payload.get('samples', 0)} watch sample(s)"
        + (
            f", {len(payload['shards'])} shard(s)"
            if payload.get("shards")
            else ""
        )
    )
    tenants = payload.get("tenants") or {}
    if tenants:
        rows = [("TENANT", "JOBS", "CHIPS", "SHARE", "RHO", "SLO-BURN")]
        for tenant, info in sorted(tenants.items()):
            series = info.get("series") or []
            last = series[-1] if series else {}
            rho = last.get("rho")
            rows.append(
                (
                    tenant,
                    f"{last.get('running', 0)}/{last.get('jobs', 0)}",
                    str(last.get("chips", 0)),
                    f"{last.get('share', 0.0):.3f}",
                    f"{rho:.2f}" if rho is not None else "-",
                    str(info.get("burn", 0)),
                )
            )
        print()
        _print_table(rows)
    jobs = payload.get("jobs") or {}
    if jobs:
        rows = [
            (
                "JOB", "TENANT", "REPLICAS", "MEASURED", "PREDICTED",
                "DRIFT", "REPROFILE", "RHO", "INCID", "ROLLBK",
            )
        ]
        for key, info in sorted(jobs.items()):
            last = info.get("latest") or {}
            drift = info.get("drift")
            rho = last.get("rho")
            rows.append(
                (
                    key,
                    info.get("tenant", "-"),
                    str(last.get("replicas", 0)),
                    _fmt_rate(last.get("measured")),
                    _fmt_rate(last.get("predicted")),
                    f"{drift:.3f}" if drift is not None else "-",
                    "YES" if info.get("reprofile") else "no",
                    f"{rho:.2f}" if rho is not None else "-",
                    str(last.get("incidents", 0)),
                    str(last.get("rollbacks", 0)),
                )
            )
        print()
        _print_table(rows)
    suspects = payload.get("suspectSlots") or {}
    if suspects:
        print(
            "\nsuspect slots (straggling step times): "
            + ", ".join(
                f"{slot} ({info['job']} rank {info['rank']}, "
                f"{info['ratio']:.2f}x median)"
                for slot, info in sorted(suspects.items())
            )
        )


def _cmd_top(args) -> int:
    """Live cluster view (graftwatch): per-tenant goodput share and
    fairness, per-job measured-vs-predicted goodput with the drift
    monitor's re-profiling flags, and straggler-suspect slots —
    rendered from one GET /watch. ``--watch N`` re-renders every N
    seconds until interrupted."""
    import time as _time

    from adaptdl_tpu import rpc

    # Ctrl-C must exit cleanly wherever the loop happens to be —
    # mid-fetch (the common case; the request dominates each
    # iteration) as much as mid-sleep.
    try:
        while True:
            payload = rpc.default_client().get(
                f"{args.supervisor}/watch",
                endpoint="cli/watch",
                timeout=10,
                attempts=3,
                deadline=30.0,
            ).json()
            _render_top(payload)
            if not args.watch:
                return 0
            _time.sleep(args.watch)
            print()
    except KeyboardInterrupt:
        return 0


def _cmd_shardmap(args) -> int:  # wire: consumes=shard_map
    """The sharded control plane's routing table: shard id → url from
    the router's journaled rendezvous map, and (with ``--key``) where
    one ``namespace/name`` lands — the first question an operator
    asks when a tenant's traffic misbehaves."""
    from adaptdl_tpu import rpc

    payload = rpc.default_client().get(
        f"{args.supervisor}/shardmap",
        endpoint="cli/shardmap",
        timeout=10,
        attempts=3,
        deadline=30.0,
    ).json()
    print(f"shard map version {payload['version']}")
    rows = [("SHARD", "URL")]
    for sid, url in sorted(
        payload["shards"].items(), key=lambda kv: int(kv[0])
    ):
        rows.append((str(sid), url))
    _print_table(rows)
    if getattr(args, "key", None):
        from adaptdl_tpu.sched.shard import ShardMap

        shard_map = ShardMap.from_payload(payload)
        print(f"\n{args.key} -> shard {shard_map.assign(args.key)}")
    return 0


def _cmd_reshard(args) -> int:  # wire: consumes=reshard,shard_map
    """Live resharding driver. ``plan`` cuts a :class:`ReshardPlan`
    (the tenant moves a shard-set change implies) from the router's
    current map plus the merged inventory; ``apply`` executes a saved
    plan move by move — each tenant migration streams, fences,
    verifies, and flips its own map version with zero job restarts;
    ``status`` shows every shard's migration state: per-tenant
    watermark lag against the source journal head, fence remaining,
    and post-flip moved markers."""
    import sys as _sys

    from adaptdl_tpu import rpc
    from adaptdl_tpu.sched import shard as shard_mod

    client = rpc.default_client()

    if args.action == "apply":
        if not args.plan or not args.map:
            print(
                "reshard apply requires --plan and --map",
                file=_sys.stderr,
            )
            return 2
        shard_map = shard_mod.ShardMap.load(args.map)
        plan = shard_mod.ReshardPlan.load(args.plan)
        # A grow plan names shards the journaled map has never seen.
        # Mirror ShardedCluster.grow: publish a widened map FIRST,
        # with every moving tenant pinned to its current owner (and
        # any drain targets marked retiring), so the publish itself
        # changes no routing — the per-tenant flips do.
        needed = {m["from"] for m in plan.moves} | {
            m["to"] for m in plan.moves
        }
        urls = dict(shard_map.shards)
        for sid, url in plan.shards.items():
            urls.setdefault(sid, url)
        missing = sorted(needed - set(urls))
        if missing:
            print(
                f"reshard apply: plan names shard(s) {missing} absent "
                "from both the map and the plan's shard set",
                file=_sys.stderr,
            )
            return 2
        retiring = tuple(set(shard_map.retiring) | set(plan.retiring))
        if urls != shard_map.shards or retiring != shard_map.retiring:
            overrides = dict(shard_map.overrides)
            for move in plan.moves:
                overrides[move["tenant"]] = move["from"]
            shard_map = shard_mod.ShardMap(
                urls,
                version=shard_map.version + 1,
                overrides=overrides,
                retiring=retiring,
            )
            shard_map.save(args.map)
            print(
                f"published widened map v{shard_map.version} "
                f"({len(urls)} shard(s), routing unchanged)"
            )
        print(
            f"applying {len(plan.moves)} move(s) "
            f"from map v{shard_map.version}"
        )
        for move in plan.moves:
            shard_map = shard_mod.migrate_tenant(
                shard_map,
                move["tenant"],
                move["from"],
                move["to"],
                map_path=args.map,
                client=client,
                fence_s=args.fence_s,
            )
            print(
                f"  {move['tenant']}: shard {move['from']} -> "
                f"{move['to']} (map v{shard_map.version})"
            )
        print(f"done: map v{shard_map.version}")
        return 0

    if not args.supervisor:
        print(
            f"reshard {args.action} requires --supervisor",
            file=_sys.stderr,
        )
        return 2
    payload = client.get(
        f"{args.supervisor}/shardmap",
        endpoint="cli/reshard",
        timeout=10,
        attempts=3,
        deadline=30.0,
    ).json()
    shard_map = shard_mod.ShardMap.from_payload(payload)

    if args.action == "plan":
        new_shards = dict(shard_map.shards)
        for spec in args.add or ():
            sid, _, url = spec.partition("=")
            new_shards[int(sid)] = url
        plan = shard_mod.plan_reshard(
            shard_map,
            new_shards=new_shards,
            retiring=tuple(args.retire or ()),
            client=client,
        )
        print(
            f"reshard plan: map v{plan.from_version} -> "
            f"v{plan.version}, {len(plan.moves)} move(s)"
        )
        rows = [("TENANT", "FROM", "TO")]
        for move in plan.moves:
            rows.append(
                (move["tenant"], str(move["from"]), str(move["to"]))
            )
        _print_table(rows)
        if args.out:
            plan.save(args.out)
            print(f"\nwrote {args.out}")
        return 0

    # status: one fan-out over the map, then cross-shard watermark
    # lag (the epoch names the source shard, whose journal head is
    # the target the destination watermark chases).
    infos: dict[int, dict] = {}
    for sid in shard_map.shard_ids():
        infos[sid] = client.get(
            f"{shard_map.shards[sid]}/shard/reshard/status",
            endpoint="cli/reshard",
            timeout=10,
            attempts=3,
            deadline=30.0,
        ).json()
    print(f"shard map v{shard_map.version}")
    rows = [("SHARD", "SEQ", "TENANT", "STATE", "WATERMARK", "LAG", "DETAIL")]
    for sid in sorted(infos):
        info = infos[sid]
        seq = int(info.get("seq") or 0)
        busy = False
        for tenant, entry in sorted((info.get("pending") or {}).items()):
            busy = True
            epoch = str(entry.get("epoch") or "")
            lag = "-"
            # epoch format: "{tenant}:{from}->{to}@v{version}"
            try:
                src_sid = int(epoch.rsplit("@", 1)[0].rsplit(":", 1)[1].split("->")[0])
                lag = str(
                    max(int(infos[src_sid].get("seq") or 0)
                        - int(entry.get("watermark") or 0), 0)
                )
            except (KeyError, IndexError, ValueError):
                pass
            rows.append(
                (str(sid), str(seq), tenant, "pending",
                 str(entry.get("watermark")), lag,
                 f"jobs={entry.get('jobs')} "
                 f"skipped={entry.get('skipped')} epoch={epoch}")
            )
        for tenant, remaining in sorted((info.get("fenced") or {}).items()):
            busy = True
            rows.append(
                (str(sid), str(seq), tenant, "fenced", "-", "-",
                 f"remaining={float(remaining):.3f}s")
            )
        for tenant, marker in sorted((info.get("moved") or {}).items()):
            busy = True
            rows.append(
                (str(sid), str(seq), tenant, "moved", "-", "-",
                 f"-> shard {marker.get('shard')} "
                 f"@ map v{marker.get('version')}")
            )
        if not busy:
            rows.append((str(sid), str(seq), "-", "idle", "-", "-", "-"))
    _print_table(rows)
    return 0


def _cmd_explain(args) -> int:  # wire: consumes=explain,topology
    """Decision provenance for one job: why the allocator's last
    cycle gave it THIS allocation and mesh shape — the winning
    candidate's objective terms and the top-k losers with the term
    that killed each (speedup, restart penalty, hazard x restart
    cost, util band)."""
    from adaptdl_tpu import rpc

    response = rpc.default_client().get(
        f"{args.supervisor}/explain/{args.job}",
        endpoint="cli/explain",
        timeout=10,
        attempts=3,
        deadline=30.0,
    )
    payload = response.json()
    if response.status_code == 404 or "latest" not in payload:
        print(
            payload.get("error", f"no explain record for {args.job}"),
            file=sys.stderr,
        )
        return 1
    # Render the last cycle that actually RE-DECIDED the job (with
    # objective terms); incremental pass-through cycles only pin.
    latest = payload.get("lastDecision") or payload["latest"]
    newest = payload["latest"]
    alloc = latest.get("alloc") or []
    slots = sorted(set(alloc))
    print(
        f"job {args.job}  cycle {latest.get('cycle')} "
        f"({latest.get('mode')})"
    )
    if latest.get("pinned"):
        print(
            f"  pinned: kept its allocation untouched this cycle "
            f"({len(alloc)} replica(s) on {', '.join(slots) or '-'})"
        )
    else:
        print(
            f"  winning allocation: {len(alloc)} replica(s) on "
            f"{', '.join(slots) or '(none)'}"
        )
        if newest.get("pinned") and newest.get("cycle") != latest.get(
            "cycle"
        ):
            print(
                f"  (pinned unchanged through cycle "
                f"{newest.get('cycle')})"
            )
    mesh = latest.get("meshShape")
    if mesh:
        print(
            "  mesh shape: "
            f"sp={mesh.get('seqShards', 1)} "
            f"tp={mesh.get('modelShards', 1)} "
            f"pp={mesh.get('stageShards', 1)} "
            f"ep={mesh.get('expertShards', 1)} "
            f"micro={mesh.get('pipelineMicro', 1)}"
        )
    if latest.get("speedup") is not None:
        print(
            "  objective terms: "
            f"speedup={latest['speedup']:.4f} "
            f"(scaled {latest.get('scaledSpeedup', 0.0):.4f}), "
            f"restartPenalty={latest.get('restartPenalty', 0.0):.3f}"
            f"{' (moved)' if latest.get('moved') else ''}, "
            f"hazardLoss={latest.get('hazardLoss', 0.0):.4f}"
        )
    cycle = payload.get("cycle") or {}
    winner = cycle.get("winner")
    if winner:
        print(
            f"  cycle winner: objective {winner['objective']:.4f} "
            f"over {cycle.get('candidates', 0)} candidate(s), "
            f"{winner['nodes']} slice(s) active"
        )
    losers = cycle.get("losers") or []
    if losers:
        print("  losing candidates:")
        for loser in losers:
            print(
                f"    objective {loser['objective']:.4f} "
                f"({loser['nodes']} slice(s)) — killed by "
                f"{loser['killedBy']}"
            )
    history = payload.get("history") or []
    if len(history) > 1:
        print(
            f"  history: {len(history)} retained decision(s), "
            f"cycles {history[0].get('cycle')}.."
            f"{history[-1].get('cycle')}"
        )
    return 0


def _cmd_trace(args) -> int:  # wire: consumes=trace_payload,trace_span
    """Render a job's stitched rescale trace (graftscope): fetch the
    supervisor's merged worker+supervisor span view, pick one trace
    (the current decision's, else the newest, else --trace-id), print
    the phase waterfall with per-phase totals, and optionally write
    the Chrome/Perfetto ``trace_event`` file."""
    from adaptdl_tpu import rpc, trace

    if args.journal:
        # A worker's own journal (ADAPTDL_TRACE_DIR): no supervisor
        # needed to read where one restart spent its time.
        payload = {"spans": trace.read_journal(args.journal)}
    elif args.supervisor:
        payload = rpc.default_client().get(
            f"{args.supervisor}/trace/{args.job}",
            endpoint="cli/trace",
            timeout=10,
            attempts=3,
            deadline=30.0,
        ).json()
    else:
        print("trace: give --supervisor URL or --journal FILE",
              file=sys.stderr)
        return 2
    spans = payload.get("spans") or []
    if not spans:
        print(f"no spans recorded for {args.job}", file=sys.stderr)
        return 1
    by_trace: dict[str, list] = {}
    for rec in spans:
        by_trace.setdefault(rec.get("trace", "?"), []).append(rec)
    if args.all:
        selected = spans
        trace_id = f"(all {len(by_trace)} traces)"
    else:
        trace_id = None
        if args.trace_id:
            trace_id = args.trace_id
            if trace_id not in by_trace:
                print(
                    f"trace {trace_id} not found; known: "
                    f"{sorted(by_trace)}",
                    file=sys.stderr,
                )
                return 1
        else:
            parsed = trace.parse_traceparent(
                payload.get("traceParent")
            )
            if parsed is not None and parsed[0] in by_trace:
                # The current decision's trace: what an operator asking
                # "where did the LAST rescale spend its time" wants.
                trace_id = parsed[0]
            else:
                trace_id = max(
                    by_trace,
                    key=lambda t: max(
                        float(r.get("ts", 0.0)) for r in by_trace[t]
                    ),
                )
        selected = by_trace[trace_id]
    print(f"job {args.job}  trace {trace_id}  {len(selected)} span(s)")
    print(trace.render_waterfall(selected))
    summary = trace.phase_summary(selected)
    if summary:
        durs: dict[str, list] = {}
        for rec in selected:
            durs.setdefault(rec["name"], []).append(rec.get("dur", 0.0))
        print("\nper-phase medians:")
        for name in sorted(summary):
            print(
                f"  {name:<28} {summary[name] * 1e3:>10.2f} ms"
                f"  x{len(durs[name]):<4}"
                f" total {sum(durs[name]) * 1e3:>10.2f} ms"
            )
    events = collections.Counter(
        rec["name"] for rec in selected if rec.get("kind") == "event"
    )
    if events:
        # Point events have no bar to draw; their counts say e.g. how
        # a restart got its calibration (step.calibrate_reused here,
        # a step.calibrate span above) and its step (aot.hit / .miss).
        print("\nevents:")
        for name in sorted(events):
            print(f"  {name:<28} x{events[name]}")
    if any(rec["name"] == "step.cycle" for rec in selected):
        # One row a pull: which cycle was long, and which phase, CPU
        # share, context switches, faults or collection held it.
        print("\ncycles (ms):")
        print(trace.render_cycles(selected))
    if args.perfetto:
        with open(args.perfetto, "w", encoding="utf-8") as f:
            json.dump(trace.to_perfetto(selected), f)
        print(
            f"\nwrote Perfetto trace_event JSON to {args.perfetto} "
            "(load in ui.perfetto.dev or chrome://tracing)"
        )
    return 0


def _cmd_sim(args) -> int:
    """graftsim: replay a JSONL job-arrival trace through the REAL
    scheduler (PolluxPolicy + Allocator + ClusterState) under a
    virtual clock and render the summary table — or generate a trace
    (``--generate N``). A fixed ``--seed`` reproduces the summary
    bit-for-bit; ``--compare-fixed`` also runs the fixed-allocation
    baseline and prints the goodput-retention ratio."""
    from adaptdl_tpu.sim import (
        generate_trace,
        load_trace,
        run_trace,
        write_trace,
    )

    if args.generate is not None:
        records = generate_trace(
            args.generate, args.duration, seed=args.seed
        )
        if args.out:
            write_trace(args.out, records)
            print(
                f"wrote {len(records)} arrivals to {args.out}",
                file=sys.stderr,
            )
        else:
            for record in records:
                print(json.dumps(record, sort_keys=True))
        return 0
    if not args.trace:
        print(
            "sim: a TRACE file is required (or --generate N)",
            file=sys.stderr,
        )
        return 2
    records = load_trace(args.trace)
    kwargs = dict(
        slices=args.slices,
        chips_per_slice=args.chips_per_slice,
        seed=args.seed,
        interval=args.interval,
        spot_fraction=args.spot_fraction,
        reclaims_per_slot_hour=args.reclaims_per_slot_hour,
    )
    report = run_trace(
        records, fixed=args.fixed, dp_only=args.dp_only, **kwargs
    )
    print(report.render())
    payload = {
        "summary": report.summary(),
        "latency": report.latency(),
        # graftwatch's deterministic per-tenant fairness/drift summary
        # (tenant = workload category) — the sim-side record stream.
        "watch": report.watch_summary(),
    }
    if args.compare_fixed and not args.fixed:
        baseline = run_trace(records, fixed=True, **kwargs)
        retention = report.summary()["avg_goodput_x_ideal"] / max(
            baseline.summary()["avg_goodput_x_ideal"], 1e-9
        )
        payload["fixed_baseline"] = baseline.summary()
        payload["goodput_retention_vs_fixed"] = round(retention, 4)
        print(
            f"\ngoodput retention vs fixed allocation: "
            f"{retention:.4f} (>= 1.0 means the adaptive policy "
            "wins)"
        )
    if args.compare_dp_only and not args.fixed and not args.dp_only:
        baseline = run_trace(records, dp_only=True, **kwargs)
        retention = report.summary()["avg_goodput_x_ideal"] / max(
            baseline.summary()["avg_goodput_x_ideal"], 1e-9
        )
        payload["dp_only_baseline"] = baseline.summary()
        payload["goodput_retention_vs_dp_only"] = round(retention, 4)
        print(
            f"\ngoodput retention vs the dp-only policy: "
            f"{retention:.4f} (>= 1.0 means mesh-shape search wins "
            "on this trace)"
        )
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(payload, f, sort_keys=True, indent=2)
        print(f"\nwrote report JSON to {args.json}", file=sys.stderr)
    return 0


def _cmd_check(args) -> int:
    """Operator-facing graftcheck: the same analyzer `make
    graftcheck` runs (wire contracts, endpoint conformance, lock /
    journal / replay discipline), without needing the Makefile.
    Exit-code semantics are graftcheck's own: 0 = clean beyond the
    committed baseline, 1 = new findings, 2 = usage error."""
    try:
        from tools.graftcheck.__main__ import main as graftcheck_main
    except ImportError:
        print(
            "check needs the graftcheck analyzer (tools/graftcheck) "
            "on PYTHONPATH — run from a source checkout of the repo",
            file=sys.stderr,
        )
        return 2
    # graftcheck anchors everything cwd-relative: the wire/faults
    # contracts, the protocols doc, the committed baseline, and its
    # --fast cache. Run from anywhere by re-anchoring at the source
    # checkout this package was imported from — otherwise the
    # contract files silently fail to load and the verb reports a
    # false clean.
    import os

    import adaptdl_tpu as _pkg

    repo_root = os.path.dirname(
        os.path.dirname(os.path.abspath(_pkg.__file__))
    )
    if os.getcwd() != repo_root and os.path.isdir(
        os.path.join(repo_root, "tools", "graftcheck")
    ):
        args.paths = [
            os.path.abspath(p) if os.path.exists(p) else p
            for p in args.paths
        ]
        for attr in ("baseline", "docs_dir"):
            value = getattr(args, attr)
            if value:
                setattr(args, attr, os.path.abspath(value))
        os.chdir(repo_root)
    argv = list(args.paths)
    if args.fast:
        argv.append("--fast")
    if args.format != "text":
        argv.extend(["--format", args.format])
    if args.rules:
        argv.extend(["--rules", args.rules])
    if args.docs_dir:
        argv.extend(["--docs-dir", args.docs_dir])
    if args.baseline:
        argv.extend(["--baseline", args.baseline])
    if args.write_baseline:
        argv.append("--write-baseline")
    if args.list_rules:
        argv.append("--list-rules")
    if args.quiet:
        argv.append("--quiet")
    return graftcheck_main(argv)


def _cmd_hints(args) -> int:
    from adaptdl_tpu import rpc

    response = rpc.default_client().get(
        f"{args.supervisor}/hints/{args.job}",
        endpoint="cli/hints",
        timeout=10,
        attempts=3,
        deadline=30.0,
    )
    print(json.dumps(response.json(), indent=2))
    return 0


def _split_job(job: str, default_namespace: str) -> tuple[str, str]:
    """'namespace/name' or bare 'name' -> (namespace, name)."""
    if "/" in job:
        namespace, name = job.split("/", 1)
        return namespace, name
    return default_namespace, job


def _require_kubectl() -> bool:
    if shutil.which("kubectl") is None:
        print("kubectl is not installed", file=sys.stderr)
        return False
    return True


def _cmd_logs(args) -> int:
    if args.job:
        # Cluster data path: stream every pod of the job by the
        # operator's label selector (reference: cli/bin/adaptdl:306-318
        # drives `kubectl logs -l` the same way).
        namespace, name = _split_job(args.job, args.namespace)
        cmd = [
            "kubectl",
            "logs",
            "-n",
            namespace,
            "-l",
            f"adaptdl/job={name}",
            "--all-containers",
            "--prefix",
            "--tail",
            str(args.lines),
            # kubectl caps selector follows at 5 streams by default;
            # elastic jobs routinely run more pods than that.
            "--max-log-requests",
            "64",
        ]
        if args.follow:
            cmd.append("-f")
        if not _require_kubectl():
            return 1
        return subprocess.call(cmd)
    if not args.log_file:
        print(
            "either a JOB (k8s backend) or --log-file (local backend) "
            "is required",
            file=sys.stderr,
        )
        return 2
    cmd = ["tail"]
    if args.follow:
        cmd.append("-f")
    cmd.extend(["-n", str(args.lines), args.log_file])
    return subprocess.call(cmd)


def _cmd_cp(args) -> int:
    import os

    if ":" in args.src:
        # Cluster data path: '<namespace>/<job>:<path>' extracts from
        # the job's checkpoint PVC via a short-lived helper pod
        # (reference: cli/bin/adaptdl:234-303 + pvc.py:81-128). The
        # path is relative to the job's checkpoint dir
        # (/adaptdl/checkpoints/<ns>-<name>, the mount the job
        # manifest sets up) unless absolute.
        job, _, path = args.src.partition(":")
        namespace, name = _split_job(job, args.namespace)
        if not _require_kubectl():
            return 1
        from adaptdl_tpu.sched.k8s import render_copy_pod_manifest

        # Unique per invocation: concurrent cp runs against the same
        # job must not share (and tear down) one helper pod.
        import uuid

        suffix = uuid.uuid4().hex[:6]
        helper = f"adaptdl-cp-{name}"[:56] + f"-{suffix}"
        manifest = render_copy_pod_manifest(
            helper,
            checkpoint_claim=args.checkpoint_claim,
            namespace=namespace,
        )
        if not path.startswith("/"):
            path = f"/adaptdl/checkpoints/{namespace}-{name}/{path}"
        apply = subprocess.run(
            ["kubectl", "apply", "-n", namespace, "-f", "-"],
            input=manifest.encode(),
        )
        if apply.returncode != 0:
            return apply.returncode
        try:
            wait = subprocess.run(
                [
                    "kubectl",
                    "wait",
                    "-n",
                    namespace,
                    "--for=condition=Ready",
                    f"pod/{helper}",
                    "--timeout=120s",
                ]
            )
            if wait.returncode != 0:
                return wait.returncode
            return subprocess.call(
                [
                    "kubectl",
                    "cp",
                    f"{namespace}/{helper}:{path}",
                    args.dst,
                ]
            )
        finally:
            # --wait=false: the pod traps TERM, but the CLI need not
            # block on kubelet teardown either way.
            subprocess.call(
                [
                    "kubectl",
                    "delete",
                    "pod",
                    "-n",
                    namespace,
                    helper,
                    "--ignore-not-found",
                    "--wait=false",
                ]
            )
    if os.path.isdir(args.src):
        # Whole checkpoint dirs are the common case (the reference's
        # cp pulls them off the PVC via a helper pod, pvc.py:81-128;
        # locally it is a recursive copy).
        shutil.copytree(args.src, args.dst, dirs_exist_ok=True)
    else:
        shutil.copy2(args.src, args.dst)
    return 0


def _apply_or_print(manifest: str, dry_run: bool) -> int:
    if shutil.which("kubectl") and not dry_run:
        proc = subprocess.run(
            ["kubectl", "apply", "-f", "-"], input=manifest.encode()
        )
        return proc.returncode
    print(manifest)
    return 0


def _cmd_deploy(args) -> int:
    """Render (and apply) the whole scheduler bundle — the
    helm-install equivalent. ``--values`` takes a helm-style YAML
    values file (reference surface: helm/adaptdl-sched/values.yaml);
    explicit flags win over file values, which win over defaults."""
    from adaptdl_tpu.sched.k8s import render_scheduler_bundle

    # The deploy flags use None/False sentinels, so "user did not pass
    # it" is directly observable — no shadow table of argparse
    # defaults to drift out of sync.
    kwargs = {
        "image": args.image,
        "namespace": args.namespace,
        "with_webhook": False if args.no_webhook else None,
        "ca_bundle": args.ca_bundle,
    }
    if args.values:
        try:
            import yaml
        except ModuleNotFoundError:
            print(
                "--values needs pyyaml: pip install adaptdl-tpu[k8s]",
                file=sys.stderr,
            )
            return 1
        with open(args.values) as f:
            values = yaml.safe_load(f) or {}
        overrides, unknown = _values_overrides(values)
        for key, value in overrides.items():
            # Explicit CLI flags win; an unset flag (sentinel) yields
            # to the values file.
            if kwargs.get(key) is None:
                kwargs[key] = value
        if unknown:
            print(
                f"warning: unrecognized values keys {sorted(unknown)}",
                file=sys.stderr,
            )
    resolved = {
        "image": "adaptdl-tpu:latest",
        "namespace": "default",
        "with_webhook": True,
        "ca_bundle": None,
    }
    resolved.update(
        {k: v for k, v in kwargs.items() if v is not None}
    )
    manifest = render_scheduler_bundle(**resolved)
    return _apply_or_print(manifest, args.dry_run)


def _values_overrides(values: dict) -> tuple[dict, list[str]]:
    """Flatten a helm-style values mapping onto
    ``render_scheduler_bundle`` kwargs; returns (overrides, unknown
    keys) so typos fail loudly instead of silently deploying
    defaults."""
    overrides: dict = {}
    unknown: list[str] = []
    for key, value in values.items():
        if key in ("image", "namespace"):
            overrides[key] = value
        elif key == "supervisor" and isinstance(value, dict):
            for sub, v in value.items():
                if sub == "port":
                    overrides["supervisor_port"] = v
                else:
                    unknown.append(f"supervisor.{sub}")
        elif key == "webhook" and isinstance(value, dict):
            for sub, v in value.items():
                if sub == "port":
                    overrides["webhook_port"] = v
                elif sub == "enabled":
                    overrides["with_webhook"] = bool(v)
                elif sub == "caBundle":
                    overrides["ca_bundle"] = v
                else:
                    unknown.append(f"webhook.{sub}")
        else:
            unknown.append(str(key))
    return overrides, unknown


def _cmd_tensorboard(args) -> int:
    if args.action == "attach":
        # Proxy a managed in-cluster instance to a local port
        # (reference: cli/adaptdl_cli/tensorboard.py:24-120 +
        # proxy.py:29-119 tunnel through the apiserver; port-forward
        # is the kubectl-native equivalent).
        name = args.name or "default"
        if not _require_kubectl():
            return 1
        # The service's port is whatever `create --port` set; default
        # to the local --port so `create --port 7007` + `attach --port
        # 7007` just works, with --remote-port for asymmetric setups.
        remote = (
            args.remote_port
            if args.remote_port is not None
            else args.port
        )
        return subprocess.call(
            [
                "kubectl",
                "port-forward",
                "-n",
                args.namespace,
                f"service/adaptdl-tb-{name}",
                f"{args.port}:{remote}",
            ]
        )
    if args.backend == "k8s":
        from adaptdl_tpu.sched.k8s import render_tensorboard_manifest

        name = args.name or "default"
        if args.action == "delete":
            # Same explicit namespace as create: a label-selector
            # delete in the kubeconfig's current namespace would miss
            # objects created elsewhere and leak them.
            cmd = [
                "kubectl",
                "delete",
                "deployment,service",
                "-n",
                args.namespace,
                "-l",
                f"adaptdl/tensorboard={name}",
            ]
            if shutil.which("kubectl") and not args.dry_run:
                return subprocess.call(cmd)
            print("# " + " ".join(cmd))
            return 0
        manifest = render_tensorboard_manifest(
            name,
            logdir_claim=args.logdir_claim,
            namespace=args.namespace,
            port=args.port,
        )
        return _apply_or_print(manifest, args.dry_run)
    if args.action == "delete":
        print(
            "tensorboard delete requires --backend k8s (the local "
            "backend runs in the foreground; just stop it)",
            file=sys.stderr,
        )
        return 2
    if not args.logdir:
        print(
            "--logdir is required for the local backend",
            file=sys.stderr,
        )
        return 2
    if shutil.which("tensorboard") is None:
        print(
            "tensorboard is not installed in this environment",
            file=sys.stderr,
        )
        return 1
    return subprocess.call(
        ["tensorboard", "--logdir", args.logdir, "--port", str(args.port)]
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="adaptdl-tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("submit", help="run a training script elastically")
    p.add_argument("script")
    p.add_argument("--backend", choices=("local", "k8s"), default="local")
    p.add_argument("--name")
    p.add_argument("--chips", type=int, default=None)
    p.add_argument("--checkpoint-dir", default="/tmp/adaptdl-ckpt")
    p.add_argument("--min-replicas", type=int, default=0)
    p.add_argument("--max-replicas", type=int, default=None)
    p.add_argument("--log-file")
    p.add_argument("--image", default="adaptdl-tpu:latest")
    p.add_argument(
        "--build",
        metavar="CONTEXT_DIR",
        default=None,
        help="build+push the image from this source tree and "
        "digest-pin the manifest (k8s backend; needs --registry)",
    )
    p.add_argument(
        "--registry",
        default=None,
        help="image registry for --build, e.g. "
        "us-docker.pkg.dev/PROJECT/REPO",
    )
    p.add_argument(
        "--dockerfile",
        default=None,
        help="Dockerfile for --build (default: CONTEXT/Dockerfile, "
        "generated if absent)",
    )
    p.add_argument("--checkpoint-claim", default="adaptdl-checkpoints")
    p.add_argument("--dry-run", action="store_true")
    p.set_defaults(fn=_cmd_submit)

    p = sub.add_parser(
        "ls",
        help="list jobs: --backend k8s reads the CRD status table; "
        "default queries a live supervisor's /metrics",
    )
    p.add_argument("--supervisor", default=None)
    p.add_argument(
        "--backend", choices=["supervisor", "k8s"], default="supervisor"
    )
    p.add_argument("--namespace", default="default")
    p.set_defaults(fn=_cmd_ls)

    p = sub.add_parser(
        "status",
        help="operator view of a live supervisor: per-job phase, "
        "degraded flag, allocation epoch/state, lease ages, slot "
        "strikes/quarantine, recovery info",
    )
    p.add_argument("--supervisor", required=True)
    p.set_defaults(fn=_cmd_status)

    p = sub.add_parser(
        "top",
        help="live cluster view (graftwatch): per-tenant goodput "
        "share/fairness, per-job measured vs predicted goodput with "
        "drift flags, straggler-suspect slots",
    )
    p.add_argument("--supervisor", required=True)
    p.add_argument(
        "--watch",
        type=float,
        default=0.0,
        metavar="SECONDS",
        help="re-render every SECONDS until interrupted "
        "(default: one shot)",
    )
    p.set_defaults(fn=_cmd_top)

    p = sub.add_parser(
        "shardmap",
        help="sharded control plane routing table: shard id → url "
        "from the router's journaled rendezvous map",
    )
    p.add_argument(
        "--supervisor",
        required=True,
        help="router (or shard-map-serving supervisor) base URL",
    )
    p.add_argument(
        "--key",
        help="a namespace/name job key to resolve to its owning shard",
    )
    p.set_defaults(fn=_cmd_shardmap)

    p = sub.add_parser(
        "reshard",
        help="live resharding: plan tenant moves for a shard-set "
        "change, apply them with zero job restarts, or show "
        "per-tenant migration status (watermark lag, fences, "
        "moved markers)",
    )
    p.add_argument(
        "action",
        choices=("plan", "apply", "status"),
        help="plan: cut a ReshardPlan from the current map + merged "
        "inventory; apply: execute a saved plan (stream, fence, "
        "verify, flip — one map bump per tenant); status: show each "
        "shard's migration state",
    )
    p.add_argument(
        "--supervisor",
        help="router base URL (plan/status)",
    )
    p.add_argument(
        "--retire",
        action="append",
        type=int,
        metavar="SHARD",
        help="shard id to drain out of the rendezvous (plan; "
        "repeatable)",
    )
    p.add_argument(
        "--add",
        action="append",
        metavar="SID=URL",
        help="shard to add to the target set (plan; repeatable)",
    )
    p.add_argument(
        "--out",
        help="write the computed plan to this file (plan)",
    )
    p.add_argument(
        "--plan",
        help="plan file to execute (apply)",
    )
    p.add_argument(
        "--map",
        help="journaled shard-map path the flips are published to "
        "(apply)",
    )
    p.add_argument(
        "--fence-s",
        type=float,
        default=None,
        dest="fence_s",
        help="per-tenant write-fence budget in seconds (apply; "
        "default 5)",
    )
    p.set_defaults(fn=_cmd_reshard)

    p = sub.add_parser(
        "explain",
        help="decision provenance for one job: the winning "
        "allocation + mesh shape with its objective terms, and the "
        "losing candidates with the term that killed each",
    )
    p.add_argument("job", help="namespace/name")
    p.add_argument("--supervisor", required=True)
    p.set_defaults(fn=_cmd_explain)

    p = sub.add_parser(
        "trace",
        help="render a job's stitched rescale trace (phase "
        "waterfall + per-phase medians; --perfetto writes the "
        "Chrome/Perfetto trace_event file)",
    )
    p.add_argument("job", help="namespace/name")
    p.add_argument("--supervisor", default=None)
    p.add_argument(
        "--journal",
        default=None,
        metavar="FILE",
        help="read the spans from a trace journal "
        "(ADAPTDL_TRACE_DIR/trace-<job>.jsonl) instead of the "
        "supervisor",
    )
    p.add_argument(
        "--trace-id",
        default=None,
        help="render this trace id (default: the current decision's "
        "trace, else the newest)",
    )
    p.add_argument(
        "--perfetto",
        default=None,
        metavar="FILE",
        help="also write the selected spans as Chrome/Perfetto "
        "trace_event JSON",
    )
    p.add_argument(
        "--all",
        action="store_true",
        help="render every stored span, not just one trace",
    )
    p.set_defaults(fn=_cmd_trace)

    p = sub.add_parser(
        "sim",
        help="graftsim: replay a job-arrival trace through the real "
        "scheduler under a virtual clock (or --generate a trace); "
        "fixed seed => bit-identical summary",
    )
    p.add_argument(
        "trace", nargs="?", default=None, help="JSONL arrival trace"
    )
    p.add_argument("--slices", type=int, default=16)
    p.add_argument("--chips-per-slice", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--interval",
        type=float,
        default=60.0,
        help="virtual seconds between allocator cycles",
    )
    p.add_argument(
        "--fixed",
        action="store_true",
        help="score the fixed-allocation baseline instead of Pollux",
    )
    p.add_argument(
        "--compare-fixed",
        action="store_true",
        help="also run the fixed baseline and print the goodput-"
        "retention ratio",
    )
    p.add_argument(
        "--dp-only",
        action="store_true",
        help="strip mesh-shape hints so the policy runs its "
        "replica-only search (the pre-mesh scheduler)",
    )
    p.add_argument(
        "--compare-dp-only",
        action="store_true",
        help="also run the dp-only policy and print the goodput-"
        "retention ratio mesh-shape search buys on this trace",
    )
    p.add_argument(
        "--spot-fraction",
        type=float,
        default=0.0,
        help="fraction of slices that are preemptible",
    )
    p.add_argument(
        "--reclaims-per-slot-hour",
        type=float,
        default=0.0,
        help="Poisson reclaim-notice rate per spot slice (0 = off)",
    )
    p.add_argument(
        "--generate",
        type=int,
        default=None,
        metavar="N",
        help="generate an N-job trace instead of simulating",
    )
    p.add_argument(
        "--duration",
        type=float,
        default=3600.0,
        help="arrival span (virtual seconds) for --generate",
    )
    p.add_argument("-o", "--out", default=None, help="trace output file")
    p.add_argument(
        "--json", default=None, help="write summary+latency JSON here"
    )
    p.set_defaults(fn=_cmd_sim)

    p = sub.add_parser(
        "check",
        help="run the graftcheck static analyzer (wire contracts, "
        "endpoint conformance, lock/journal/replay discipline); "
        "exit 0 clean, 1 new findings, 2 usage error",
    )
    p.add_argument(
        "paths",
        nargs="*",
        default=["adaptdl_tpu"],
        help="files or directories to analyze (default: adaptdl_tpu)",
    )
    p.add_argument(
        "--fast",
        action="store_true",
        help="smoke mode: reuse cached results for unchanged files",
    )
    p.add_argument(
        "--format", choices=("text", "json", "sarif"), default="text"
    )
    p.add_argument(
        "--rules", default=None,
        help="comma-separated rule-id prefixes (e.g. GC10,GC1101)",
    )
    p.add_argument("--baseline", default=None)
    p.add_argument("--docs-dir", default=None)
    p.add_argument("--write-baseline", action="store_true")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("-q", "--quiet", action="store_true")
    p.set_defaults(fn=_cmd_check)

    p = sub.add_parser("hints", help="show a job's posted sched hints")
    p.add_argument("job", help="namespace/name")
    p.add_argument("--supervisor", required=True)
    p.set_defaults(fn=_cmd_hints)

    p = sub.add_parser(
        "logs",
        help="stream a cluster job's pod logs by label selector "
        "(JOB), or tail a local job's log file (--log-file)",
    )
    p.add_argument(
        "job", nargs="?", default=None, help="namespace/name or name"
    )
    p.add_argument("--log-file")
    p.add_argument("--namespace", default="default")
    p.add_argument("-f", "--follow", action="store_true")
    p.add_argument("-n", "--lines", type=int, default=50)
    p.set_defaults(fn=_cmd_logs)

    p = sub.add_parser(
        "cp",
        help="copy files out of a job's checkpoint storage: local "
        "paths, or 'namespace/job:path' to extract from the cluster "
        "PVC via a helper pod",
    )
    p.add_argument("src")
    p.add_argument("dst")
    p.add_argument("--namespace", default="default")
    p.add_argument("--checkpoint-claim", default="adaptdl-checkpoints")
    p.set_defaults(fn=_cmd_cp)

    p = sub.add_parser(
        "tensorboard",
        help="launch tensorboard locally, manage an in-cluster "
        "instance (--backend k8s create/delete), or attach to one "
        "(attach port-forwards it locally)",
    )
    p.add_argument("action", nargs="?", default="create",
                   choices=("create", "delete", "attach"))
    p.add_argument("--backend", choices=("local", "k8s"),
                   default="local")
    p.add_argument("--name")
    p.add_argument("--logdir")
    p.add_argument("--logdir-claim", default="adaptdl-checkpoints")
    p.add_argument("--namespace", default="default")
    p.add_argument("--port", type=int, default=6006)
    p.add_argument(
        "--remote-port",
        type=int,
        default=None,
        help="service port of the in-cluster instance (attach); "
        "defaults to --port",
    )
    p.add_argument("--dry-run", action="store_true")
    p.set_defaults(fn=_cmd_tensorboard)

    p = sub.add_parser(
        "deploy",
        help="render/apply the scheduler bundle (CRD, operator, "
        "webhook, services) — the helm-install equivalent",
    )
    # None = not passed (sentinel): lets a --values file apply, with
    # the real defaults resolved in _cmd_deploy after the merge.
    p.add_argument("--image", default=None)
    p.add_argument("--namespace", default=None)
    p.add_argument("--no-webhook", action="store_true")
    p.add_argument(
        "--ca-bundle",
        help="base64 CA bundle for the webhook serving cert; without "
        "it the webhook is registered with failurePolicy Ignore",
    )
    p.add_argument(
        "--values",
        default=None,
        help="helm-style YAML values file (image, namespace, "
        "supervisor.port, webhook.{enabled,port,caBundle}); explicit "
        "flags win",
    )
    p.add_argument("--dry-run", action="store_true")
    p.set_defaults(fn=_cmd_deploy)

    args = parser.parse_args(argv)
    from adaptdl_tpu.sched.validator import ValidationError

    try:
        return args.fn(args)
    except ValidationError as exc:
        print(f"invalid job spec: {exc}", file=sys.stderr)
        return 2
    except BrokenPipeError:
        # stdout went away (e.g. piped into `head`); not an error.
        return 0


if __name__ == "__main__":
    sys.exit(main())
