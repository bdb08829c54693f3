"""The ``ADAPTDL_*`` surface, held in the open (adaptdl_tpu/env.py).

A key is what a launcher, a deployment or an operator sets; a tuning
number with one value in use is a constant beside its caller
(docs/environment.md, "Adding a key"). A PR that adds a key edits
``KEYS`` below, where a reviewer sees it, and an accessor that nothing
in the package calls is a key that does nothing.
"""

import ast
import os
import re

from adaptdl_tpu import env

PACKAGE = os.path.dirname(os.path.abspath(env.__file__))

KEYS = """
AOT_CACHE CHECKPOINT_PATH COMPILE_CACHE COORDINATOR_ADDR FAULT_SEED
FAULT_SPEC JOB_ID MASTER_ADDR MASTER_PORT SHARE_PATH SUPERVISOR_URL
TRACE TRACE_DIR TRACEPARENT TRIAL_CONFIG TRIAL_RESULT_FILE

NUM_NODES NUM_PROCESSES NUM_REPLICAS NUM_RESTARTS PROCESS_RANK
REPLICA_RANK EXPERT_SHARDS MODEL_SHARDS SEQ_SHARDS STAGE_SHARDS
PIPELINE_MICRO HANDOFF_URL WARMUP WARMUP_CUTOVER_FILE WARMUP_READY_FILE

CKPT_EVERY_STEPS CKPT_FULL_EVERY CKPT_VERIFY HANDOFF HANDOFF_DIFF
SHARDED_HASHES WARMUP_ENABLED

FIT_INTERVAL GUARD_CONFIRM_STEPS GUARD_POLICY HEARTBEAT_INTERVAL
JOURNAL_GROUP_COMMIT_S LEASE_TTL PREEMPT_MARGIN_S PREEMPT_NOTICE_S
PREEMPT_POLL_S SPOT_PRICE_RATIO WATCH_SLO_RHO

ALLOCATOR_INTERVAL CHECKPOINT_CLAIM DEFAULT_RESOURCES GKE_NODE_POOL
JOB_IMAGE MAX_FAILURES MAX_SLICES MIN_SLICES NAMESPACE SCALE_DOWN_DELAY
SCHED_STATE_DIR SHARD_COUNT SHARD_ID SHARD_MAP_PATH SLICE_TEMPLATE
SUPERVISOR_PORT WEBHOOK_CERT WEBHOOK_KEY WEBHOOK_PORT
""".split()


def _source(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_the_keys_are_the_listed_ones():
    found = set(re.findall(r"ADAPTDL_([A-Z0-9_]+)", _source(env.__file__)))
    assert len(KEYS) == len(set(KEYS))
    assert found == set(KEYS), (
        f"added: {sorted(found - set(KEYS))}, "
        f"gone: {sorted(set(KEYS) - found)} — edit KEYS with the key"
    )


def _called(tree, qualified):
    """Names called as ``env.x()`` / ``env_mod.x()``, or, where not
    ``qualified`` (env.py itself), as ``x()``."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in ("env", "env_mod")
        ):
            yield func.attr
        elif not qualified and isinstance(func, ast.Name):
            yield func.id


def test_every_accessor_has_a_caller_in_the_package():
    own = ast.parse(_source(env.__file__))
    accessors = {
        node.name
        for node in own.body
        if isinstance(node, ast.FunctionDef) and not node.name.startswith("_")
    }
    called = set(_called(own, qualified=False))
    for root, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                tree = ast.parse(_source(os.path.join(root, name)))
                called.update(_called(tree, qualified=True))
    assert len(accessors) > 60
    assert not accessors - called, (
        f"called nowhere in adaptdl_tpu/: {sorted(accessors - called)}"
    )
