"""One rescale, signal to first step, out of the SUCCESSOR alone.

Workers flush spans to the supervisor on the heartbeat cadence, and the
final save and the exit come after the last beat: without a hand-set
``ADAPTDL_TRACE_DIR`` the doomed worker's last spans died with it. Now
the exiting rank 0 leaves them in one file of the checkpoint directory
and ``initialize_job`` adopts them. The tests below drive two REAL
processes through the loader's exit agreement (SIGTERM -> ``_check_exit``
-> save -> 143) and ``initialize_job`` (the mechanism of
``test_trace_id_survives_worker_kill_mid_rescale``), with no journal
configured, and read only what the successor's ring buffer holds."""

from __future__ import annotations

import ast
import dis
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from adaptdl_tpu import _signal, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent(
    """
    import json
    import os
    import sys
    import time

    import numpy as np

    import adaptdl_tpu
    from adaptdl_tpu import checkpoint, epoch, trace
    from adaptdl_tpu.data import AdaptiveDataLoader


    class Blob(checkpoint.State):
        def __init__(self):
            super().__init__("model")
            self.payload = b"x" * 4096

        def save(self, fileobj):
            fileobj.write(self.payload)

        def load(self, fileobj):
            self.payload = fileobj.read()


    adaptdl_tpu.initialize_job()
    blob = Blob()
    restored = checkpoint.load_state(blob)
    successor = os.environ["WORKER_PHASE"] == "successor"
    assert bool(restored) == successor
    loader = AdaptiveDataLoader(
        {"x": np.arange(1 << 16, dtype=np.float32)}, batch_size=8
    )
    steps = 0
    for _ in epoch.remaining_epochs_until(10**6):
        for batch in loader:
            time.sleep(0.005)
            steps += 1
            if steps == 3 and not successor:
                print("READY", flush=True)
            if steps == 3 and successor:
                with open(os.environ["WORKER_OUT"], "w") as f:
                    json.dump(
                        {"pid": os.getpid(),
                         "spans": trace.snapshot_spans()}, f
                    )
                sys.exit(0)
    """
)

EXIT_SPANS = ["exit.agree", "ckpt.snapshot", "ckpt.write", "exit.atexit"]
BOOT_SPANS = ["boot.process", "boot.import", "restart.first_step"]


def _rescale(tmp_path, **extra_env) -> dict:
    """Incarnation 0 until SIGTERM -> 143, then incarnation 1 for three
    steps; what the successor's ``snapshot_spans()`` held."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    out = tmp_path / "successor.json"
    ckpt = tmp_path / "ckpt"
    traceparent = trace.new_traceparent()
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("ADAPTDL_TRACE_DIR", "ADAPTDL_SUPERVISOR_URL")
    }
    env.update(
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        ADAPTDL_CHECKPOINT_PATH=str(ckpt),
        ADAPTDL_TRACEPARENT=traceparent,
        ADAPTDL_NUM_REPLICAS="1",
        WORKER_OUT=str(out),
        **extra_env,
    )
    doomed = subprocess.Popen(
        [sys.executable, str(script)],
        env=dict(env, WORKER_PHASE="doomed", ADAPTDL_NUM_RESTARTS="0"),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        assert doomed.stdout.readline().strip() == "READY", (
            doomed.stderr.read()[-2000:]
        )
        sent_at = time.time()
        os.kill(doomed.pid, signal.SIGTERM)
        _, err = doomed.communicate(timeout=120)
    finally:
        if doomed.poll() is None:
            doomed.kill()
            doomed.wait()
    assert doomed.returncode == 143, err[-2000:]
    handover = ckpt / trace.HANDOVER_FILE
    written = handover.exists()
    successor = subprocess.run(
        [sys.executable, str(script)],
        env=dict(env, WORKER_PHASE="successor", ADAPTDL_NUM_RESTARTS="1"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert successor.returncode == 0, successor.stderr[-2000:]
    result = json.loads(out.read_text())
    return {
        "spans": result["spans"],
        "successor_pid": result["pid"],
        "doomed_pid": doomed.pid,
        "sent_at": sent_at,
        "trace_id": trace.parse_traceparent(traceparent)[0],
        "written": written,
        "leftovers": sorted(os.listdir(ckpt)),
    }


@pytest.fixture(scope="module")
def rescale(tmp_path_factory):
    return _rescale(tmp_path_factory.mktemp("handover"))


def _last(run, name):
    found = [r for r in run["spans"] if r["name"] == name]
    assert found, (name, sorted({r["name"] for r in run["spans"]}))
    return found[-1]


@pytest.mark.parametrize("name", EXIT_SPANS)
def test_successor_holds_the_predecessors_exit_span(rescale, name):
    rec = _last(rescale, name)
    assert rec["inc"] == 0 and rec["pid"] == rescale["doomed_pid"]
    assert rec["trace"] == rescale["trace_id"]


@pytest.mark.parametrize("name", BOOT_SPANS)
def test_successor_holds_its_own_boot_span(rescale, name):
    rec = _last(rescale, name)
    assert rec["inc"] == 1 and rec["pid"] == rescale["successor_pid"]
    assert rec["trace"] == rescale["trace_id"]


def test_rescale_reads_in_time_order_from_the_signal(rescale):
    recs = [_last(rescale, name) for name in EXIT_SPANS + BOOT_SPANS]
    starts = [r["ts"] for r in recs]
    assert starts == sorted(starts), list(zip(EXIT_SPANS + BOOT_SPANS, starts))
    # The buffer itself is in that order too: the adopted records come
    # before anything the successor recorded.
    incs = [r["inc"] for r in rescale["spans"]]
    assert incs == sorted(incs)
    seqs = [r["seq"] for r in rescale["spans"]]
    assert seqs == sorted(set(seqs))
    agree = recs[0]
    # The span starts on the handler's clock, not where the loader
    # noticed: within 50 ms of when the test sent the signal.
    assert abs(agree["ts"] - rescale["sent_at"]) < 0.05
    assert agree["attrs"]["replicas"] == 1
    assert agree["attrs"]["notice"] is False
    # signal -> agreement -> snapshot -> write -> atexit, each starting
    # no earlier than the one before it ended (1 ms of clock slack).
    for before, after in zip(recs[:3], recs[1:4]):
        assert after["ts"] >= before["ts"] + before["dur"] - 1e-3
    # The predecessor was gone before the successor's process started.
    atexit_, process = recs[3], recs[4]
    assert process["ts"] >= atexit_["ts"] + atexit_["dur"] - 0.02


def test_boot_process_contains_boot_import(rescale):
    process = _last(rescale, "boot.process")
    imports = _last(rescale, "boot.import")
    assert imports["parent"] == process["span"]
    assert process["ts"] <= imports["ts"]
    assert (
        imports["ts"] + imports["dur"]
        <= process["ts"] + process["dur"] + 1e-3
    )
    assert 0 < imports["dur"] <= process["dur"]
    assert imports["attrs"]["modules"] > 0
    assert process["attrs"] == {
        "restarts": 1, "jax_preloaded": False, "backend_ready": False,
    }
    # restart.first_step opens where boot.process ends.
    first = _last(rescale, "restart.first_step")
    assert abs(first["ts"] - (process["ts"] + process["dur"])) < 0.05


def test_handover_file_is_not_a_checkpoint(rescale):
    assert rescale["written"]
    assert trace.HANDOVER_FILE in rescale["leftovers"]
    assert not trace.HANDOVER_FILE.startswith(("checkpoint-", "_tmp-"))
    # Written whole and renamed: no temporary name is left behind.
    assert not [n for n in rescale["leftovers"] if ".tmp-" in n]


def test_trace_off_writes_and_reads_nothing(tmp_path):
    run = _rescale(tmp_path, ADAPTDL_TRACE="off")
    assert not run["written"]
    assert trace.HANDOVER_FILE not in run["leftovers"]
    assert run["spans"] == []


# ---- the file's guards, in process -----------------------------------


def _as_incarnation(monkeypatch, ckpt, restarts):
    trace._reset_state()
    if ckpt is None:
        monkeypatch.delenv("ADAPTDL_CHECKPOINT_PATH", raising=False)
    else:
        monkeypatch.setenv("ADAPTDL_CHECKPOINT_PATH", str(ckpt))
    monkeypatch.setenv("ADAPTDL_NUM_RESTARTS", str(restarts))


def _leave_handover(monkeypatch, ckpt, restarts, events=3) -> float:
    _as_incarnation(monkeypatch, ckpt, restarts)
    since = time.time()
    for i in range(events):
        trace.record_span("exit.agree", 0.01, ts=since + i * 1e-3, i=i)
    assert trace.write_handover(since) is (ckpt is not None)
    return since


def test_adjacent_incarnation_is_adopted_under_its_own_identity(
    tmp_path, monkeypatch
):
    _leave_handover(monkeypatch, tmp_path, restarts=4)
    theirs = trace.snapshot_spans()
    _as_incarnation(monkeypatch, tmp_path, restarts=5)
    assert trace.adopt_handover() == 3
    mine = trace.snapshot_spans()
    assert [(r["inc"], r["ts"], r["span"]) for r in mine] == [
        (4, r["ts"], r["span"]) for r in theirs
    ]
    # Adopted records are new to this process's supervisor flush...
    assert [r["seq"] for r in mine] == [1, 2, 3]
    # ...but were observed (histograms) where they were recorded.
    assert "exit.agree" not in trace.prometheus_lines()


@pytest.mark.parametrize(
    "case", ["stale", "torn_tail", "torn_line", "no_file", "no_path", "off"]
)
def test_nothing_is_adopted_and_nothing_raises(case, tmp_path, monkeypatch):
    ckpt = None if case == "no_path" else tmp_path
    _leave_handover(
        monkeypatch, ckpt, restarts=3 if case == "stale" else 4
    )
    path = tmp_path / trace.HANDOVER_FILE
    if case == "torn_tail":
        path.write_bytes(path.read_bytes()[:-20])
    elif case == "torn_line":
        lines = path.read_bytes().split(b"\n")
        lines[1] = lines[1][:30]
        path.write_bytes(b"\n".join(lines))
    elif case == "no_file":
        path.unlink()
    _as_incarnation(monkeypatch, ckpt, restarts=5)
    if case == "off":
        monkeypatch.setenv("ADAPTDL_TRACE", "off")
        before = path.read_bytes()
        assert trace.write_handover(0.0) is False
        assert path.read_bytes() == before
    assert trace.adopt_handover() == 0
    assert trace.snapshot_spans() == []
    if case == "no_path":
        assert trace.handover_path() is None
        assert not path.exists()


def test_handover_is_bounded(tmp_path, monkeypatch):
    n = trace.HANDOVER_MAX_RECORDS
    assert n == 256
    _leave_handover(monkeypatch, tmp_path, restarts=0, events=n + 144)
    lines = (tmp_path / trace.HANDOVER_FILE).read_text().splitlines()
    assert len(lines) == n
    assert json.loads(lines[-1])["attrs"]["i"] == n + 143  # the newest
    _as_incarnation(monkeypatch, tmp_path, restarts=1)
    assert trace.adopt_handover() == n
    assert len(trace.snapshot_spans()) == n


def test_records_before_the_signal_stay_behind(tmp_path, monkeypatch):
    _as_incarnation(monkeypatch, tmp_path, restarts=0)
    trace.record_span("step.calibrate", 1.0)  # started a second ago
    since = time.time()
    trace.record_span("exit.agree", 0.0, ts=since)
    assert trace.write_handover(since)
    names = [
        r["name"]
        for r in trace.read_journal(str(tmp_path / trace.HANDOVER_FILE))
    ]
    assert names == ["exit.agree"]


# ---- the handler stays a handler -------------------------------------


def test_handler_does_two_stores_and_nothing_else():
    ops = list(dis.get_instructions(_signal._handler))
    stores = [i.argval for i in ops if i.opname.startswith("STORE")]
    assert sorted(stores) == ["_exit_flag", "_signal_time"]
    calls = [
        i.argval for i in ops
        if i.opname in ("LOAD_GLOBAL", "LOAD_ATTR", "LOAD_METHOD")
    ]
    assert set(calls) <= {"_signal_time", "time"}  # time.time(), no lock


def test_signal_module_imports_only_signal_and_time():
    with open(_signal.__file__, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "signal", "time"}


def test_only_the_first_signal_sets_the_time():
    _signal.set_exit_flag(False)
    try:
        assert _signal.signal_time() is None
        _signal._handler(signal.SIGTERM, None)
        first = _signal.signal_time()
        assert first is not None and _signal.get_exit_flag()
        time.sleep(0.002)
        _signal._handler(signal.SIGTERM, None)
        _signal.set_exit_flag(True)  # the preemption path's store
        assert _signal.signal_time() == first
    finally:
        _signal.set_exit_flag(False)
    assert _signal.signal_time() is None
    _signal.set_exit_flag(True)
    try:
        assert _signal.signal_time() is not None  # stamps it too
    finally:
        _signal.set_exit_flag(False)
