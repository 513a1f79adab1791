"""Resilience subsystem: anomaly detection, rollback, watchdog, fault
injection, checkpoint-corruption recovery, and the supervisor relauncher.

The e2e tests drive the full loop the package exists for — inject a fault,
detect it, recover, finish training — on CPU, through the real Trainer.
Subprocess tests (watchdog exit codes, supervisor relaunch) reuse the
test_multiprocess.py idiom: single-device children, XLA_FLAGS stripped.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import pytest

from pretraining_llm_tpu.config import ResilienceConfig, get_preset
from pretraining_llm_tpu.resilience import (
    EXIT_WEDGED,
    Anomaly,
    AnomalyDetector,
    StepWatchdog,
    parse_faults,
)
from pretraining_llm_tpu.resilience.faults import truncate_leaf
from pretraining_llm_tpu.training import checkpoint as ckpt
from pretraining_llm_tpu.training.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN = os.path.join(REPO, "scripts", "train.py")
SUPERVISOR = os.path.join(REPO, "scripts", "supervisor.py")


def _rcfg(**kw):
    return ResilienceConfig(anomaly_detection=True, **kw)


def _resilient_config(tmp_path, **overrides):
    cfg = get_preset("tiny")
    train_kw = {
        "train_steps": 16,
        "checkpoint_interval": 4,
        "log_interval": 2,
        "eval_interval": 0,
        "checkpoint_dir": str(tmp_path / "ck"),
        "metrics_path": str(tmp_path / "metrics.jsonl"),
    }
    res_kw = {"anomaly_detection": True}
    for key, val in overrides.items():
        section, _, name = key.partition(".")
        (train_kw if section == "train" else res_kw)[name] = val
    return cfg.replace(
        train=dataclasses.replace(cfg.train, **train_kw),
        resilience=ResilienceConfig(**res_kw),
    )


def _events(tmp_path):
    path = tmp_path / "metrics.jsonl"
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines()]


# ---------------------------------------------------------------- unit: config


def test_resilience_config_validates():
    with pytest.raises(ValueError):
        ResilienceConfig(anomaly_window=1)
    with pytest.raises(ValueError):
        ResilienceConfig(loss_spike_factor=1.0)
    with pytest.raises(ValueError):
        ResilienceConfig(rollback_budget=-1)
    with pytest.raises(ValueError):
        ResilienceConfig(faults="nan@0")
    with pytest.raises(ValueError):
        ResilienceConfig(faults="frobnicate@5")
    ResilienceConfig(faults="nan@9, sigterm@20")  # valid plan constructs


def test_parse_faults():
    assert parse_faults("nan@9,sigterm@20") == [("nan", 9), ("sigterm", 20)]
    with pytest.raises(ValueError, match="empty"):
        parse_faults("")  # an all-empty plan is a config typo, not a no-op
    with pytest.raises(ValueError, match="hang"):
        parse_faults("hang")  # missing @step
    with pytest.raises(ValueError, match="bogus"):
        parse_faults("bogus@3")


# -------------------------------------------------------------- unit: detector


def test_detector_flags_nonfinite_immediately():
    det = AnomalyDetector(_rcfg())
    # NaN/Inf checks are armed from the first sample — no warmup.
    a = det.observe(1, {"loss": float("nan"), "grad_norm": 1.0})
    assert a is not None and a.kind == "nan"
    a = det.observe(2, {"loss": 2.0, "grad_norm": float("inf")})
    assert a is not None and a.kind == "nan"


def test_detector_spike_needs_history():
    det = AnomalyDetector(_rcfg(anomaly_min_history=5, loss_spike_factor=3.0))
    # Below min_history no spike can fire, however large the value.
    for step in range(1, 5):
        assert det.observe(step, {"loss": 2.0, "grad_norm": 1.0}) is None
    assert det.observe(5, {"loss": 1000.0, "grad_norm": 1.0}) is None
    for step in range(6, 8):
        assert det.observe(step, {"loss": 2.0, "grad_norm": 1.0}) is None
    a = det.observe(8, {"loss": 50.0, "grad_norm": 1.0})
    assert a is not None and a.kind == "loss_spike"
    # The spike was NOT folded into the baseline: an immediately following
    # normal sample is clean, and the same spike re-fires.
    assert det.observe(9, {"loss": 2.0, "grad_norm": 1.0}) is None
    assert det.observe(10, {"loss": 50.0, "grad_norm": 1.0}) is not None


def test_detector_grad_spike_and_reset():
    det = AnomalyDetector(_rcfg(anomaly_min_history=3, grad_spike_factor=10.0))
    for step in range(1, 6):
        assert det.observe(step, {"loss": 2.0, "grad_norm": 0.5}) is None
    a = det.observe(6, {"loss": 2.0, "grad_norm": 25.0})
    assert a is not None and a.kind == "grad_spike"
    det.reset()
    # Post-reset the baseline is empty again: spikes need fresh history.
    assert det.observe(7, {"loss": 2.0, "grad_norm": 25.0}) is None


def test_anomaly_event_shape():
    event = Anomaly("loss_spike", 10, 50.0, 6.0).as_event()
    assert event["event"] == "anomaly_detected"
    assert event["kind"] == "loss_spike"
    assert event["step"] == 10


# -------------------------------------------------------------- unit: watchdog


def test_watchdog_fires_and_reports_exit_code():
    codes = []
    timeouts = []
    dog = StepWatchdog(
        0.2,
        on_timeout=lambda: timeouts.append(True),
        exit_fn=codes.append,
    ).start()
    try:
        dog.heartbeat()  # arm
        deadline = time.monotonic() + 5.0
        # exit_fn is the last thing the fire path does, after the stacks of every
        # thread of the process are dumped: ``fired`` alone is set long before it
        while not codes and time.monotonic() < deadline:
            time.sleep(0.05)
        assert dog.fired
        assert codes == [EXIT_WEDGED]
        assert timeouts == [True]
    finally:
        dog.stop()


def test_watchdog_heartbeats_keep_it_quiet():
    codes = []
    dog = StepWatchdog(0.4, exit_fn=codes.append).start()
    try:
        for _ in range(6):
            dog.heartbeat()
            time.sleep(0.1)
        assert not dog.fired and codes == []
    finally:
        dog.stop()
    # ...and it never fires before the first heartbeat arms it (compile time).
    lazy = StepWatchdog(0.2, exit_fn=codes.append).start()
    try:
        time.sleep(0.5)
        assert not lazy.fired and codes == []
    finally:
        lazy.stop()


def test_watchdog_rejects_nonpositive_timeout():
    with pytest.raises(ValueError):
        StepWatchdog(0.0)


def test_watchdog_pause_covers_slow_offpath_work():
    """A save/eval longer than the timeout must not fire while paused."""
    codes = []
    dog = StepWatchdog(0.2, exit_fn=codes.append).start()
    try:
        dog.heartbeat()  # arm
        dog.pause()
        time.sleep(0.6)  # "slow checkpoint save": 3x the timeout
        assert not dog.fired and codes == []
        dog.resume()
        # resume() re-armed with a fresh beat: paused time isn't charged...
        time.sleep(0.1)
        assert not dog.fired
        # ...but a genuine post-resume stall still fires.
        deadline = time.monotonic() + 5.0
        while not dog.fired and time.monotonic() < deadline:
            time.sleep(0.05)
        assert dog.fired and codes == [EXIT_WEDGED]
    finally:
        dog.stop()


def test_watchdog_pause_before_arming_stays_disarmed():
    """pause/resume before the first heartbeat must not arm the watchdog —
    compile time stays excluded."""
    codes = []
    dog = StepWatchdog(0.2, exit_fn=codes.append).start()
    try:
        dog.pause()
        dog.resume()
        time.sleep(0.5)
        assert not dog.fired and codes == []
    finally:
        dog.stop()


# ------------------------------------------------- checkpoint corruption


def _write_two_checkpoints(tmp_path):
    """Train 8 steps with interval 4 -> step-4 and step-8 on disk."""
    cfg = _resilient_config(tmp_path, **{"train.train_steps": 8})
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    ckdir = cfg.train.checkpoint_dir
    assert sorted(ckpt._list_steps(ckdir)) == [4, 8]
    return cfg, ckdir


def test_restore_skips_truncated_leaf(tmp_path):
    cfg, ckdir = _write_two_checkpoints(tmp_path)
    truncate_leaf(os.path.join(ckdir, "step-8"))
    t2 = Trainer(cfg, synthetic_data=True, resume=True)
    assert t2.start_step == 4
    kinds = [e.get("event") for e in _events(tmp_path)]
    assert "checkpoint_skipped" in kinds


def test_restore_skips_missing_metadata(tmp_path):
    cfg, ckdir = _write_two_checkpoints(tmp_path)
    os.remove(os.path.join(ckdir, "step-8", "metadata.json"))
    t2 = Trainer(cfg, synthetic_data=True, resume=True)
    assert t2.start_step == 4


def test_restore_ignores_and_gcs_partial_tmp_dir(tmp_path):
    cfg, ckdir = _write_two_checkpoints(tmp_path)
    partial = os.path.join(ckdir, "tmp-12")
    os.makedirs(partial)
    with open(os.path.join(partial, "half_written.npy"), "wb") as f:
        f.write(b"\x93NUMPY garbage")
    t2 = Trainer(cfg, synthetic_data=True, resume=True)
    assert t2.start_step == 8
    assert not os.path.exists(partial)  # GC'd on restore


def test_all_checkpoints_corrupt_refuses_to_reinitialize(tmp_path):
    cfg, ckdir = _write_two_checkpoints(tmp_path)
    for step in (4, 8):
        os.remove(os.path.join(ckdir, f"step-{step}", "metadata.json"))
    with pytest.raises(RuntimeError, match="none are loadable"):
        Trainer(cfg, synthetic_data=True, resume=True)


# ------------------------------------------------------------ e2e: in-process


def test_nan_injection_rolls_back_and_completes(tmp_path):
    """The headline loop: NaN at step 9 -> detected at the step-10 log
    boundary -> rollback to step-8 -> data frontier skips the poison window
    -> training still reaches step 16 with finite loss."""
    cfg = _resilient_config(tmp_path, **{"resilience.faults": "nan@9"})
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    final = trainer.train()
    assert trainer.exit_reason == "completed"
    assert math.isfinite(final["loss"])

    events = _events(tmp_path)
    kinds = [e.get("event") for e in events]
    assert "fault_injected" in kinds
    assert "anomaly_detected" in kinds
    rollbacks = [e for e in events if e.get("event") == "rollback"]
    assert len(rollbacks) == 1
    assert rollbacks[0]["from_step"] == 10
    assert rollbacks[0]["to_step"] == 8
    assert rollbacks[0]["skipped_batches"] == 2
    # Training genuinely continued past the rollback to the target step.
    steps = [e["step"] for e in events if "loss" in e and "step" in e]
    assert steps[-1] == 16
    assert all(math.isfinite(e["loss"]) for e in events if "loss" in e and e["step"] > 10)


def test_rollback_budget_exhaustion_stops_the_run(tmp_path):
    # 14 steps (NOT a multiple of checkpoint_interval=4): with the run
    # breaking early, an unguarded save_final would persist the poisoned
    # (NaN) state as a mislabeled step-14 — newest in the dir, corrupting
    # every later resume.
    cfg = _resilient_config(
        tmp_path,
        **{
            "train.train_steps": 14,
            "resilience.faults": "nan@9",
            "resilience.rollback_budget": 0,
        },
    )
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    assert trainer.exit_reason == "anomaly_budget"
    kinds = [e.get("event") for e in _events(tmp_path)]
    assert "rollback_budget_exhausted" in kinds
    # Newest on disk stays the last good in-loop save (step-8: the run
    # broke at the step-10 log boundary), and resume lands on it.
    assert max(ckpt._list_steps(cfg.train.checkpoint_dir)) == 8
    t2 = Trainer(cfg, synthetic_data=True, resume=True)
    assert t2.start_step == 8


def test_anomaly_without_checkpoint_stops_the_run(tmp_path):
    cfg = _resilient_config(
        tmp_path,
        **{"train.checkpoint_interval": 0, "resilience.faults": "nan@3"},
    )
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    assert trainer.exit_reason == "anomaly_no_checkpoint"


def test_sigterm_fault_checkpoints_and_reports_preempted(tmp_path):
    cfg = _resilient_config(tmp_path, **{"resilience.faults": "sigterm@6"})
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    assert trainer.exit_reason == "preempted"
    # The preemption path checkpointed at the stop boundary.
    assert max(ckpt._list_steps(cfg.train.checkpoint_dir)) >= 6


def test_ckpt_truncate_fault_then_resume_falls_back(tmp_path):
    """Torn-write drill end-to-end: the fault truncates a leaf of step-8
    right after it lands; a later resume must dig back to step-4."""
    # 9 steps, not 8: the fault fires at the top of the loop iteration
    # AFTER step 8's checkpoint lands, so the run must still have one
    # iteration left to execute. save_final off, or the end-of-run step-9
    # checkpoint would mask the torn step-8.
    cfg = _resilient_config(
        tmp_path,
        **{
            "train.train_steps": 9,
            "train.save_final": False,
            "resilience.faults": "ckpt_truncate@8",
        },
    )
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    kinds = [e.get("event") for e in _events(tmp_path)]
    assert "fault_injected" in kinds
    t2 = Trainer(cfg, synthetic_data=True, resume=True)
    assert t2.start_step == 4


def test_resumed_run_does_not_refire_spent_faults(tmp_path):
    cfg = _resilient_config(tmp_path, **{"resilience.faults": "nan@9"})
    trainer = Trainer(cfg, synthetic_data=True, resume=False)
    trainer.train()
    assert trainer.exit_reason == "completed"
    # Resume from the final checkpoint (step 16 == train_steps): a second
    # train() call in a fresh Trainer must not re-inject nan@9.
    more = cfg.replace(train=dataclasses.replace(cfg.train, train_steps=20))
    t2 = Trainer(more, synthetic_data=True, resume=True)
    assert t2.start_step == 16
    final = t2.train()
    assert t2.exit_reason == "completed"
    assert math.isfinite(final["loss"])
    injected = [
        e for e in _events(tmp_path) if e.get("event") == "fault_injected"
    ]
    assert len(injected) == 1  # only the first run's


# ------------------------------------------------------------ e2e: subprocess


def _run_child(cmd, timeout):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # children run single-device: fast compile
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        cmd,
        env=env,
        cwd=REPO,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"child timed out:\n{out[-3000:]}")
    return proc.returncode, out


def _train_cmd(ckdir, steps=20, extra=()):
    return [
        sys.executable, TRAIN, "--preset", "tiny", "--data", "synthetic",
        "--steps", str(steps), "--override",
        f"train.checkpoint_dir={ckdir}",
        "train.log_interval=2", "train.checkpoint_interval=5",
        *extra,
    ]


@pytest.mark.slow
def test_watchdog_exits_wedged_with_emergency_checkpoint(tmp_path):
    ckdir = str(tmp_path / "ck")
    rc, out = _run_child(
        _train_cmd(ckdir, extra=[
            "resilience.watchdog_timeout_s=2.0", "resilience.faults=hang@6",
        ]),
        timeout=240,
    )
    assert rc == EXIT_WEDGED, out[-3000:]
    # The watchdog persisted the last completed step before exiting...
    assert 6 in ckpt._list_steps(ckdir), out[-3000:]
    # ...and dumped thread stacks for the postmortem.
    assert "watchdog" in out and "_fire_hang" in out, out[-3000:]


@pytest.mark.slow
def test_supervisor_relaunches_after_wedge_and_completes(tmp_path):
    ckdir = str(tmp_path / "ck")
    cmd = [
        sys.executable, SUPERVISOR,
        "--max-restarts", "3", "--backoff-base", "0.2", "--",
        *_train_cmd(ckdir, extra=[
            "resilience.watchdog_timeout_s=2.0", "resilience.faults=hang@6",
        ]),
    ]
    rc, out = _run_child(cmd, timeout=420)
    assert rc == 0, out[-3000:]
    # First launch wedged at 6; the relaunch resumed (hang@6 <= start step
    # is spent) and ran to the target.
    assert 20 in ckpt._list_steps(ckdir), out[-3000:]
    sup = [json.loads(l) for l in out.splitlines() if l.startswith('{"supervisor"')]
    sup_events = [e["event"] for e in sup]
    assert sup_events.count("launch") == 2
    assert "relaunch" in sup_events
    exits = [e["rc"] for e in sup if e["event"] == "exit"]
    assert exits == [EXIT_WEDGED, 0]


def test_supervisor_gives_up_on_anomaly_exit_code(tmp_path):
    """EXIT_ANOMALY is fatal: the supervisor must NOT relaunch."""
    marker = tmp_path / "launches.txt"
    child = (
        "import sys, pathlib; "
        f"p = pathlib.Path({str(marker)!r}); "
        "p.write_text(p.read_text() + 'x' if p.exists() else 'x'); "
        "sys.exit(44)"
    )
    cmd = [
        sys.executable, SUPERVISOR, "--max-restarts", "5",
        "--backoff-base", "0.05", "--", sys.executable, "-c", child,
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 44
    assert marker.read_text() == "x"  # exactly one launch


def test_supervisor_restart_budget(tmp_path):
    """A persistent crash burns the restart budget then surfaces the code."""
    cmd = [
        sys.executable, SUPERVISOR, "--max-restarts", "2",
        "--backoff-base", "0.05", "--",
        sys.executable, "-c", "import sys; sys.exit(7)",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 7
    sup = [
        json.loads(l) for l in proc.stdout.splitlines()
        if l.startswith('{"supervisor"')
    ]
    assert [e["event"] for e in sup].count("launch") == 3  # 1 + 2 restarts


def test_supervisor_wedge_never_resets_failure_count(tmp_path):
    """EXIT_WEDGED must not reset the failure counter, however long the
    child lived: a wedged child's lifetime includes the whole watchdog
    timeout spent hung. --healthy-secs 0 makes every exit 'healthy' by
    wall clock — with the reset applying to wedges this loops forever."""
    cmd = [
        sys.executable, SUPERVISOR, "--max-restarts", "2",
        "--backoff-base", "0.05", "--healthy-secs", "0", "--",
        sys.executable, "-c", f"import sys; sys.exit({EXIT_WEDGED})",
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert proc.returncode == EXIT_WEDGED
    sup = [
        json.loads(l) for l in proc.stdout.splitlines()
        if l.startswith('{"supervisor"')
    ]
    events = [e["event"] for e in sup]
    assert events.count("launch") == 3  # 1 + 2 restarts, then give up
    assert "failure_count_reset" not in events


def test_supervisor_forwards_sigterm_and_does_not_relaunch(tmp_path):
    """A TERM delivered to the supervisor ALONE must reach the child (no
    orphan) and surface the child's exit code without a relaunch."""
    import signal as _signal

    ready = tmp_path / "ready"
    child = (
        "import pathlib, signal, sys, time; "
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(43)); "
        f"pathlib.Path({str(ready)!r}).write_text('r'); "
        "time.sleep(120)"
    )
    cmd = [
        sys.executable, SUPERVISOR, "--backoff-base", "0.05", "--",
        sys.executable, "-c", child,
    ]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 30.0
        while not ready.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert ready.exists(), "child never came up under the supervisor"
        os.kill(proc.pid, _signal.SIGTERM)  # supervisor only, not the group
        out, _ = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 43  # the child's EXIT_PREEMPTED, surfaced
    sup = [json.loads(l) for l in out.splitlines() if l.startswith('{"supervisor"')]
    events = [e["event"] for e in sup]
    assert events.count("launch") == 1  # terminated supervisors don't relaunch
    assert "terminated" in events
