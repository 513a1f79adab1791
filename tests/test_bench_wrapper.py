"""Unit tests for bench.py's candidate-racing wrapper.

The wrapper is the driver's only window onto the chip; its failure handling
is load-bearing (round-2 recorded an unattributable 0.0 for the whole round).
These tests drive `wrapper_main` with monkeypatched `_attempt`/`_run_canary`
to pin the round-3 on-chip lessons:

  * a hung attempt triggers a cheap canary before more budget is spent;
  * a wedged backend (canary dead after the kill) is polled for recovery
    instead of burning full attempt timeouts, and reported as an
    ENVIRONMENT error if it never returns;
  * a candidate that hangs twice (with recovery between) is abandoned —
    retrying a chip-wedging program forever would wedge the chip forever.
"""

import json

import bench


class _FakeTime:
    """Deterministic clock: sleep() advances it, monotonic() reads it."""

    def __init__(self):
        self.t = 0.0

    def monotonic(self):
        return self.t

    def sleep(self, s):
        self.t += s

    def perf_counter(self):  # pragma: no cover - not used by the wrapper
        return self.t


def _wrapper_args(**over):
    # race_repeats=1 keeps the candidate-racing tests single-sample; the
    # median-of-N repeat pass has its own dedicated tests below.
    opts = {"preset": "gpt2-124m", "timeout_budget": "600",
            "race_repeats": "1"}
    opts.update({k: str(v) for k, v in over.items()})
    argv = ["--skip-canary"]
    for k, v in opts.items():
        argv += [f"--{k.replace('_', '-')}", v]
    return bench.parse_args(argv)


def _run(monkeypatch, capsys, attempts_script, canary_script, args=None):
    """Run wrapper_main with scripted attempt/canary outcomes.

    attempts_script: list of (rec|None, err) popped per _attempt call; a hang
    advances the fake clock by the attempt timeout (like a real kill would).
    canary_script: list of (ok, detail) popped per _run_canary call; the
    last entry repeats forever.
    """
    ft = _FakeTime()
    monkeypatch.setattr(bench, "time", ft)
    calls = {"attempts": [], "canaries": 0}

    def fake_attempt(a, remat, timeout, attention="", batch_override=0,
                     ce_override=""):
        rec, err = attempts_script.pop(0)
        calls["attempts"].append((remat, attention))
        calls.setdefault("batches", []).append(batch_override)
        calls.setdefault("ces", []).append(ce_override)
        ft.sleep(timeout if "hung" in err else 5.0)
        return rec, err

    def fake_canary(timeout):
        i = min(calls["canaries"], len(canary_script) - 1)
        calls["canaries"] += 1
        ft.sleep(5.0 if canary_script[i][0] else timeout)
        return canary_script[i]

    monkeypatch.setattr(bench, "_attempt", fake_attempt)
    monkeypatch.setattr(bench, "_run_canary", fake_canary)
    rc = bench.wrapper_main(args or _wrapper_args())
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(out), calls


def _ok(value, remat):
    return ({"metric": "mfu_gpt2-124m_train", "value": value,
             "unit": "fraction_of_peak_bf16", "vs_baseline": value / 0.5,
             "remat": remat}, "")


HUNG = (None, "hung past 150s (killed)")


def test_hang_with_live_canary_moves_to_next_candidate(monkeypatch, capsys):
    # Candidate 1 hangs; canary says the backend is fine => the program was
    # the problem; candidate 2 succeeds and is reported.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[HUNG, _ok(0.41, "save_attn"), _ok(0.39, "save_attn"),
                        _ok(0.38, "none"), _ok(0.37, "none")],
        canary_script=[(True, {"ok": True})],
    )
    assert rc == 0
    assert rec["value"] == 0.41
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn", "none", "none"]
    # Rungs reach the inner run at THEIR batch and CE head (the r5
    # save_attn_res+dense rung leads, then the save_attn pair, then none).
    assert calls["batches"] == [0, 0, 0, 8, 8]
    assert calls["ces"] == ["dense", "dense", "", "dense", ""]
    assert calls["canaries"] == 1  # exactly one cheap probe after the hang


def test_wedged_backend_is_an_environment_error(monkeypatch, capsys):
    # Hang, then the canary never answers again: the wrapper must poll
    # canaries (not burn full attempts) and report an environment error.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[HUNG],
        canary_script=[(False, "canary hung past 150s (backend unreachable)")],
    )
    assert rc == 1
    assert rec["value"] == 0.0
    assert rec.get("environment_error") is True
    assert "wedged" in rec["error"]
    # Only the first attempt burned a full timeout; everything after was
    # cheap canary polls.
    assert len(calls["attempts"]) == 1
    assert calls["canaries"] >= 2


def test_wedged_then_recovered_retries_same_candidate(monkeypatch, capsys):
    # Hang -> canary dead -> canary recovers -> the SAME candidate gets one
    # retry and succeeds. (Budget must outlive the burnt share: a hang costs
    # min(attempt_timeout, share), so share > 2*attempt_timeout + polls.)
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[HUNG, _ok(0.40, "save_attn_res"),
                        _ok(0.38, "save_attn"), _ok(0.37, "save_attn"),
                        _ok(0.36, "none"), _ok(0.35, "none")],
        canary_script=[(False, "dead"), (True, {"ok": True})],
        args=_wrapper_args(timeout_budget=4200, attempt_timeout=150),
    )
    assert rc == 0
    assert rec["value"] == 0.40  # best of the race, from the retried candidate
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn_res", "save_attn", "save_attn",
        "none", "none"]


def test_double_hang_abandons_candidate(monkeypatch, capsys):
    # A candidate that hangs twice (backend recovering in between) is the
    # problem itself; the wrapper must move on, not wedge the chip a third
    # time.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[HUNG, HUNG, _ok(0.39, "save_attn"),
                        _ok(0.38, "save_attn"), _ok(0.37, "none"),
                        _ok(0.36, "none")],
        canary_script=[(False, "dead"), (True, {"ok": True})],
        args=_wrapper_args(timeout_budget=4200, attempt_timeout=150),
    )
    assert rc == 0
    assert rec["value"] == 0.39
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn_res", "save_attn", "save_attn",
        "none", "none"]


def test_wedge_with_banked_result_reports_it_immediately(monkeypatch, capsys):
    # Candidate 1 already banked a number; candidate 2 hangs and wedges the
    # backend. The wrapper must report the banked result NOW, not poll the
    # dead backend for the rest of the budget.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.30, "save_big"), HUNG],
        canary_script=[(False, "dead")],
    )
    assert rc == 0
    assert rec["value"] == 0.30
    assert len(calls["attempts"]) == 2
    assert calls["canaries"] == 1  # one classifying probe, zero polling


def test_race_reports_best_of_successes(monkeypatch, capsys):
    # Both new policies succeed: the better number wins and the known-good
    # tail is never run (budget preserved).
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.41, "save_attn_res"), _ok(0.40, "save_attn"),
                        _ok(0.39, "save_attn"), _ok(0.30, "none"),
                        _ok(0.28, "none")],
        canary_script=[(True, {"ok": True})],
    )
    assert rc == 0
    assert rec["value"] == 0.41
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn", "none", "none"]
    assert calls["batches"] == [0, 0, 0, 8, 8]
    # Every successful rung's measurement is banked on the winner (r4):
    # losing contenders' values must not vanish from the campaign log.
    assert [r["value"] for r in rec["rungs"]] == [
        0.41, 0.40, 0.39, 0.30, 0.28]


def test_race_repeats_bank_same_session_median(monkeypatch, capsys):
    # VERDICT #1: the race winner is re-run until --race-repeats same-config
    # samples exist; the record banks {best, median, n, spread} and a
    # value_median, while `value` keeps the best-sample series semantics.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.41, "save_attn_res"), _ok(0.40, "save_attn"),
                        _ok(0.39, "save_attn"), _ok(0.30, "none"),
                        _ok(0.28, "none"), _ok(0.37, "save_attn_res"),
                        _ok(0.44, "save_attn_res")],
        canary_script=[(True, {"ok": True})],
        args=_wrapper_args(race_repeats=3),
    )
    assert rc == 0
    # Repeats re-run the WINNER's exact config (save_attn_res + dense).
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn", "none", "none",
        "save_attn_res", "save_attn_res"]
    assert calls["ces"][-2:] == ["dense", "dense"]
    assert rec["race"] == {"best": 0.44, "median": 0.41, "n": 3,
                           "spread": 0.07, "values": [0.41, 0.37, 0.44]}
    assert rec["value_median"] == 0.41
    # A repeat that beats the original becomes the headline value...
    assert rec["value"] == 0.44
    # ...and every sample (5 race rungs + 2 repeats) stays in the evidence.
    assert len(rec["rungs"]) == 7


def test_race_repeat_failure_keeps_partial_samples(monkeypatch, capsys):
    # A deterministic failure during repeats must stop the sampling loop
    # cold (no retry ladder): the median is over the samples that exist.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.41, "save_attn_res"), _ok(0.40, "save_attn"),
                        _ok(0.39, "save_attn"), _ok(0.30, "none"),
                        _ok(0.28, "none"), _ok(0.39, "save_attn_res"),
                        (None, "rc=1: RuntimeError: boom")],
        canary_script=[(True, {"ok": True})],
        args=_wrapper_args(race_repeats=4),
    )
    assert rc == 0
    assert rec["value"] == 0.41
    assert rec["race"]["n"] == 2
    assert rec["race"]["values"] == [0.41, 0.39]
    assert rec["race"]["median"] == 0.4
    assert calls["canaries"] == 0  # not a hang: no probe burned


def test_hung_race_repeat_marks_wedge_and_reports(monkeypatch, capsys):
    # A repeat that hangs and kills the backend must still report the
    # collected samples NOW, marked backend_wedged for chained callers.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.41, "save_attn_res"), _ok(0.40, "save_attn"),
                        _ok(0.39, "save_attn"), _ok(0.30, "none"),
                        _ok(0.28, "none"), HUNG],
        canary_script=[(False, "dead")],
        args=_wrapper_args(race_repeats=3),
    )
    assert rc == 0
    assert rec["value"] == 0.41
    assert rec.get("backend_wedged") is True
    assert rec["race"]["n"] == 1
    assert calls["canaries"] == 1  # one classifying probe, zero polling


def test_explicit_batch_drops_override_rungs(monkeypatch, capsys):
    # `--batch 24` is a series point the caller chose; the race must not
    # silently answer it with a batch-8 measurement (code-review r4). With
    # the none@8 rung dropped there is no second CONTENDER, so a first-rung
    # success ends the race — the measured-slower save_big fallback must
    # not burn hardware window that cannot improve the number.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.40, "save_attn_res"), _ok(0.39, "save_attn"),
                        _ok(0.38, "save_attn")],
        canary_script=[(True, {"ok": True})],
        args=_wrapper_args(batch=24),
    )
    assert rc == 0
    assert rec["value"] == 0.40
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn"]
    assert calls["batches"] == [0, 0, 0]  # no per-candidate override in play
    assert calls["ces"] == ["dense", "dense", ""]  # ce rungs race at --batch


def test_matching_explicit_batch_keeps_override_rung(monkeypatch, capsys):
    # `--batch 8` equals the none rung's own batch: the rung stays, so a
    # banked none@8 race win is reproducible at its explicit batch.
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.40, "save_attn_res"), _ok(0.39, "save_attn"),
                        _ok(0.38, "save_attn"), _ok(0.52, "none"),
                        _ok(0.50, "none")],
        canary_script=[(True, {"ok": True})],
        args=_wrapper_args(batch=8),
    )
    assert rc == 0
    assert rec["value"] == 0.52
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn", "none", "none"]


def test_explicit_ce_drops_override_rungs(monkeypatch, capsys):
    # `--ce chunked` applies to every rung; the dense-overridden rung would
    # be a duplicate of its plain sibling (or a contradiction of the
    # caller's choice) and must not burn a contender share (code-review r4).
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[_ok(0.40, "save_attn"), _ok(0.38, "none")],
        canary_script=[(True, {"ok": True})],
        args=_wrapper_args(ce="chunked"),
    )
    assert rc == 0
    assert rec["value"] == 0.40
    assert [r for r, _ in calls["attempts"]] == ["save_attn", "none"]
    assert calls["ces"] == ["", ""]  # no per-candidate CE override in play


def test_oom_is_deterministic_not_transient(monkeypatch, capsys):
    # XLA OOM surfaces as RESOURCE_EXHAUSTED (a transient_markers match),
    # but retrying the identical compile only drains the rung's budget
    # share: one bounded attempt, then the next candidate (code-review r4).
    oom = (None, "rc=1: XlaRuntimeError: RESOURCE_EXHAUSTED: Out of memory "
                 "while trying to allocate 18.3GiB")
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[oom, _ok(0.41, "save_attn"), _ok(0.40, "save_attn"),
                        _ok(0.39, "none"), _ok(0.38, "none")],
        canary_script=[(True, {"ok": True})],
    )
    assert rc == 0
    assert rec["value"] == 0.41
    # Exactly ONE attempt on the OOM-ing candidate, no backoff retries.
    assert [r for r, _ in calls["attempts"]] == [
        "save_attn_res", "save_attn", "save_attn", "none", "none"]


def test_mode_flag_guards_reject_foreign_knobs():
    """Every mode rejects the other modes' knobs (a silently-ignored flag
    would bank a record indistinguishable from the baseline while the
    operator believes they measured the override config)."""
    import pytest

    cases = [
        # (mode runner, argv, rejected-flag fragment)
        (bench.run_serving_bench, ["--mode", "serving", "--remat",
                                   "save_attn"], "--remat"),
        (bench.run_serving_bench, ["--mode", "serving", "--decode-unroll"],
         "--decode-unroll"),
        (bench.run_decode_bench, ["--mode", "decode", "--steps-per-sched",
                                  "4"], "--steps-per-sched"),
        (bench.run_decode_bench, ["--mode", "decode", "--optimizer",
                                  "adafactor"], "--optimizer"),
        (bench.run_decode_bench, ["--mode", "decode", "--context", "2048"],
         "--context"),
        (bench.run_trainer_bench, ["--mode", "trainer", "--cache-layout",
                                   "stacked"], "--cache-layout"),
        (bench.run_trainer_bench, ["--mode", "trainer", "--context",
                                   "2048"], "--context"),
    ]
    import re

    for runner, argv, frag in cases:
        args = bench.parse_args(argv)
        with pytest.raises(ValueError, match=re.escape(frag)):
            runner(args)


def test_error_result_metric_mirrors_success_series():
    """A failed run's metric name must match the success series of the
    SAME invocation (decode layout suffixes, serving suffixes, ctx)."""
    # Default decode (unstacked default) fails -> _unstacked series.
    rec = bench.error_result(
        bench.parse_args(["--mode", "decode"]), "boom", 1)
    assert rec["metric"] == "decode_tokens_per_sec_gpt2-124m_unstacked"
    # Explicit stacked -> the historical unsuffixed series.
    rec = bench.error_result(
        bench.parse_args(["--mode", "decode", "--cache-layout", "stacked"]),
        "boom", 1)
    assert rec["metric"] == "decode_tokens_per_sec_gpt2-124m"
    # Serving default -> _unstacked.
    rec = bench.error_result(
        bench.parse_args(["--mode", "serving"]), "boom", 1)
    assert rec["metric"] == "serving_tokens_per_sec_gpt2-124m_unstacked"
    # Train with a context override -> _ctxN series.
    rec = bench.error_result(
        bench.parse_args(["--context", "16384",
                          "--preset", "gpt2-8k-sp"]), "boom", 1)
    assert rec["metric"] == "mfu_gpt2-8k-sp_train_ctx16384"


def test_structured_inner_error_is_relayed(monkeypatch, capsys):
    # Deterministic inner failures relay the inner run's structured record.
    inner = {"metric": "mfu_gpt2-124m_train", "value": 0.0,
             "unit": "fraction_of_peak_bf16", "vs_baseline": 0.0,
             "error": "RuntimeError: boom", "attempts": 1}
    rc, rec, calls = _run(
        monkeypatch, capsys,
        attempts_script=[(inner, "rc=1: RuntimeError")] * 8,
        canary_script=[(True, {"ok": True})],
    )
    assert rc == 1
    assert rec["error"] == "RuntimeError: boom"
