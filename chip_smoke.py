#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once at the full width of the shipped `gpt2-124m`
preset (d 768, 12 layers, 12 heads, ctx 1024, vocab 50304, flash attention,
batch 12) through the entry points a user calls, and checks what comes out:

  probe    a child that touches JAX: prints the JAX version, backend, device
           kind and count, fails at once unless the backend is `tpu`, and
           asserts that the lowered train step holds a Mosaic custom call
           (a flash path degraded to blockwise JAX must fail, not pass
           slowly).
  train    `scripts/train.py --no-resume`, 20 steps on the committed
           byte-token files through the file loader and the native batcher,
           one eval and one checkpoint save inside the run. First loss
           ~ ln(vocab), falling and finite, exit_reason completed.
  offline  `scripts/serve.py --input_file`: the checkpoint answers six
           prompts of mixed length through ServingEngine at the flags'
           defaults, greedy.
  gateway  `scripts/serve.py --http`: the same six over POST /v1/generate
           (one SSE, one as text). Every answer done, the asked number of
           tokens, ids inside the vocabulary, tokens equal to offline's.
  workers  `scripts/serve.py --http --replica_mode process --replicas 1`:
           the same answers from a worker process, while the parent never
           loads the TPU runtime.

This parent never touches JAX: a chip belongs to one process at a time, so
the phases are children run in series, and every one is stopped before the
next starts. Every work file goes under chiprun_out/chip_smoke/ (wiped at the
start; the checkpoint is removed at the end), never the checkout's
`checkpoints/`. Weights are random from the preset's seed.

Exit 0 and a last stdout line `{"ok": true, "device": {...}}` only if every
phase passed; with no TPU, or without the rest of the repo beside this file,
it exits non-zero and prints no result line.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
CKPT = os.path.join(OUT, "ckpt")
METRICS = os.path.join(OUT, "train_metrics.jsonl")
PRESET = "gpt2-124m"
VOCAB = 50304
STEPS = 20
OVERRIDES = {
    "data.train_path": os.path.join(ROOT, "data", "parity", "train.bin"),
    "data.val_path": os.path.join(ROOT, "data", "parity", "val.bin"),
    "data.tokenizer_name": "byte",
    "train.train_steps": STEPS,
    "train.log_interval": 1,
    "train.eval_interval": 10,
    "train.eval_iters": 2,
    "train.checkpoint_interval": 10,
    "train.keep_checkpoints": 1,
    "train.checkpoint_dir": CKPT,
    "train.metrics_path": METRICS,
}
PROMPT_LENS = (5, 40, 64, 130, 300, 17)  # below, on and across 64-token pages
MAX_NEW = 16
BUDGET_S = 1100.0  # the whole run, compilation included
_T0 = time.monotonic()


class Failed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise Failed(what)


def left(cap: float) -> float:
    remaining = BUDGET_S - (time.monotonic() - _T0)
    check(remaining > 5, "out of time budget")
    return min(cap, remaining)


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:5.0f}s] {msg}", flush=True)


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


def stop(proc: subprocess.Popen, grace: float = 45.0) -> None:
    """SIGTERM the child (a server drains and stops its own workers), then
    SIGKILL its whole group: nothing this script starts may outlive it
    holding the chip."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)  # the child, or stragglers of it
    except ProcessLookupError:
        pass
    proc.wait()


def run_phase(name: str, cmd: list, cap: float) -> str:
    """Run one child to its end; its output goes to <OUT>/<name>.log."""
    log = os.path.join(OUT, f"{name}.log")
    say(f"{name}: {' '.join(cmd[:3])} ...")
    with open(log, "w") as f:
        proc = subprocess.Popen(
            cmd, stdout=f, stderr=subprocess.STDOUT, cwd=OUT,
            start_new_session=True,
        )
        try:
            rc = proc.wait(timeout=left(cap))
        except subprocess.TimeoutExpired:
            raise Failed(f"{name}: still running after its time limit\n{tail(log)}")
        finally:
            stop(proc)
    check(rc == 0, f"{name}: exit code {rc}\n{tail(log)}")
    return log


# --------------------------------------------------------------------------
# probe (child): the only code in this file that touches JAX
# --------------------------------------------------------------------------


def probe() -> int:
    sys.path.insert(0, ROOT)
    from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    print(f"jax {jax.__version__} backend {backend} device_kind "
          f"{devices[0].device_kind} count {len(devices)}", flush=True)
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); this "
              "smoke computes nothing off the chip", file=sys.stderr)
        return 1
    from pretraining_llm_tpu.config import get_preset
    from pretraining_llm_tpu.parallel.mesh import build_mesh, needs_mesh
    from pretraining_llm_tpu.training.train_step import lower_train_step

    config = get_preset(PRESET).with_overrides(OVERRIDES)
    mesh = build_mesh(config.mesh) if needs_mesh(config.mesh) else None
    text = lower_train_step(config, mesh).as_text()
    if "tpu_custom_call" not in text:
        print("chip_smoke: the lowered train step holds no Mosaic custom "
              "call — flash attention did not reach its Pallas kernel",
              file=sys.stderr)
        return 1
    print(f"train step lowers with {text.count('tpu_custom_call')} Mosaic "
          f"custom calls; mesh {dict(mesh.shape) if mesh else None}", flush=True)
    with open(os.path.join(OUT, "device.json"), "w") as f:
        json.dump({"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)}, f)
    return 0


# --------------------------------------------------------------------------
# phases (parent side)
# --------------------------------------------------------------------------


def train_phase() -> None:
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "train.py"),
           "--preset", PRESET, "--no-resume", "--override",
           *(f"{k}={v}" for k, v in OVERRIDES.items())]
    log = run_phase("train", cmd, 600)
    with open(METRICS) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    steps = [r for r in recs if "loss" in r and "step" in r]
    evals = [r for r in recs if "val_loss" in r]
    check(len(steps) == STEPS, f"train: {len(steps)} step records, want {STEPS}")
    losses = [r["loss"] for r in steps]
    check(all(isinstance(x, float) and math.isfinite(x) for x in losses),
          f"train: non-finite loss in {losses}")
    check(abs(losses[0] - math.log(VOCAB)) < 0.2,
          f"train: first loss {losses[0]:.3f}, want ~ln({VOCAB})={math.log(VOCAB):.2f}")
    check(losses[-1] < losses[0] - 1.0,
          f"train: loss did not fall: {losses[0]:.3f} -> {losses[-1]:.3f}")
    check(bool(evals) and all(math.isfinite(r["val_loss"]) for r in evals),
          f"train: no finite eval in {evals}")
    check(abs(evals[-1]["val_loss"] - losses[-1]) < 1.0,
          f"train: eval loop and train step disagree: val "
          f"{evals[-1]['val_loss']:.3f} vs train {losses[-1]:.3f}")
    check(any(r.get("event") == "batcher" and r.get("backend") == "native"
              for r in recs), "train: the native C++ batcher did not serve")
    check("exit_reason=completed" in tail(log, 5),
          f"train: no 'exit_reason=completed'\n{tail(log, 5)}")
    saved = sorted(os.listdir(CKPT))
    check(saved == [f"step-{STEPS}"], f"train: checkpoints {saved}")
    say(f"train: loss {losses[0]:.3f} -> {losses[-1]:.3f}, val "
        f"{evals[-1]['val_loss']:.3f}, checkpoint {saved[0]}")


def make_prompts() -> list:
    """Six ASCII prompts cut from the committed corpus, one per line."""
    with open(os.path.join(ROOT, "data", "parity", "corpus.txt"), "rb") as f:
        raw = f.read(20000)
    text = "".join(chr(b) if 32 <= b < 127 else " " for b in raw)
    prompts, at = [], 100
    for n in PROMPT_LENS:
        prompts.append(text[at:at + n])
        at += n + 37
    with open(os.path.join(OUT, "prompts.txt"), "w") as f:
        f.write("\n".join(prompts) + "\n")
    return prompts


def check_tokens(where: str, tokens: list, want: list = None) -> None:
    check(len(tokens) == MAX_NEW, f"{where}: {len(tokens)} tokens, want {MAX_NEW}")
    check(all(isinstance(t, int) and 0 <= t < VOCAB for t in tokens),
          f"{where}: token outside the vocabulary: {tokens}")
    if want is not None:
        check(tokens == want, f"{where}: tokens {tokens} != offline {want}")


def offline_phase(prompts: list) -> list:
    out = os.path.join(OUT, "offline.jsonl")
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "serve.py"),
           "--model_path", CKPT, "--input_file", os.path.join(OUT, "prompts.txt"),
           "--max_new_tokens", str(MAX_NEW), "--temperature", "0",
           "--output", out]
    run_phase("offline", cmd, 420)
    with open(out) as f:
        recs = sorted((json.loads(ln) for ln in f), key=lambda r: r["index"])
    check([r["index"] for r in recs] == list(range(len(prompts))),
          f"offline: answers for {[r['index'] for r in recs]}")
    for r, p in zip(recs, prompts):
        check(r["prompt"] == p, f"offline: prompt {r['index']} was altered")
        check_tokens(f"offline[{r['index']}]", r["tokens"])
    say(f"offline: {len(recs)} answers of {MAX_NEW} tokens")
    return [r["tokens"] for r in recs]


def post(port: int, body: dict, timeout: float) -> dict:
    """POST /v1/generate; an SSE answer is folded into the JSON shape."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read().decode()
    except OSError as e:  # refused, timed out, or an HTTP error status
        raise Failed(f"gateway: POST /v1/generate failed: {e!r}")
    if not body.get("stream"):
        return json.loads(raw)
    events = [ln[len("data: "):] for ln in raw.splitlines() if ln.startswith("data: ")]
    check(events[-1] == "[DONE]", f"gateway: SSE did not end in [DONE]: {events[-3:]}")
    events = [json.loads(e) for e in events[:-1]]
    end = events[-1]
    check(end.get("done") is True, f"gateway: SSE terminal event {end}")
    return {"status": end["status"], "tokens": [e["token"] for e in events[:-1]]}


def http_phase(name: str, extra: list, prompts: list, want: list,
               parent_off_tpu: bool = False) -> None:
    """Start `serve.py --http`, ask the six, stop it."""
    log = os.path.join(OUT, f"{name}.log")
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "serve.py"),
           "--model_path", CKPT, "--http", "--port", "0", "--temperature", "0",
           *extra]
    say(f"{name}: serve.py --http {' '.join(extra)}")
    with open(log, "w") as f:
        proc = subprocess.Popen(
            cmd, stdout=f, stderr=subprocess.STDOUT, cwd=OUT,
            start_new_session=True,
        )
    try:
        port, deadline = None, time.monotonic() + left(420)
        while port is None:
            check(proc.poll() is None, f"{name}: server exited\n{tail(log)}")
            check(time.monotonic() < deadline, f"{name}: server never listened\n{tail(log)}")
            for ln in tail(log, 200).splitlines():
                if "listening on http://" in ln:
                    port = int(ln.split("listening on http://")[1].split()[0].rsplit(":", 1)[1])
            time.sleep(0.5)
        for i, (p, w) in enumerate(zip(prompts, want)):
            body = {"prompt": p if i == 5 else list(p.encode()),
                    "max_new_tokens": MAX_NEW}
            if i == 1:
                body["stream"] = True
            ans = post(port, body, left(300))
            check(ans.get("status") == "done", f"{name}[{i}]: {ans}")
            check_tokens(f"{name}[{i}]", ans["tokens"], w)
        if parent_off_tpu:
            with open(f"/proc/{proc.pid}/maps") as f:
                check("libtpu" not in f.read(),
                      f"{name}: the serve.py parent loaded the TPU runtime")
    finally:
        stop(proc)
    text = tail(log, 400)
    check(proc.returncode == 0 and "SIGABRT" not in text
          and "abandoned wedged" not in text,
          f"{name}: the server did not shut down cleanly "
          f"(exit code {proc.returncode})\n{tail(log)}")
    say(f"{name}: {len(prompts)} answers done, tokens equal to offline's")


def main() -> int:
    if sys.argv[1:] == ["probe"]:
        return probe()
    missing = [p for p in ("pretraining_llm_tpu", "scripts/train.py",
                           "scripts/serve.py", "data/parity/train.bin")
               if not os.path.exists(os.path.join(ROOT, p))]
    if missing:
        print(f"chip_smoke: not in a checkout of the repo: {missing} missing "
              f"beside {__file__}", file=sys.stderr)
        return 2
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    try:
        log = run_phase("probe", [sys.executable, os.path.abspath(__file__), "probe"], 300)
        print(tail(log), end="", flush=True)
        with open(os.path.join(OUT, "device.json")) as f:
            device = json.load(f)
        train_phase()
        prompts = make_prompts()
        want = offline_phase(prompts)
        http_phase("gateway", [], prompts, want)
        http_phase("workers", ["--replica_mode", "process", "--replicas", "1"],
                   prompts, want, parent_off_tpu=True)
    except Failed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(CKPT, ignore_errors=True)
    say("all phases passed")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
