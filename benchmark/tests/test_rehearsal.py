"""Every driver runs end to end on the CPU at a toy size, prints counts and the
numbers compared, and no device metric; the closed loop repeats count for count."""

import csv
import json
import os
import subprocess
import sys

import pytest

from harness import registry
from harness.drivers import closed_decode

ROOT = registry.ROOT
DEVICE_WORDS = ("tokens_per_s", "ttft", "tpot", "setup_s", "mfu", "roofline", "idle", "busy_s", "_ms")


def rehearse(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
                          "--workload", cell, *extra], capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


@pytest.mark.parametrize("cell", [w["name"] for w in registry.manifest()["workloads"]])
def test_driver_rehearses_without_device_metrics(cell):
    line, _ = rehearse(cell)
    assert line["rehearsal"] and line["within_limits"] and line["failed"] == 0 and line["attempted"] > 0
    assert "metrics" not in line and "device" not in line
    assert not [k for k in json.dumps(line["counts"]) .split('"') if any(w in k for w in DEVICE_WORDS)]


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
                          "serve_mistral_decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0 and '"metrics"' not in out.stdout


def test_first_wave_keeps_residency_constant():
    rows, p, o = 40, 512, 512
    wave = closed_decode.first_wave(rows, p, o)
    assert all(a + b == p + o for a, b in wave) and wave[0] == (512, 512) and wave[-1] == (1011, 13)
    # t steps later row i holds min(prompt_i + t, ...) or has restarted at p: the total stays
    # within one row of rows * (p + o/2)
    for t in range(0, 2 * o, 7):
        resident = sum(p + ((a - p) + t) % o for a, _ in wave)
        assert abs(resident - rows * (p + o / 2)) <= p + o


def test_closed_loop_counts_repeat():
    seqs = []
    for tag, seconds in (("a", "0.5"), ("b", "1.0")):
        line, _ = rehearse("serve_mistral_decode", "--tag", tag, "--seconds", seconds)
        with open(line["counts_path"]) as f:
            seqs.append([row[:5] for row in csv.reader(f)])  # the counts; the last column is time
    n = min(len(s) for s in seqs)
    assert n > 20 and seqs[0][:n] == seqs[1][:n]
