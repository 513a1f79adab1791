#!/usr/bin/env python3
"""Freeze a command for a moment, to see the late-wake witness name the cause.

Starts the command in a session of its own, passes its output through and,
``--at`` seconds after the first output line that holds ``--mark``, sends it
``SIGSTOP`` and, ``--stop_ms`` later, ``SIGCONT``:

  --whom process   the command's process alone: its witness's sleeper outside
                   (a child of it) stays on time, so the verdict must read
                   ``process``;
  --whom group     its whole process group: the sleeper outside stands still
                   too, so the verdict must read ``machine``.

It wraps nothing of the program: it only signals. Example (on the chip):

  python3 scripts/stop_process.py --whom process --at 13.4 --stop_ms 100 --mark "] cell " -- \\
      python3 benchmark/run.py --workload serve_mistral_decode --seed 1 --seconds 40 --trace 1
"""

import argparse
import os
import signal
import subprocess
import sys
import threading
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--whom", choices=("process", "group"), default="process")
    ap.add_argument("--at", type=float, default=0.0, help="seconds after the marked line")
    ap.add_argument("--stop_ms", type=float, default=100.0, help="milliseconds between SIGSTOP and SIGCONT")
    ap.add_argument("--mark", default="", help="text of the output line to count from (default: the first line)")
    ap.add_argument("command", nargs=argparse.REMAINDER, help="-- command [arguments]")
    args = ap.parse_args()
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    if not command:
        ap.error("missing '-- <command ...>'")
    proc = subprocess.Popen(command, start_new_session=True, stdout=subprocess.PIPE, text=True)
    send = (lambda sig: os.killpg(proc.pid, sig)) if args.whom == "group" else (lambda sig: os.kill(proc.pid, sig))

    def stop() -> None:
        a = time.monotonic()
        send(signal.SIGSTOP)
        time.sleep(args.stop_ms / 1e3)
        send(signal.SIGCONT)
        b = time.monotonic()
        print(f"[stop_process] {args.whom}: SIGSTOP {args.at:.2f} s after the marked line, SIGCONT "
              f"{1e3 * (b - a):.1f} ms later (CLOCK_MONOTONIC {a:.3f}..{b:.3f})", flush=True)

    timer = None
    for line in proc.stdout:
        sys.stdout.write(line)
        sys.stdout.flush()
        if timer is None and args.mark in line:
            timer = threading.Timer(args.at, stop)
            timer.daemon = True
            timer.start()
    return proc.wait()


if __name__ == "__main__":
    sys.exit(main())
