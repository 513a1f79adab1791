"""Tracing/profiling: jax.profiler capture around training steps.

The reference's only observability is wall-clock deltas printed at eval
boundaries (`/root/reference/scripts/train_transformer.py:75,98-101`). Here
(SURVEY §5): on-demand XLA trace capture (TensorBoard/Perfetto-readable
xplane dumps) scoped to a step window. The regions that show up in such a
trace are named where the work is: `jax.named_scope` in the model and the
train step, `observability.spans.span` on the host.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture a profiler trace into `logdir` (view with TensorBoard)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepProfiler:
    """Capture a [start, stop) window of training steps.

    Used by the train CLI: `--profile logdir --profile_start 10 --profile_steps 5`.
    """

    def __init__(self, logdir: str, start_step: int, n_steps: int) -> None:
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = start_step + n_steps
        self._active = False

    def step(self, step: int) -> None:
        if not self.logdir:
            return
        if step == self.start_step and not self._active:
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif step >= self.stop_step and self._active:
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        """Stop an in-flight capture. Idempotent and exception-safe: called
        from every train() exit path (including the watchdog's emergency
        path and mid-window exceptions), where a stop_trace failure must
        not mask the original error or block the emergency save."""
        if not self._active:
            return
        self._active = False
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
