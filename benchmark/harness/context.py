"""What every driver gets and gives back, and the last line of a run."""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional


@dataclass
class Ctx:
    cell: Dict[str, Any]
    arch: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    out_dir: str
    t_start: float  # perf_counter at process start
    tag: str = ""
    rehearsal: bool = False
    setup_split: Dict[str, float] = field(default_factory=dict)
    trace_dir: Optional[str] = None

    def log(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t_start:7.2f}s] {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Time one part of set-up; printed as the set-up split."""
        t = time.perf_counter()
        yield
        self.setup_split[name] = self.setup_split.get(name, 0.0) + time.perf_counter() - t

    def run_id(self) -> str:
        return f"{self.cell['name']}_seed{self.seed}_t{int(self.trace)}_{self.tag or 'x'}_{os.getpid()}"

    @contextlib.contextmanager
    def window(self) -> Iterator[None]:
        """The measured window: a ``bench.window`` host span, traced when asked."""
        import jax

        if self.trace and not self.rehearsal:
            self.trace_dir = os.path.join(self.out_dir, "traces", self.run_id())
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            if self.trace_dir is not None:
                jax.profiler.stop_trace()

    @property
    def window_seconds(self) -> float:
        """A traced run measures a short window: traces are large."""
        if self.trace:
            return min(self.seconds, float(self.traffic.get("trace_seconds", 4)))
        return self.seconds


@dataclass
class RunResult:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    # whatever the readers need: engine stats, counters, step counts
    observed: Dict[str, Any] = field(default_factory=dict)
    # name -> (value, limit); correct iff every value <= limit
    compared: Dict[str, Any] = field(default_factory=dict)


def span(name: str):
    """A ``bench.<name>`` host span on the profiler's clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def emit(obj: Dict[str, Any]) -> None:
    sys.stdout.flush()
    print(json.dumps(obj), flush=True)
