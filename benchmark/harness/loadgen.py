"""Traffic generation: a seeded, pure schedule and the percentile the tails use.

The schedule builder and the nearest-rank percentile follow
``frontend/loadgen.py`` (``build_schedule``, ``_percentile``); lengths are
log-uniform here, and the arrival process and sizes come from the traffic
file's own ``schedule_seed``, so every ``--seed`` offers the same arrivals
and sizes and differs in token contents (and weights) only.
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence


@dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float  # offset from the start of the lead-in
    prompt_tokens: int
    output_tokens: int


def _log_uniform(rng: random.Random, lo: int, hi: int) -> int:
    return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))


def build_schedule(traffic: Dict[str, Any], horizon_s: float) -> List[Arrival]:
    """Poisson arrivals at ``rate_rps`` until ``horizon_s``. A pure function
    of the traffic file: unit-rate gaps are drawn first, so another rate
    stretches the same arrival pattern."""
    rng = random.Random(traffic["schedule_seed"])
    out: List[Arrival] = []
    t = 0.0
    while True:
        t += rng.expovariate(1.0) / traffic["rate_rps"]
        p = _log_uniform(rng, *traffic["prompt_tokens"])
        o = _log_uniform(rng, *traffic["output_tokens"])
        if t >= horizon_s:
            return out
        out.append(Arrival(len(out), t, p, o))


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank on the sorted values; q in [0, 1]."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    return vals[min(len(vals) - 1, max(0, round(q * (len(vals) - 1))))]
