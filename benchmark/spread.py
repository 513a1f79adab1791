"""Per cell and metric: each run, the median and the spread of each set.

Reads the records ``run.py`` appends to ``benchmark/out/records.jsonl`` (or
the files given). A set is the runs that share a ``--tag``. The spread is the
distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, as a share of the median:
the number the bounds in ``BENCHMARK.json`` are set from.

``spread.py --observed a,b`` prints the drivers' observed numbers ``a`` and
``b`` (``ttft_p50_ms``, ...) the same way, beside the metrics.

``spread.py --counts <dir>`` compares the per-commit count files of the closed
decode loop instead: runs of one seed must agree count for count.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from collections import defaultdict
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: List[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q[2] - q[0]) / median if median else float("nan")  # a count that reads 0 in every run


def counts_agree(directory: str) -> int:
    """For each cell and seed, whether every run's per-commit counts (all
    columns but the time) are identical up to the shortest run's closing commit."""
    import csv
    import re

    runs: Dict[tuple, List[tuple]] = defaultdict(list)
    for name in sorted(os.listdir(directory)):
        m = re.match(r"(.+)_seed(\d+)_t\d_", name)
        if m and name.endswith(".csv"):
            with open(os.path.join(directory, name)) as f:
                runs[(m.group(1), int(m.group(2)))].append((name, [r[:5] for r in csv.reader(f)]))
    bad = 0
    for (cell, seed), files in sorted(runs.items()):
        n = min(len(rows) for _, rows in files)
        same = all(rows[:n] == files[0][1][:n] for _, rows in files)
        bad += not same
        print(f"{cell} seed {seed}: {len(files)} runs, {n - 1} commits compared, "
              f"{'identical' if same else 'DIFFERENT'}")
    return bad


def main(argv: List[str]) -> int:
    if argv and argv[0] == "--counts":
        return 1 if counts_agree(argv[1]) else 0
    observed: List[str] = []
    if argv and argv[0] == "--observed":
        observed, argv = argv[1].split(","), argv[2:]
    paths = argv or [os.path.join(HERE, "out", "records.jsonl")]
    sets: Dict[tuple, List[dict]] = defaultdict(list)
    for path in paths:
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                sets[(r["workload"], r["trace"], r["tag"])].append(r)
    for (cell, trace, tag), runs in sorted(sets.items()):
        print(f"## {cell} trace={trace} set={tag or '-'} runs={len(runs)} "
              f"seeds={[r['seed'] for r in runs]} correct={[r['correct'] for r in runs]}")
        names = sorted({n for r in runs for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
            print(f"{n:34s} median {statistics.median(vals):.6g} spread {100 * spread(vals):.3f}%  "
                  + " ".join(f"{v:.6g}" for v in vals))
        for n in observed:
            vals = [r["observed"][n] for r in runs if n in r.get("observed", {})]
            if vals:
                print(f"  observed {n:25s} median {statistics.median(vals):.6g} spread "
                      f"{100 * spread(vals):.3f}%  " + " ".join(f"{v:.6g}" for v in vals))
        cmp_names = sorted({n for r in runs for n in r.get("compared", {})})
        for n in cmp_names:
            vals = [r["compared"][n][0] for r in runs if n in r.get("compared", {})]
            print(f"  compared {n:28s} max {max(vals):.4g} limit {runs[0]['compared'][n][1]:.4g}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
