"""The contract's spread of every metric over sets of runs: reads result
lines (one JSON object a line, as ``run.py`` prints last) from files given
as ``set1.jsonl set2.jsonl``, prints for each metric each set's median and
spread (interquartile distance by ``statistics.quantiles(n=4)`` over the
median), the wider of the two, and five times it — the bound's starting
point.

    python3 benchmarks/tools/spread.py set1.jsonl set2.jsonl
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness.stats import spread  # noqa: E402


def read(path):
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith('{"correct"'):
                rows.append(json.loads(line))
    return rows


def main(paths) -> int:
    sets = [read(p) for p in paths]
    names = sorted({m for rows in sets for r in rows for m in r["metrics"]})
    print(f"runs per set: {[len(s) for s in sets]}; correct: "
          f"{[sum(r['correct'] for r in s) for s in sets]}")
    for name in names:
        per = []
        for rows in sets:
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:]          # a set's first run may compile
            if len(vals) < 2:
                continue
            med = statistics.median(vals)
            per.append({"median": med, "spread": spread(vals),
                        "min": min(vals), "max": max(vals), "n": len(vals)})
        if not per:
            continue
        widest = max(p["spread"] for p in per)
        drift = (abs(per[1]["median"] - per[0]["median"]) / per[0]["median"]
                 if len(per) > 1 else 0.0)
        print(json.dumps({"metric": name, "sets": per, "widest_spread": widest,
                          "five_times": 5 * widest,
                          "second_median_vs_first": drift}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
