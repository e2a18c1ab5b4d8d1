"""Look at one trace by hand: which planes are devices, which lines they
have, and how the kernels are named. Reads the newest ``.xplane.pb`` under
a directory (default ``.bench_out/trace``).

    python3 benchmarks/tools/trace_names.py [DIR] [--out FILE]
"""

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("dir", nargs="?", default=".bench_out/trace")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    files = glob.glob(os.path.join(args.dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        print(f"no .xplane.pb under {args.dir}", file=sys.stderr)
        return 1
    data = jax.profiler.ProfileData.from_file(max(files,
                                                  key=os.path.getmtime))
    report = []
    for plane in data.planes:
        for line in plane.lines:
            total, first = {}, {}
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in first:
                    first[ev.name] = {"start_ns": ev.start_ns,
                                      "stats": {k: str(v)[:120]
                                                for k, v in ev.stats}}
            names = sorted(total, key=lambda k: -total[k])[:40]
            report.append({"plane": plane.name, "line": line.name,
                           "events": n, "top": [
                               [k, total[k] * 1e-9, first[k]] for k in names]})
    text = json.dumps(report, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    for r in report:
        print(r["plane"], "|", r["line"], "|", r["events"], "events")
        for k, s, _ in r["top"][:12]:
            print(f"    {s:10.6f}s  {k[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
