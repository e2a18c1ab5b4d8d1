"""The benchmark's one command: run one cell once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--workload`` -> its entry in ``BENCHMARK.json`` -> configuration file and
traffic file by name -> runner (by the configuration's ``kind``),
generator and per-layer metric readers by name. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``. Earlier lines (one JSON
object each) say what the set-up was made of and print every number
compared beside its limit. The run fails, and prints no result line, when
JAX finds no TPU or another number of chips than the cell asks for.

``--manifest`` and ``--rehearse`` exist for the CPU tests: a tiny manifest
of fixtures, and leave to run off a TPU (the line then says ``cpu`` and
carries no device metric).
"""

import time

_T_START = time.time()          # set-up is counted from here

import argparse                  # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import manifest as mf  # noqa: E402


class Context:
    """What a runner and the metric readers see of one run."""

    def __init__(self, args, man, cell, config, traffic, bench_dir, device):
        from benchmarks.harness.compare import Verdict

        self.manifest, self.cell, self.config, self.traffic = (
            man, cell, config, traffic)
        self.bench_dir, self.device = bench_dir, device
        self.seed, self.seconds, self.trace = args.seed, args.seconds, \
            bool(args.trace)
        self.seconds_left = float(args.seconds)
        self.rehearse = args.rehearse
        # what a run leaves behind goes inside the checkout
        self.out_dir = os.path.join(mf.ROOT, ".bench_out")
        self.trace_dir = os.path.join(self.out_dir, "trace", cell["name"])
        self.verdict = Verdict()
        self.setup_s = None

    def note(self, obj) -> None:
        print(json.dumps({"note": obj}, default=str), flush=True)

    def mark_setup_done(self) -> None:
        self.setup_s = time.time() - _T_START


def read_layer_metrics(ctx, result, wanted):
    """Each per-layer metric by its own reader. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in wanted:
        reader = mf.load_module("layer_metrics", m["name"], ctx.bench_dir)
        value = reader.read(ctx, result)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(mf.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    man, bench_dir, cell, config, traffic = mf.resolve(args.manifest,
                                                       args.workload)

    # whatever the program dumps goes inside the checkout
    os.environ.setdefault("DSTPU_FLIGHT_DIR",
                          os.path.join(mf.ROOT, ".bench_out", "flight"))
    mf.program_logs_to_stderr()

    from benchmarks.harness import cache, compiles, device

    cache_dir = cache.enable()
    compiles.install()
    try:
        dev = device.require(cell["chips"], args.rehearse)
    except device.NoChipError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    ctx = Context(args, man, cell, config, traffic, bench_dir, dev)
    ctx.note({"workload": cell["name"], "seed": args.seed, "device": dev,
              "compile_cache_dir": cache_dir})

    runner = mf.load_module("runners", config["kind"], bench_dir)
    result = runner.run(ctx)
    ctx.verdict.print(sys.stdout)

    metrics = {}
    if ctx.trace:
        metrics = read_layer_metrics(
            ctx, result, mf.metrics_of(man, "per_layer", cell["name"]))
    else:
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        for m in mf.metrics_of(man, "end_to_end", cell["name"]):
            if m["name"] not in values:
                raise KeyError(f"the {config['kind']} runner reported no "
                               f"{m['name']} for {cell['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(dev, memory_peak_bytes=device.memory_peak_bytes())
    line = {"correct": ctx.verdict.correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": dev}
    tr = result.get("trace")
    if ctx.trace and tr is not None and dev["platform"] == "tpu":
        dev["busy_s"], dev["window_s"] = tr.busy_s(), tr.window_s
        line["breakdown"] = tr.breakdown()
    sys.stdout.flush()
    ctx.verdict.print(sys.stderr)       # the record of a run keeps stderr's end
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # engines register process-wide hooks and worker threads and have no
    # teardown (PERF.md, Open questions); nothing of theirs outlives this
    os._exit(code)
