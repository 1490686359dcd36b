#!/usr/bin/env python3
"""A traced run of one cell with the whole scoped breakdown written out.

    python benchmark/tools/scoped_breakdown.py --workload <cell> --seed <n> \
        --seconds <s> [--out chiprun_out/scoped_<cell>.json]

``run.py --trace 1`` prints the per-layer metrics; this runs the same
command in this process and writes beside its result what the readers
of ``harness/scopes.py`` summed them from, for ``PERF.md``'s "where the
time goes": per hot program the device time by part, by layer (the
innermost scope) and by op, the ops no scope claims, the modules that
matched no program of the scope table, what the table's compiles cost;
and per span name of the program the count, the total and the median.
Not used by the driver.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import common, scopes  # noqa: E402

PROGRAMS = (("train_sweep", scopes.train_part),
            ("slot_step_many", scopes.serve_part))


def breakdown(ctx, top=40):
    """What ``scopes.scoped`` found, as plain JSON."""
    out = {"programs": {}, "spans": {}}
    for function, part_of in PROGRAMS:
        found = scopes.scoped(ctx, function, part_of)
        if found is None:
            continue
        layers = {}
        for (part, layer, _), ns in found["ops"].items():
            key = "%s %s" % (part, layer)
            layers[key] = layers.get(key, 0.0) + ns
        ranked = sorted(found["ops"].items(), key=lambda kv: -kv[1])
        out["programs"][function] = {
            "modules": found["modules"], "unmatched": found["unmatched"],
            "op_s": found["total"] / 1e9,
            "module_s": found["module_ns"] / 1e9,
            "parts_s": {k: v / 1e9 for k, v in found["parts"].items()},
            "layers_s": sorted(([k, v / 1e9] for k, v in layers.items()),
                               key=lambda kv: -kv[1])[:top],
            "ops_s": [[list(k), v / 1e9] for k, v in ranked[:top]],
            "unscoped_ops_s": [[k[2], v / 1e9] for k, v in ranked
                               if k[0] == scopes.UNSCOPED][:top],
            "tables": [{"function": t["function"],
                        "instructions": len(t["instructions"]),
                        "outside_cache": t["outside_cache"],
                        "seconds": t["seconds"]}
                       for t in scopes.scope_table(function)]}
    by_name = {}
    for name, _, duration in ctx["reduced"]["trace"]["spans"]:
        by_name.setdefault(name, []).append(duration)
    for name, durations in by_name.items():
        out["spans"][name] = {"count": len(durations),
                              "total_ms": sum(durations) / 1e6,
                              "median_us":
                                  statistics.median(durations) / 1e3}
    out["idle_gaps"] = ctx["reduced"]["breakdown"]["idle_gaps"]
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    out_path = args.out or os.path.join(
        ROOT, "chiprun_out", "scoped_%s.json" % args.workload)
    run = common.load_module("benchmark/run.py", "benchmark_run")
    read_per_layer = run.read_per_layer
    kept = {}

    def keeping(cell, result, reduced, peaks=None):
        metrics = read_per_layer(cell, result, reduced, peaks=peaks)
        ctx = {"reduced": reduced, "counters": result["counters"]}
        kept.update(breakdown(ctx), metrics=metrics)
        return metrics

    run.read_per_layer = keeping
    status = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fout:
        json.dump(kept, fout, indent=1)
    return status


if __name__ == "__main__":
    sys.exit(main())
