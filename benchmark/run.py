#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the chips the
cell asks for. It finds everything that belongs to the cell by name:
the cell's entry in ``BENCHMARK.json`` names its configuration and its
traffic mix; ``benchmark/configs/<config>.json`` holds the sizes and
names its plain reference, its operation counts and the driver kind;
``benchmark/traffic/<mix>.json`` holds the mix's parameters;
``benchmark/harness/<kind>.py`` drives a configuration of that kind;
``benchmark/metrics/<metric>.py`` reads one per-layer metric. See
``benchmark/README.md``.

The last line of standard output is the result object. Before it comes
one ``series`` line (the window's time series); the numbers compared
with the reference, each beside its limit, are the last lines of
standard error and the result's last key.

``--plan`` prints what the command would use for the cell (files and
metric readers) and touches no device. ``--rehearse`` walks the same
control flow on the CPU at the configuration's ``rehearsal`` sizes:
it prints no metric, says ``"correct": false`` and exits 3, so it can
never pass for a chip run. Without a TPU (or with fewer chips than
the cell asks for) the command prints no result and exits 2.
``--control float8_e4m3fn`` is the calibration of a cell's limits: the
run also puts the reference at that operand type in the program's
place, holds it to the same limits in a comparison of its own
(``control_compared``), and says ``control_correct``, which has to be
false.
"""

import argparse
import json
import os
import shutil
import sys
import time

STARTED = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
#: JAX's persistent compile cache: a fixed directory inside the
#: checkout (the path is part of every entry's key), whatever the
#: machine's environment names, so that only a checkout's first run of
#: a cell compiles and two checkouts share nothing. No size limit: a
#: serving cell's programs come to 206 MB, and under a limit below that
#: every run evicts what the next one needs and compiles it all again.
COMPILE_CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def find_cell(name):
    """The cell's entry with its configuration and traffic files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fin:
        bench = json.load(fin)
    cells = {cell["name"]: cell for cell in bench["workloads"]}
    if name not in cells:
        raise SystemExit("run.py: no cell %r in BENCHMARK.json (cells: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = dict(cells[name])
    configs = {c["name"]: c for c in bench["configs"]}
    cell["config_file"] = configs[cell["config"]]["file"]
    cell["traffic_file"] = "benchmark/traffic/%s.json" % cell["traffic"]
    for key in ("config_file", "traffic_file"):
        with open(os.path.join(ROOT, cell[key])) as fin:
            cell[key.replace("_file", "")] = json.load(fin)

    def applies(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return cell


def reader_file(name):
    """``benchmark/metrics/<name>.py``; a quantity that ``BENCHMARK.json``
    splits by the end-to-end metric it moves (``x.train``, ``x.serve``)
    may keep one reader, ``benchmark/metrics/x.py``."""
    path = "benchmark/metrics/%s.py" % name
    if os.path.exists(os.path.join(ROOT, path)):
        return path
    return "benchmark/metrics/%s.py" % name.rpartition(".")[0]


def read_per_layer(cell, result, reduced, peaks=None):
    """Each per-layer metric of the cell from its own reader
    (``reader_file``); a reader that finds nothing to read returns
    None and the metric is left out."""
    from benchmark.harness import common

    context = {
        "cell": cell, "config": result["config"],
        "traffic": cell["traffic"], "counters": result["counters"],
        "memory": result["memory"], "reduced": reduced,
        "peaks": peaks or common.peaks_for(result["facts"]["kind"]),
        "ops": common.load_module(cell["config"]["ops"]),
    }
    metrics = {}
    for metric in cell["per_layer"]:
        reader = common.load_module(reader_file(metric["name"]))
        value = reader.read(context)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    return metrics


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--plan", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control", default=None, metavar="DTYPE",
                        help="calibration: also hold the control (the "
                        "reference at this operand type, in the "
                        "program's place) to the cell's limits")
    args = parser.parse_args(argv)
    cell = find_cell(args.workload)
    kind = cell["config"]["kind"]
    if args.plan:
        print(json.dumps({
            "workload": cell["name"], "chips": cell["chips"],
            "config_file": cell["config_file"],
            "traffic_file": cell["traffic_file"],
            "driver": "benchmark/harness/%s.py" % kind,
            "reference": cell["config"]["reference"],
            "ops": cell["config"]["ops"],
            "end_to_end": [m["name"] for m in cell["end_to_end"]],
            "per_layer": {m["name"]: reader_file(m["name"])
                          for m in cell["per_layer"]}}))
        return 0

    # before anything imports jax, which reads these once
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    from benchmark.harness import common

    driver = common.load_module("benchmark/harness/%s.py" % kind,
                                "benchmark.harness." + kind)
    try:
        return report(cell, args, driver.run(cell, args, STARTED))
    except common.NoDevice as exc:
        print("run.py: %s" % exc, file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(common.run_dir(), ignore_errors=True)


def plain(counters):
    """The counters that are single values (the generator's rows and
    the like stay out of the line)."""
    return {key: value for key, value in counters.items()
            if isinstance(value, (int, float, str))}


def report(cell, args, result):
    """Print the series line, the numbers compared and the result."""
    from benchmark.harness import common

    facts, compared = result["facts"], result["compared"]
    print(json.dumps({"series": result["series"]}), flush=True)
    tail = {"counters": plain(result["counters"])}
    control = result.get("control")
    if control is not None:
        tail["control_correct"] = control.correct
        tail["control_compared"] = control.as_dict()
        for text in control.lines():
            print("control " + text, file=sys.stderr)
    tail["compared"] = compared.as_dict()
    for text in compared.lines():
        print(text, file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        if result["tracer"] is not None:
            from benchmark.harness import trace

            reduced = trace.reduce(result["tracer"].path)
            result["tracer"].discard()
            # the readers' code paths only: nothing of it is printed
            read_per_layer(cell, result, reduced,
                           peaks=common.peaks_for("TPU v5 lite"))
        print(json.dumps(dict({
            "correct": False, "rehearsal": True,
            "would_be_correct": compared.correct,
            "attempted": result["attempted"],
            "failed": result["failed"], "device": facts}, **tail)))
        return 3
    wanted = {m["name"]: m for m in cell["end_to_end"]}
    device = dict(facts, memory_peak_bytes=int(
        result["memory"].get("peak_bytes_in_use", 0)))
    line = {"correct": compared.correct,
            "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        from benchmark.harness import trace

        tracer = result["tracer"]
        reduced = trace.reduce(tracer.path)
        tracer.discard()
        line["metrics"] = read_per_layer(cell, result, reduced)
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        line["breakdown"] = reduced["breakdown"]
    else:
        line["metrics"] = {
            name: {"value": float(result["end_to_end"][name]),
                   "unit": wanted[name]["unit"]} for name in wanted}
    line["device"] = device
    line.update(tail)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
