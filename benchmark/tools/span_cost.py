#!/usr/bin/env python3
"""What one span of the program costs its host thread.

    python benchmark/tools/span_cost.py [--spans 20000]

Prints one JSON line: the mean microseconds of ``with
get_tracer().span(name): pass`` with the tracer off (the shared null
span), on (the event recorders alone) and on inside a
``jax.profiler`` capture with ``annotate_device`` (what a traced
window of the benchmark pays: the recorders and a ``TraceAnnotation``).
A host number: run it where the spans will run. Not used by the
driver.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def mean_us(tracer, spans):
    t0 = time.perf_counter()
    for _ in range(spans):
        with tracer.span("bench.span"):
            pass
    return (time.perf_counter() - t0) / spans * 1e6


def pieces_us(repeats):
    """What a span is made of, each alone (a host where a system call
    is dear shows here: the ids and the process id)."""
    import contextvars
    import threading
    import uuid

    var = contextvars.ContextVar("piece", default=None)
    pieces = {"urandom8": lambda: os.urandom(8),
              "uuid4": lambda: uuid.uuid4().hex[:16],
              "getpid": os.getpid, "monotonic": time.monotonic,
              "get_ident": threading.get_ident,
              "contextvar": lambda: var.reset(var.set(1))}
    out = {}
    for name, piece in pieces.items():
        t0 = time.perf_counter()
        for _ in range(repeats):
            piece()
        out[name] = (time.perf_counter() - t0) / repeats * 1e6
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--spans", type=int, default=20000)
    args = parser.parse_args(argv)
    import jax

    from veles_tpu.observe.tracing import get_tracer

    tracer = get_tracer()
    out = {"spans": args.spans, "platform": jax.devices()[0].platform,
           "off_us": mean_us(tracer, args.spans)}
    tracer.enabled = True
    out["on_us"] = mean_us(tracer, args.spans)
    where = tempfile.mkdtemp(prefix="span_cost_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(where, profiler_options=options)
    tracer.annotate_device = True
    try:
        out["on_in_capture_us"] = mean_us(tracer, args.spans)
    finally:
        tracer.annotate_device = tracer.enabled = False
        jax.profiler.stop_trace()
        shutil.rmtree(where, ignore_errors=True)
    out["pieces_us"] = pieces_us(args.spans)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
