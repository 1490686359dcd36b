"""Driver for configurations of kind ``serve_generate``: a causal LM
served by ``GenerateAPI`` over HTTP, loaded by a generator process.

This process holds the chip. It makes the weights on the device from
the seed (the reference module's ``init_params``, one jitted call,
bfloat16 as they are served), builds ``GenerateAPI`` with the
configuration's serving shape, warms every program the mix can reach
by direct grouped submissions to the decoder (every admission bucket x
padded group size, every attended span), starts the HTTP front, and
starts ``benchmark/harness/loadgen.py`` (a process that never imports
jax). The generator sends from the start of the lead-in; the window
opens ``lead_in_s`` later on a system already under the cell's load,
and lasts ``--seconds``. The driver samples ``/metrics`` once a
second through it. When the generator has every answer (it waits a
minute past the close) the driver reads ``/healthz``, the allocator's
peak, stops the server, frees its state, and runs the plain reference
over a sample of the answered requests drawn from the seed, the
longest among them.
"""

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import numpy

from benchmark.harness import common, readings, traffic as traffic_lib


def scaled(config, rehearse):
    if not rehearse:
        return config
    toy = dict(config)
    small = dict(config["rehearsal"])
    toy["serving"] = dict(config["serving"], **small.pop("serving"))
    toy.update(small)
    return toy


def scaled_mix(mix, rehearse):
    if not rehearse:
        return mix
    return dict(mix, **mix["rehearsal"])


def buckets_of(decoder, mix):
    """The admission buckets the mix's prompt lengths can fall in,
    with the longest length that lies in each."""
    out = {}
    for n in range(mix["prompt_len"]["min"], mix["prompt_len"]["max"] + 1):
        out[decoder.bucket_for(n)] = n
    return out


def warm_up(api, serving, mix, vocab):
    """Run every program the window can use, through the decoder's own
    admit / dispatch / collect calls, before the front starts: each
    (bucket, padded group) admission, and each attended span up to the
    longest sequence the mix can make."""
    decoder, chunk = api.decoder, serving["chunk"]
    rng = numpy.random.Generator(numpy.random.PCG64(0))
    warmed = {"admit": [], "span": []}
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    for bucket, length in sorted(buckets_of(decoder, mix).items()):
        group = 1
        while group <= serving["slots"]:
            t0 = time.perf_counter()
            for _ in range(group):
                decoder.submit(rng.integers(0, vocab, length), chunk)
            decoder.drain_pipelined(chunk)
            warmed["admit"].append(
                [bucket, group, round(time.perf_counter() - t0, 2)])
            group *= 2
    spans = sorted({min(-(-n // decoder.tile) * decoder.tile,
                        decoder.max_len)
                    for n in range(mix["prompt_len"]["min"] + chunk,
                                   longest + 1)})
    top = mix["prompt_len"]["max"]
    for span in spans:
        length = min(span - chunk, top)
        budget = max(chunk, min(mix["output_len"]["max"],
                                span - length))
        if length + budget > decoder.max_len:
            budget = decoder.max_len - length
        t0 = time.perf_counter()
        decoder.submit(rng.integers(0, vocab, length), budget)
        decoder.drain_pipelined(chunk)
        warmed["span"].append([span,
                               round(time.perf_counter() - t0, 2)])
    return warmed


class DispatchLog(list):
    """The decoder's own ``dispatch_log`` hook: its driver thread
    appends ``("admit", bucket, group)``, ``("dispatch", chunk)`` and
    ``("collect", steps)`` as it goes. This list keeps the chunk
    dispatches, each with the host's clock, the answer tokens the
    decoder had delivered by then, and what the occupied slots hold:
    ``{slot: [request id, positions cached once the chunk has run]}``.
    The kernel's roofline and the step's MFU read the live positions
    from here: the decoder's own books, not the generator's clock."""

    def __init__(self, decoder):
        super().__init__()
        self.decoder = decoder

    def append(self, entry):
        if entry[0] != "dispatch":
            return
        decoder = self.decoder
        super().append({
            "at": time.time(), "chunk": int(entry[1]),
            "tokens_out": int(decoder.tokens_out),
            "held": {slot: [rid, decoder._slot_len[slot]]
                     for slot, rid in decoder._slot_req.items()}})


def http_get(url):
    with urllib.request.urlopen(url, timeout=30) as response:
        return response.read().decode()


def percentile(values, share):
    """The value at ``share`` of the sorted sample (nearest rank)."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(0, min(len(ordered) - 1,
                      int(numpy.ceil(share * len(ordered))) - 1))
    return ordered[rank]


def run(cell, args, started):
    import gc

    rehearse = args.rehearse
    facts = common.device_facts(cell["chips"], require_tpu=not rehearse)
    import jax

    from veles_tpu.serving import GenerateAPI

    config = scaled(cell["config"], rehearse)
    mix = scaled_mix(cell["traffic"], rehearse)
    serving = config["serving"]
    seconds = float(args.seconds)
    reference = common.load_module(config["reference"])
    compiles = common.CompileCounter()
    marks = {}
    t0 = time.perf_counter()
    params, table = reference.init_params(args.seed, config)
    jax.block_until_ready(table)
    marks["weights_s"] = time.perf_counter() - t0
    api = GenerateAPI(
        params, table, config["n_head"], slots=serving["slots"],
        max_len=serving["max_len"], n_tokens=serving["n_tokens"],
        chunk=serving["chunk"], max_queue=serving["max_queue"],
        deadline=serving["deadline"], temperature=0.0, paged=False,
        quantize=None, port=0)
    t0 = time.perf_counter()
    warmed = warm_up(api, serving, mix, config["vocab_size"])
    marks["warm_up_s"] = time.perf_counter() - t0
    marks["warm_up_programs"] = json.dumps(warmed)
    marks.update(compiles.set_up_marks())
    compiles_warm = compiles.count
    dispatches = api.decoder.dispatch_log = DispatchLog(api.decoder)
    api.start()
    base = "http://127.0.0.1:%d" % api.port
    plan = traffic_lib.request_plan(mix, args.seed,
                                    config["vocab_size"], seconds)
    plan.update(url=base + "/generate", grace_s=60.0,
                timeout_s=serving["deadline"] + 30.0)
    plan_path = os.path.join(common.run_dir(), "plan.json")
    rows_path = os.path.join(common.run_dir(), "rows.json")
    child = None
    tracer = None
    samples = []
    try:
        plan["start_at"] = time.time() + 0.5
        with open(plan_path, "w") as fout:
            json.dump(plan, fout)
        child = subprocess.Popen(
            [sys.executable,
             os.path.join(common.BENCH, "harness", "loadgen.py"),
             plan_path, rows_path],
            stdout=subprocess.DEVNULL, stderr=sys.stderr)
        t_open = plan["start_at"] + plan["lead_in_s"]
        t_close = t_open + seconds
        time.sleep(max(0.0, t_open - time.time()))
        setup_s = time.perf_counter() - started
        compiles_before = compiles.count
        heartbeat = common.Heartbeat()
        heartbeat.start()
        marks["compiles_in_lead_in"] = compiles_before - compiles_warm
        if args.trace:
            tracer = common.TracedWindow(cell["name"])
            tracer.start()
            with jax.profiler.TraceAnnotation("benchmark.window"):
                time.sleep(min(seconds,
                               float(mix.get("trace_seconds", 2.0))))
            tracer.stop()
            marks["traced_from"] = t_open
            marks["traced_s"] = tracer.host_seconds
        tick = 0
        while time.time() < t_close:
            tick += 1
            time.sleep(max(0.0, min(t_close, t_open + tick)
                           - time.time()))
            metrics = http_get(base + "/metrics")
            found = re.search(
                r"^veles_serve_slot_occupancy(?:\{[^}]*\})? (\S+)$",
                metrics, re.M)
            samples.append({
                "t": round(time.time() - t_open, 3),
                "occupancy": float(found.group(1)) if found else None,
                "slots_busy": len(api.decoder._slot_req),
                "compiles": compiles.count - compiles_before})
        marks["compiles_in_window"] = compiles.count - compiles_before
        marks.update(heartbeat.stop())
        child.wait(timeout=seconds + plan["lead_in_s"]
                   + plan["grace_s"] + 60.0)
        health = json.loads(http_get(base + "/healthz"))
        memory = common.memory_stats()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        api.stop()
    with open(rows_path) as fin:
        sent = json.load(fin)
    os.unlink(plan_path)
    os.unlink(rows_path)
    # free the decoder's slab before the reference runs
    api.decoder.dispatch_log = dispatches.decoder = None
    api.decoder.state = None
    api.decoder = None
    api._decoder_kwargs = None
    del api
    gc.collect()

    rows = sent["rows"]
    closed = mix["loop"] == "closed"
    stamp = "done" if closed else "due"
    inside = [r for r in rows if t_open <= r[stamp] < t_close]
    answered = [r for r in inside if r["status"] == 200]
    failed = len(inside) - len(answered)
    tokens_out = sum(len(r["tokens"]) for r in rows
                     if r["status"] == 200
                     and t_open <= r["done"] < t_close)
    # a failed or refused request counts as the worst: the time the
    # generator would have waited for it
    worst = plan["timeout_s"] * 1e3
    latencies = [(r["done"] - (r["sent"] if closed else r["due"])) * 1e3
                 if r["status"] == 200 else worst for r in inside]
    late = [(r["sent"] - r["due"]) * 1e3 for r in inside]
    end_to_end = {"serve_tokens_per_s_chip": tokens_out / seconds,
                  "setup_s": setup_s}
    if latencies:
        end_to_end["request_latency_p95_ms"] = percentile(latencies,
                                                          0.95)
    series = []
    for second in range(int(numpy.ceil(seconds))):
        lo, hi = t_open + second, min(t_close, t_open + second + 1)
        done = [r for r in rows if r["status"] == 200
                and lo <= r["done"] < hi]
        sample = next((s for s in samples
                       if second < s["t"] <= second + 1.5), {})
        series.append({"t": second + 1, "requests": len(done),
                       "tokens": sum(len(r["tokens"]) for r in done),
                       # sent and not yet answered at the second's
                       # end: a backlog that grows shows here
                       "in_flight": readings.in_flight(rows, hi),
                       "slots_busy": sample.get("slots_busy"),
                       "compiles": sample.get("compiles")})

    compared, control = check(
        config, mix, reference, params, table, rows, plan["requests"],
        args.seed, failed + sent["unanswered"], marks,
        control=args.control)
    counters = dict(marks)
    counters.update(
        requests_in_window=len(inside), answered_in_window=len(answered),
        unanswered=sent["unanswered"], tokens_in_window=tokens_out,
        generator_late_p95_ms=percentile(late, 0.95),
        generator_late_max_ms=max(late) if late else None,
        latency_p50_ms=percentile(latencies, 0.5),
        occupancy_mean=(float(numpy.mean(
            [s["slots_busy"] for s in samples])) / serving["slots"]
            if samples else None),
        health_counters=health["counters"],
        health_latency_ms=health.get("latency_ms", {}),
        admit_programs=len(warmed["admit"]),
        span_programs=len(warmed["span"]),
        rows=rows, dispatches=list(dispatches), t_open=t_open,
        t_close=t_close, slots=serving["slots"],
        chunk=serving["chunk"])
    return {"facts": facts, "memory": memory, "compared": compared,
            "control": control,
            "attempted": len(inside), "failed": failed,
            "end_to_end": end_to_end, "counters": counters,
            "series": series, "tracer": tracer, "config": config}


def check(config, mix, reference, params, table, rows, requests,
          seed, missing, marks, control=None):
    """Every answer in range and whole; then the reference over a
    sample of the answered requests drawn from the seed, the longest
    in it: the widest gap by which an answered token's logit lies
    below the reference's best. With ``control`` (calibration), the
    same sample also goes through the reference at that operand type
    put in the program's place: the gap of the token it puts first,
    held to the same limit in a comparison of its own, which has to
    come out as not correct. Returns (comparison, control's or None).
    """
    limits = config["limits"]
    out = common.Comparison()
    good = [r for r in rows if r["status"] == 200]
    vocab = config["vocab_size"]
    malformed = sum(
        1 for r in good
        if len(r["tokens"]) != r["n_tokens"]
        or not all(isinstance(t, int) and 0 <= t < vocab
                   for t in r["tokens"]))
    out.add("requests_failed_or_unanswered", missing, 0.0)
    out.add("answers_malformed", malformed, 0.0)
    if not good:
        out.add("no_answer_to_compare", 1.0, 0.0)
        return out, None
    rng = numpy.random.Generator(numpy.random.PCG64(int(seed)))
    longest = max(good, key=lambda r: r["n_prompt"] + r["n_tokens"])
    count = min(len(good), int(mix["checked_requests"]))
    picked = [longest] + [good[i] for i in rng.choice(
        len(good), count - 1, replace=False)]
    prompts = {r["i"]: r for r in picked}
    t0 = time.perf_counter()
    stacked = reference.stack_blocks(params)
    widest, lower, tokens = 0.0, 0.0, 0
    for row in prompts.values():
        gaps = reference.served_gaps(
            config, params, table, requests[row["i"]]["tokens"],
            row["tokens"], stacked=stacked)
        widest = max(widest, float(gaps.max()))
        tokens += len(row["tokens"])
        if control:
            lower = max(lower, float(reference.control_gaps(
                config, params, table, requests[row["i"]]["tokens"],
                row["tokens"], control, stacked=stacked).max()))
    marks["reference_s"] = time.perf_counter() - t0
    marks["checked_tokens"] = tokens
    out.add("served_logit_gap", widest, limits["served_logit_gap"])
    if not control:
        return out, None
    low = common.Comparison()
    low.add("served_logit_gap", lower, limits["served_logit_gap"])
    return out, low
