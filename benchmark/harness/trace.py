"""Reduce a ``jax.profiler`` capture (``*.xplane.pb``) to numbers.

What a TPU capture holds (looked at by hand, PR 26; the recorded
``benchmark/tests/data/small_trace.xplane.pb`` is one):

- plane ``/device:TPU:<n>``: line ``XLA Modules`` has one event per
  execution of a compiled program, named ``jit_<function>(<hash>)``;
  line ``XLA Ops`` has one event per HLO instruction that ran, named
  by the instruction's whole text (``%fusion.8 = bf16[32,28,28,64]{...}
  fusion(...), kind=kOutput, calls=...``); ``Async XLA Ops`` holds the
  start-to-done stretch of asynchronous copies, which overlap the ops
  and are left out of every sum here.
- plane ``/host:CPU``: one line per host thread; ``TraceAnnotation``s
  (the program's spans, ``unit.run``, ``decode.collect`` ...) are
  events named ``word.word``. Device times lie about a millisecond
  before the host's on the shared axis (the module of a call starts
  "before" the call's host span), so a gap is given to the span that
  covers most of it, and gaps of a few microseconds to none.

Busy time is the union of the ``XLA Ops`` intervals: the seconds in
which an operation ran on the device.
"""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
OP_HEAD = re.compile(r"^%(?P<name>\S+) = ")
OP_KIND = re.compile(r"[\}\]\)] (?P<kind>[a-z][a-z0-9\-]*)\(")
SHAPE = re.compile(r"\b(?P<dtype>pred|[a-z]+[0-9]+[a-z0-9]*)"
                   r"\[(?P<dims>[0-9,]*)\]")
#: instructions that only hold other instructions: their time is their
#: children's, so they stay out of per-op sums (not out of busy time)
CONTAINERS = ("while", "conditional", "call")
WINDOW_SPAN = "benchmark.window"
MIN_GAP_NS = 2000.0


def read(path):
    """``{"devices": {n: {"ops": [...], "modules": [...]}}, "spans":
    [...]}``; every entry is ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        found = DEVICE_PLANE.match(plane.name)
        if found:
            lines = {line.name: line for line in plane.lines}
            devices[int(found.group(1))] = {
                key: sorted(((e.name, float(e.start_ns),
                              float(e.duration_ns))
                             for e in lines[title].events),
                            key=lambda e: e[1])
                if title in lines else []
                for key, title in (("ops", "XLA Ops"),
                                   ("modules", "XLA Modules"))}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns),
                              float(e.duration_ns))
                             for e in line.events
                             if SPAN_NAME.match(e.name))
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "spans": spans}


def window_of(trace):
    """(start_ns, end_ns) of the traced window: the harness's own
    ``benchmark.window`` span, or first op to last op without it."""
    for name, start, duration in trace["spans"]:
        if name == WINDOW_SPAN:
            return start, start + duration
    ops = [op for dev in trace["devices"].values() for op in dev["ops"]]
    if not ops:
        return None
    return (min(op[1] for op in ops),
            max(op[1] + op[2] for op in ops))


def merged(intervals):
    """Sorted union of (start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def busy_intervals(events, window):
    lo, hi = window
    return merged((max(s, lo), min(s + d, hi)) for _, s, d in events
                  if s + d > lo and s < hi and d > 0)


def busy_seconds(trace, window):
    """Seconds in which an op ran, averaged over the devices traced."""
    per_device = [sum(e - s for s, e in
                      busy_intervals(dev["ops"], window)) / 1e9
                  for dev in trace["devices"].values()]
    return sum(per_device) / len(per_device) if per_device else 0.0


def gaps(intervals, window):
    """The idle (start, end) stretches of ``window``."""
    lo, hi = window
    out, at = [], lo
    for start, end in intervals:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def attribute_gaps(idle, spans, top=10):
    """``[[span name, seconds], ...]``: each idle gap of at least
    2 us goes to the host span that covers most of it, if that is at
    least half of the gap (``no span`` otherwise), shorter gaps to
    ``gaps under 2 us``; longest first."""
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    totals = {}
    for start, end in idle:
        if end - start < MIN_GAP_NS:
            key = "gaps under 2 us"
        else:
            best, key = 0.0, "no span"
            for name, s, d in spans:
                if s >= end:
                    break
                cover = min(end, s + d) - max(start, s)
                if cover > best and 2 * cover >= end - start:
                    best, key = cover, name
        totals[key] = totals.get(key, 0.0) + (end - start) / 1e9
    return sorted(([k, v] for k, v in totals.items()),
                  key=lambda kv: -kv[1])[:top]


def parse_op(text):
    """``{"name", "kind", "fusion_kind", "shapes": [(dtype, dims)]}``
    of one ``XLA Ops`` event name; the first shape is the output's
    (a tuple output lists all its parts first)."""
    head = OP_HEAD.match(text)
    kind = OP_KIND.search(text)
    fusion = re.search(r"kind=(k[A-Za-z]+)", text)
    return {
        "name": head.group("name") if head else text[:40],
        "kind": kind.group("kind") if kind else "",
        "fusion_kind": fusion.group(1) if fusion else "",
        "shapes": [(m.group("dtype"),
                    tuple(int(d) for d in m.group("dims").split(",")
                          if d))
                   for m in SHAPE.finditer(text)],
    }


def label(text):
    """A short name for the breakdown: ``fusion.8 f32[9216,4096]``."""
    op = parse_op(text)
    shape = ""
    if op["shapes"]:
        dtype, dims = op["shapes"][0]
        shape = " %s[%s]" % (dtype, ",".join(map(str, dims)))
    return "%s %s%s" % (op["name"], op["kind"], shape)


def op_table(trace, window, top=10):
    """``[[label, seconds], ...]`` of the ops that took most device
    time inside the window (containers left out), over all devices."""
    lo, hi = window
    totals = {}
    for dev in trace["devices"].values():
        for text, start, duration in dev["ops"]:
            if start + duration <= lo or start >= hi:
                continue
            if parse_op(text)["kind"] in CONTAINERS:
                continue
            totals[text] = totals.get(text, 0.0) + duration / 1e9
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[label(text), seconds] for text, seconds in rows]


def modules_named(trace, fragment, window=None):
    """Executions of programs whose name holds ``fragment``, as
    ``(name, start_ns, duration_ns)`` on device 0 (every device runs
    the same programs). With ``window``, those whose midpoint lies in
    it: a module's event starts a little before its first op and ends
    a little after its last, so where the window is first op to last
    op (no ``benchmark.window`` span) the first and the last execution
    still count, and a window that cuts through executions counts as
    many as fill it."""
    if not trace["devices"]:
        return []
    first = trace["devices"][min(trace["devices"])]
    out = [m for m in first["modules"] if fragment in m[0]]
    if window is not None:
        lo, hi = window
        out = [m for m in out if lo <= m[1] + m[2] / 2.0 < hi]
    return out


def ops_inside(trace, modules):
    """The non-container ops of device 0 that ran inside any of
    ``modules`` (sorted, disjoint): ``(text, start, duration)``."""
    if not trace["devices"] or not modules:
        return []
    first = trace["devices"][min(trace["devices"])]
    out, i = [], 0
    spans = [(m[1], m[1] + m[2]) for m in modules]
    for op in first["ops"]:
        while i < len(spans) and op[1] >= spans[i][1]:
            i += 1
        if i == len(spans):
            break
        if op[1] >= spans[i][0] \
                and parse_op(op[0])["kind"] not in CONTAINERS:
            out.append(op)
    return out


def reduce(path):
    """Everything the result line needs from one capture."""
    trace = read(path)
    window = window_of(trace)
    if window is None:
        return {"trace": trace, "window": None, "window_s": 0.0,
                "busy_s": 0.0, "breakdown": {"device_ops": [],
                                             "idle_gaps": []}}
    first = trace["devices"][min(trace["devices"])] \
        if trace["devices"] else {"ops": []}
    idle = gaps(busy_intervals(first["ops"], window), window)
    return {
        "trace": trace, "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_seconds(trace, window),
        "breakdown": {"device_ops": op_table(trace, window),
                      "idle_gaps": attribute_gaps(idle, trace["spans"])},
    }
