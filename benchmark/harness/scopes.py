"""What the scope and span readers share: the device time of a hot
program's ops told apart by the ``jax.named_scope`` that wrote each,
and the host time of the program's own spans.

A capture names a device op by its instruction's text and holds no
``op_name`` (``harness/trace.py``), so the scopes come from the
program: ``veles_tpu.observe.xla_stats.scope_table(<function>)`` gives,
for every program of that function dispatched inside the traced
window, ``{instruction name: (output shape text, op_name)}``. Several
programs share a function's name (one ``slot_step_many`` per attended
span); the executions of one traced module (``jit_<function>(<hash>)``)
go to the program whose instruction names and output shapes cover the
ops that ran inside them, and to ``unscoped`` where none does. Every
op falls in exactly one part of ``TRAIN_PARTS`` / ``SERVE_PARTS`` or
in ``unscoped``, so a program's parts and its unscoped time add up to
the op time of its modules by construction.

A program that has no scope table (the parent of the PR that brought
it), or a run that dispatched nothing through it, gives ``None``
everywhere here, and the readers leave their metric out.
"""

import re
import statistics

from benchmark.harness import trace

OP = re.compile(r"^%(?P<name>\S+) = (?P<shape>.+?) "
                r"(?P<opcode>[a-z][a-z0-9\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+(?P<inner>[^()]*)\)+$")
UNSCOPED = "unscoped"

#: part -> the scopes of ``parallel/fused.py`` ``build_tick`` it sums
#: (``fwd`` is forward or backward by ``transpose(`` in the op_name)
TRAIN_PARTS = ("forward", "backward", "update")
#: part -> the scopes of ``parallel/decode.py`` ``_slot_step`` it sums
SERVE_PARTS = {
    "cache.append": "cache_append", "cache.read": "cache_read",
    "attn.attend": "attend",
    "attn.qkv": "matmul", "attn.out": "matmul", "mlp": "matmul",
    "embed": "head", "head": "head", "sample": "head",
}


def names_of(op_name):
    """The names along an ``op_name``, each freed of the
    transformations JAX wraps round it (``transpose(jvp(fwd))`` ->
    ``fwd``)."""
    out = []
    for part in op_name.split("/"):
        found = WRAPPED.match(part)
        out.append(found.group("inner") if found else part)
    return out


def train_part(op_name):
    """``forward``, ``backward``, ``update`` or ``unscoped``: under
    ``data``, or under ``fwd`` without ``transpose(``, an op is
    forward; under ``fwd`` with it, or under ``reduce``, backward."""
    for name in names_of(op_name):
        if name == "data":
            return "forward"
        if name == "fwd":
            return "backward" if "transpose(" in op_name else "forward"
        if name == "reduce":
            return "backward"
        if name == "update":
            return "update"
    return UNSCOPED


def serve_part(op_name):
    """The part of ``SERVE_PARTS`` whose scope the op is under, or
    ``unscoped``."""
    for name in names_of(op_name):
        if name in SERVE_PARTS:
            return SERVE_PARTS[name]
    return UNSCOPED


def layer_of(op_name):
    """The innermost scope an op is under (its ``op_name`` less the
    primitive), for a breakdown by layer: ``fwd/l3_conv``."""
    names = names_of(op_name)[:-1]
    keep = [n for n in names if not n.startswith(("jit(", "pjit("))
            and n not in ("while", "body", "cond", "closed_call")]
    return "/".join(keep[-2:]) if keep else ""


def shape_key(text):
    """An output shape's text without its layout: what a capture and
    a compiled module's text agree on."""
    return LAYOUT.sub("", text).replace(" ", "")


def head_of(op_text):
    """(instruction name, shape key) of one ``XLA Ops`` event."""
    found = OP.match(op_text)
    if not found:
        return op_text[:40], ""
    return found.group("name"), shape_key(found.group("shape"))


def program_for(ops, programs):
    """The entry of ``programs`` (``scope_table``'s list) whose
    instructions cover every op of ``ops`` by name and output shape;
    None where none does."""
    seen = {head_of(text) for text, _, _ in ops}
    for program in programs:
        table = program["instructions"]
        if all(name in table and shape_key(table[name][0]) == shape
               for name, shape in seen):
            return program
    return None


def scope_table(function):
    """The program's scope table for ``function``, or None where the
    program has none to give."""
    try:
        from veles_tpu.observe import xla_stats
    except ImportError:
        return None
    table = getattr(xla_stats, "scope_table", None)
    return table(function) if table is not None else None


def scoped(ctx, function, part_of):
    """The device time of the traced window's ``*<function>*``
    modules by part, kept in ``ctx`` so that the readers of one run
    share one classification: ``{"parts": {part: ns}, "ops":
    {(part, layer, label): ns}, "modules": n, "unmatched": n, "total":
    ns, "module_ns": ns}``; None without a scope table or without such
    modules in the window."""
    kept = ctx.setdefault("scoped", {})
    if function in kept:
        return kept[function]
    kept[function] = None
    reduced = ctx["reduced"]
    modules = trace.modules_named(reduced["trace"], function,
                                  reduced["window"])
    programs = scope_table(function) if modules else None
    if not programs:
        return None
    by_name = {}
    for module in modules:
        by_name.setdefault(module[0], []).append(module)
    out = {"parts": {}, "ops": {}, "modules": len(modules),
           "unmatched": 0, "total": 0.0,
           "module_ns": sum(m[2] for m in modules)}
    for runs in by_name.values():
        ops = trace.ops_inside(reduced["trace"], runs)
        program = program_for(ops, programs)
        if program is None:
            out["unmatched"] += len(runs)
        for text, _, duration in ops:
            op_name = "" if program is None else \
                program["instructions"][head_of(text)[0]][1]
            part = part_of(op_name)
            out["parts"][part] = out["parts"].get(part, 0.0) + duration
            key = (part, layer_of(op_name), trace.label(text))
            out["ops"][key] = out["ops"].get(key, 0.0) + duration
            out["total"] += duration
    kept[function] = out
    return out


def part_ms(ctx, function, part_of, parts, steps):
    """Milliseconds a step spends in ``parts``, over every step of the
    window's modules (a module that matched no program adds to the
    unscoped time alone); None with nothing to read, which includes
    that no module matched."""
    found = scoped(ctx, function, part_of)
    if found is None or not steps \
            or found["unmatched"] == found["modules"]:
        return None
    return sum(found["parts"].get(part, 0.0) for part in parts) \
        / 1e6 / (found["modules"] * steps)


def unscoped_share(ctx, function, part_of):
    """Percent of the modules' op time that no scope claims."""
    found = scoped(ctx, function, part_of)
    if found is None or not found["total"]:
        return None
    return 100.0 * found["parts"].get(UNSCOPED, 0.0) / found["total"]


def train_ms(ctx, *parts):
    return part_ms(ctx, "train_sweep", train_part, parts,
                   ctx["counters"].get("steps_per_train_sweep"))


def serve_ms(ctx, *parts):
    return part_ms(ctx, "slot_step_many", serve_part, parts,
                   ctx["counters"].get("chunk"))


# -- the program's own spans --------------------------------------------

def spans_named(ctx, *names):
    """The program's spans of these names that began inside the
    traced window, ``(name, start_ns, duration_ns)`` by start."""
    reduced = ctx["reduced"]
    if reduced["window"] is None:
        return []
    lo, hi = reduced["window"]
    return [s for s in reduced["trace"]["spans"]
            if s[0] in names and lo <= s[1] < hi]


def self_ns(span, spans):
    """A span's duration less what the spans inside it cover."""
    _, start, duration = span
    end = start + duration
    inside = [(s, s + d) for name, s, d in spans
              if start <= s and s + d <= end and (s, d) != (start,
                                                            duration)
              and name != trace.WINDOW_SPAN]
    return duration - sum(e - s for s, e in trace.merged(inside))


def median_self_ms(ctx, *names):
    """Median self time of the spans of these names; None without."""
    found = spans_named(ctx, *names)
    if not found:
        return None
    every = ctx["reduced"]["trace"]["spans"]
    return statistics.median(self_ns(s, every) for s in found) / 1e6


def median_ms_between(ctx, name, fence):
    """Host time in the spans called ``name`` between one ``fence``
    span's start and the next one's, median over the traced window's
    fences; None where the window holds no ``name`` span or fewer
    than two fences."""
    spans = spans_named(ctx, name)
    fences = [s[1] for s in spans_named(ctx, fence)]
    if not spans or len(fences) < 2:
        return None
    sums = [0.0] * (len(fences) - 1)
    for _, start, duration in spans:
        for i in range(len(sums)):
            if fences[i] <= start < fences[i + 1]:
                sums[i] += duration
                break
    return statistics.median(sums) / 1e6
