"""Arithmetic that several per-layer readers share."""


def traced_span(ctx):
    """(from, to) of the traced window on the host's clock
    (``time.time()``, which stamps the decoder's dispatches too)."""
    counters = ctx["counters"]
    if "traced_from" not in counters:
        return None
    return (counters["traced_from"],
            counters["traced_from"] + counters["traced_s"])


def in_flight(rows, at):
    """How many requests the generator had sent and not yet got back
    at time ``at``: those in slots and those waiting for one."""
    return sum(1 for row in rows
               if row["status"] == 200 and row["sent"] <= at < row["done"])


def chunks_in(ctx):
    """The decode chunks the decoder dispatched inside the traced
    window, from its own books (``serve_generate.DispatchLog``): for
    each ``{"steps", "lengths", "admitted", "tokens_out"}``, where
    ``lengths`` are the positions each occupied slot had cached at the
    chunk's first step, ``admitted`` the prompt lengths of the
    requests that took a slot since the chunk before, and
    ``tokens_out`` the answer tokens the decoder had delivered when it
    dispatched the chunk. None without a traced window."""
    span = traced_span(ctx)
    if span is None:
        return None
    slots = ctx["counters"]["slots"]
    out, before = [], {}
    for row in ctx["counters"]["dispatches"]:
        held, steps = row["held"], row["chunk"]
        if len(held) > slots:
            raise RuntimeError("a chunk was dispatched over %d slots; "
                               "the decoder has %d" % (len(held), slots))
        if span[0] <= row["at"] < span[1]:
            out.append({
                "steps": steps,
                "lengths": [n - steps for _, n in held.values()],
                "admitted": [n - steps for slot, (rid, n) in held.items()
                             if before.get(slot, (None,))[0] != rid],
                "tokens_out": row["tokens_out"]})
        before = held
    return out


def mean_step_lengths(chunk):
    """The positions each slot has cached at the chunk's mean step."""
    return [n + (chunk["steps"] - 1) / 2.0 for n in chunk["lengths"]]
