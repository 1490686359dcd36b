"""What the readers of the routed experts' and latent attention's
scopes share: the device time of the decode chunk's ops under a scope
that lies INSIDE one the accepted readers know (``mlp/moe.experts``,
``attn.qkv/mla.q``).

``scopes.scoped`` keeps one classification of a function's modules per
run, whatever ``part_of`` asked first, so these readers ask for the
accepted one (``scopes.serve_part``, and the five accepted parts stay
what they were) and tell their own ops apart by the innermost scopes,
which the classification keeps for every op (``layer_of``: the last
two names of the ``op_name``). A program without these scopes (the
parent, GPT-2's block) has no such op, and the readers return None.
"""

from benchmark.harness import scopes


#: the compiler's own grouped kernel (what ``jax.lax.ragged_dot``
#: becomes on the TPU) carries no scope: the scope table gives it its
#: first user's, which for the experts' last product is the fusion
#: that un-sorts and sums under ``moe.combine``. By its name it is the
#: experts' product wherever it landed
GROUPED = "ragged-dot"
EXPERTS = "moe.experts"


def inner_ms(ctx, *names):
    """Milliseconds a decode step spends in ops whose innermost scopes
    hold one of ``names``; None where nothing ran under them. The
    grouped kernel counts under ``moe.experts`` and nowhere else."""
    found = scopes.scoped(ctx, "slot_step_many", scopes.serve_part)
    steps = ctx["counters"].get("chunk")
    if found is None or not steps \
            or found["unmatched"] == found["modules"]:
        return None
    total = 0.0
    for (_, layer, label), ns in found["ops"].items():
        if label.startswith(GROUPED):
            total += ns if EXPERTS in names else 0.0
        elif any(name in layer.split("/") for name in names):
            total += ns
    if not total:
        return None
    return total / 1e6 / (found["modules"] * steps)
