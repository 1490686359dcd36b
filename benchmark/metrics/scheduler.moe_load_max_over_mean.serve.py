"""scheduler.moe_load_max_over_mean.serve: The busiest routed expert's assignments over the mean expert's, of all
the decode steps the decoder ran (the worst expert block's), from
``/healthz`` (``moe_load_max_over_mean``; ``/metrics`` has it as
``veles_moe_load_max_over_mean``): 1 is an even load; the grouped
products take as long as their busiest rows and read every expert that
got a token."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ratio'
SOURCE = 'program_counter'


def read(ctx):
    return (ctx["counters"].get("health_counters") or {}) \
        .get("moe_load_max_over_mean")
