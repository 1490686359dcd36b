"""scheduler.compiles_in_window.serve: Programs JAX lowered between the window's opening and its close
(JAX's own monitoring events); expected 0."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'count'
SOURCE = 'program_counter'


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
