"""scheduler.slot_occupancy.serve: Mean share of the decoder's slots that held a request, sampled
once a second through the window."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'program_counter'


def read(ctx):
    value = ctx["counters"].get("occupancy_mean")
    return None if value is None else 100.0 * value
