"""front.refused_share.serve: ``/healthz`` ``rejected`` + ``expired`` + ``shed`` over the
requests the front saw (admitted + rejected) since it started;
expected 0."""

LAYER = 'HTTP front (serving.py GenerateAPI)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'program_counter'


def read(ctx):
    counters = ctx["counters"].get("health_counters")
    if not counters:
        return None
    seen = counters.get("admitted", 0) + counters.get("rejected", 0)
    if not seen:
        return None
    lost = sum(counters.get(key, 0)
               for key in ("rejected", "expired", "shed"))
    return 100.0 * lost / seen
