"""device.hbm_peak_share.train, device.hbm_peak_share.serve: The allocator's ``peak_bytes_in_use`` over its ``bytes_limit``,
read when the window has closed and before the reference runs. One
reader for the quantity; ``BENCHMARK.json`` splits it by the
end-to-end metric it moves."""

LAYER = 'Device (XLA on the v5e)'
UNIT = '%'
SOURCE = 'program_counter'


def read(ctx):
    memory = ctx["memory"]
    if not memory.get("bytes_limit"):
        return None
    return 100.0 * memory["peak_bytes_in_use"] / memory["bytes_limit"]
