"""device.idle_share.train, device.idle_share.serve: 1 - (union of the device-op intervals) / (traced window). One
reader for the quantity; ``BENCHMARK.json`` splits it by the
end-to-end metric it moves."""

LAYER = 'Device (XLA on the v5e)'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    reduced = ctx["reduced"]
    if not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
