"""loader.upload_s: Host clock around ``FullBatchLoader.load_data`` (cast, mean_disp
analysis on the host, upload) until the set is ready on the device."""

LAYER = 'Loader (loader/fullbatch.py)'
MOVES = 'setup_s'
UNIT = 's'
SOURCE = 'host_clock'


def read(ctx):
    return ctx["counters"].get("upload_s")
