"""engine.epoch_gap_ms.train: Median device-idle gap between consecutive sweep programs (the end
of one ``*sweep*`` module to the start of the next); the breakdown's
``idle_gaps`` names the host span under the gaps."""

LAYER = 'Workflow engine (models/standard.py, parallel/fused.py, nn/decision.py)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    import statistics

    from benchmark.harness import trace

    reduced = ctx["reduced"]
    sweeps = trace.modules_named(reduced["trace"], "sweep",
                                 reduced["window"])
    gaps = [max(0.0, b[1] - (a[1] + a[2])) / 1e6
            for a, b in zip(sweeps, sweeps[1:])]
    return statistics.median(gaps) if gaps else None
