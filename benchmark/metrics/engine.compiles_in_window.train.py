"""engine.compiles_in_window.train: Programs JAX lowered between the window's opening and its close
(JAX's own monitoring events); expected 0."""

LAYER = 'Workflow engine (models/standard.py, parallel/fused.py, nn/decision.py)'
MOVES = 'train_images_per_s_chip'
UNIT = 'count'
SOURCE = 'program_counter'


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
