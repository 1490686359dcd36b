"""What decides ``correct`` fails when it should.

Each case skips the harness's look for a chip (``--rehearse``: the
configuration's rehearsal sizes on the CPU) and drives the rest of a
run through ``benchmark/run.py``'s own ``main``, once sound and once
with the timed path broken underneath:

- training: a step that returns its state unchanged; half of each
  minibatch left out, the mean taken over the rest;
- serving: a token altered where it is produced.

(The exchange between chips is no fault a one-chip cell can have.)
The controls (the reference one precision below what the
configuration states) are held to the configurations' own limits:
training's by the harness itself (``--control``), serving's by the
reference's ``control_gaps`` over some hundreds of positions (the
rehearsal run checks too few tokens for the toy model's control to
pass the limit set at the cell's size). The readings at the cells'
own sizes on the chip, through ``--control``, are in PERF.md.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT

PATCHES = {
    "sound": "",
    "state_unchanged": """
import jax, jax.numpy as jnp
import veles_tpu.parallel.fused as fused
_build = fused.build_tick
def build(*args, **kwargs):
    steps = list(_build(*args, **kwargs))
    sweep = steps[2]
    def frozen(params, *rest):
        _, out = sweep(jax.tree.map(jnp.copy, params), *rest)
        return params, out
    steps[2] = frozen
    return tuple(steps)
fused.build_tick = build
""",
    "half_batch": """
import veles_tpu.parallel.fused as fused
_build = fused.build_tick
def build(*args, **kwargs):
    steps = list(_build(*args, **kwargs))
    sweep = steps[2]
    def half(params, hypers, norm, data, labels, rows, sizes, total,
             seeds):
        keep = rows.shape[1] // 2
        return sweep(params, hypers, norm, data, labels, rows[:, :keep],
                     sizes // 2, total / 2, seeds)
    steps[2] = half
    return tuple(steps)
fused.build_tick = build
""",
    "token_altered": """
import veles_tpu.parallel.decode as decode
_many = decode.slot_step_many
def altered(params, table, heads, state, *args, **kwargs):
    state, emitted = _many(params, table, heads, state, *args, **kwargs)
    return state, emitted.at[1].set((emitted[1] + 1) % table.shape[0])
decode.slot_step_many = altered
""",
}


def rehearse(workload, patch, more=()):
    code = PATCHES[patch] + """
import runpy, sys
sys.argv = ["benchmark/run.py", "--workload", %r, "--seed",
            "3000000019", "--seconds", "1", "--trace", "0", "--rehearse"]
sys.argv += %r
runpy.run_path("benchmark/run.py", run_name="__main__")
""" % (workload, list(more))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert out.returncode == 3, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["rehearsal"] is True
    assert "metrics" not in line
    over = sorted(name for name, row in line["compared"].items()
                  if row["value"] > row["limit"])
    if more:
        return line["would_be_correct"], line["control_correct"]
    return line["would_be_correct"], over


@pytest.mark.parametrize("workload, patch, must_be_over", [
    ("alexnet-227.train_mb128", "sound", None),
    ("alexnet-227.train_mb128", "state_unchanged", "dparam_norm_gap"),
    ("alexnet-227.train_mb128", "half_batch", "dparam_norm_gap"),
    ("gpt2-medium.serve_closed24", "sound", None),
    ("gpt2-medium.serve_closed24", "token_altered", "served_logit_gap"),
])
def test_a_broken_timed_path_reads_not_correct(workload, patch,
                                               must_be_over):
    would_be_correct, over = rehearse(workload, patch)
    if must_be_over is None:
        assert would_be_correct and not over
    else:
        assert not would_be_correct
        assert must_be_over in over


def test_training_control_fails_the_limits():
    """The reference at float8 operands, put in the program's place,
    against the reference, at the rehearsal size."""
    import jax.numpy as jnp
    import numpy

    from benchmark.harness import data as data_lib, train_fullbatch

    full = common.load_json("benchmark/configs/alexnet-227.json")
    config = train_fullbatch.scaled(full, True)
    reference = common.load_module(config["reference"])
    sizes, seed, mb = config["dataset"], 11, full["rehearsal"]["minibatch"]
    data, labels = data_lib.dataset(
        seed, config["input_shape"], sizes["n_valid"], sizes["n_train"],
        sizes["label_classes"])
    rows = sizes["n_valid"] + numpy.random.default_rng(seed).permutation(
        sizes["n_train"])
    runs = {name: reference.follow_first_epoch(
        config, seed, jnp.asarray(data), jnp.asarray(labels), rows, mb,
        **kwargs) for name, kwargs in (
            ("reference", {}), ("bfloat16", {"operands": "bfloat16"}),
            ("control", {"operands": "float8_e4m3fn"}))}
    stated, _ = train_fullbatch.gaps_between(runs["bfloat16"],
                                             runs["reference"])
    control, _ = train_fullbatch.gaps_between(runs["control"],
                                              runs["reference"])
    limits = full["limits"]
    assert all(stated[key] <= limits[key] for key in stated), stated
    assert any(control[key] > limits[key] for key in control), control


def test_the_harness_reads_the_training_control_as_not_correct():
    """``--control``: the run's own comparison reads correct, and the
    reference at float8 operands in the program's place, held to the
    same limits by the same code, does not."""
    assert rehearse("alexnet-227.train_mb128", "sound",
                    ("--control", "float8_e4m3fn")) == (True, False)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_serving_control_fails_the_limit(seed):
    """At each position of the same prompts and answered tokens, the
    token float8 operands put first lies further below the
    reference's best than the limit allows, and the token bfloat16
    operands (what the configuration states) put first does not."""
    import numpy

    from benchmark.harness import serve_generate

    full = common.load_json("benchmark/configs/gpt2-medium.json")
    config = serve_generate.scaled(full, True)
    limit = full["limits"]["served_logit_gap"]
    reference = common.load_module(config["reference"])
    params, table = reference.init_params(seed, config)
    rng = numpy.random.default_rng(seed)
    stated = control = 0.0
    for _ in range(6):
        prompt = rng.integers(0, config["vocab_size"], 20).tolist()
        served = rng.integers(0, config["vocab_size"], 100).tolist()
        stated = max(stated, reference.control_gaps(
            config, params, table, prompt, served, "bfloat16").max())
        control = max(control, reference.control_gaps(
            config, params, table, prompt, served,
            "float8_e4m3fn").max())
    assert stated <= limit < control, (stated, control)
