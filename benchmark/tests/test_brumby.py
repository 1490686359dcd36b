"""The ``brumby-14b-base`` configuration off the chip: the operation and
byte counts against their hand counts, the catalog's every number in
the file, the manifest's lines, ``--plan`` and ``--rehearse`` of its
cell, the control at the rehearsal's size, and the two new readers
over a made-up classification (a capture's worth of ``ret.state`` ops,
with one module the scope table did not match)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT
CELL = "brumby-14b-base.serve_closed24"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    return common.load_json("benchmark/configs/brumby-14b-base.json")


@pytest.fixture(scope="module")
def ops():
    return common.load_module("benchmark/ops/brumby.py")


def test_hand_counts(config, ops):
    assert ops.layer_matrices(config) == ops.HAND_LAYER_MATRICES \
        == (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
            + 3 * 5120 * 17408)
    assert ops.layer_parameters(config) == ops.HAND_LAYER_PARAMETERS \
        == 330342400 + 2 * 5120 + 2 * 128 + 8
    assert ops.head_parameters(config) == ops.HAND_HEAD == 151936 * 5120
    assert ops.per_token(config) == ops.HAND_PER_TOKEN \
        == 8 * 330342400 + 777912320
    # the issue's 4,198,652,992 parameters = 8.40 GB
    assert ops.parameters(config) == ops.HAND_PARAMETERS == 4198652992
    assert round(ops.parameters(config) * 2 / 1e9, 2) == 8.40
    assert ops.features(config) == ops.HAND_FEATURES == 128 * 129 // 2
    assert ops.state_bytes_per_slot_layer(config) \
        == ops.HAND_STATE_BYTES_PER_SLOT_LAYER == 8 * 8256 * 129 * 4
    assert ops.state_bytes_per_slot(config) \
        == ops.HAND_STATE_BYTES_PER_SLOT == 8 * 34080768
    # 16 slots hold 4.36 GB
    assert round(16 * ops.state_bytes_per_slot(config) / 1e9, 2) == 4.36


def test_the_program_holds_what_the_file_says(config):
    """``ops/retention.py``'s layout: 8,320 products a head, 0.8% over
    the 8,256 the counts are made at."""
    from veles_tpu.ops import retention

    held = retention.features(config["head_dim"])
    assert held == 65 * 128 == 8320
    assert "8,320" in config["deployment"] \
        and "34,344,960" in config["deployment"]
    assert config["num_key_value_heads"] * held * 129 * 4 == 34344960


def test_a_decode_step_and_a_prefill(config, ops):
    state_ops, state_bytes = ops.retention_state(config, 16)
    assert state_bytes == 2 * 16 * 272646144 == 8724676608
    assert state_ops == 8 * 16 * (3 * 8 + 2 * 40) * 8256 * 129
    step_ops, step_bytes = ops.decode_step(config, [700] * 16)
    assert step_bytes == ops.HAND_STEP_BYTES_16 \
        == 2 * 3420651520 + 8724676608
    assert step_ops == 2 * 3420651520 * 16 + state_ops
    # the lengths change nothing: the state does not grow
    assert ops.decode_step(config, [3] * 16) == (step_ops, step_bytes)
    # 19.0 ms at 819 GB/s, 56% of it the states
    assert round(step_bytes / 819e9 * 1e3, 1) == 19.0
    assert round(state_bytes / step_bytes, 2) == 0.56
    assert ops.prefill(config, [256]) == (
        (2 * 8 * 330342400 + 8 * 8 * 2 * 8256 * 129) * 256
        + 8 * 40 * 4 * 128 * 256 * 257 // 2 + 2 * 777912320)
    # ~5.5 GFLOP a prompt token at the median prompt
    assert 5.3e9 < ops.prefill(config, [256]) / 256 < 6.2e9


def test_the_file_holds_every_number_of_the_catalog(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fin:
        row = next(r for r in map(json.loads, fin)
                   if r["name"] == "Brumby-14B-Base")
    assert config["source"] == row["source_url"]
    differ = sorted(key for key, value in row["config"].items()
                    if config.get(key, "absent") != value)
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 40}
    for key in ("reduced", "assumed", "deployment", "departures",
                "precision", "init", "limits", "rehearsal"):
        assert config[key], key
    assert config["serving"] == dict(
        slots=16, max_len=2048, chunk=8, n_tokens=192, max_queue=48,
        deadline=300.0, paged=False, quantize=None, temperature=0.0,
        prefill_tokens=4096)
    bench = common.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "brumby-14b-base")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/brumby-14b-base.json"


def test_the_manifests_lines_are_lines():
    # a why, a layer and a source are one printable line of 1 to 200
    bench = common.load_json("BENCHMARK.json")
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "per_layer")
             for e in bench[group] for key in ("why", "layer", "source")
             if key in e]
    assert [(name, key, len(text)) for name, key, text in lines
            if not (1 <= len(text) <= 200 and text.isprintable()
                    and text.isascii())] == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "brumby-14b-base"
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_the_mix_is_the_issues(config):
    mix = common.load_json("benchmark/traffic/chat_closed24_k12.json")
    assert mix["clients"] == 24 == 1.5 * config["serving"]["slots"]
    assert mix["pool"] == 24 * 12
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 128,
         "max": 1024},
        {"dist": "lognormal", "median": 96, "sigma": 0.5, "min": 32,
         "max": 192})
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1216 \
        <= config["serving"]["max_len"]
    assert mix["output_len"]["max"] == config["serving"]["n_tokens"]
    assert (mix["loop"], mix["lead_in_s"], mix["trace_seconds"],
            mix["checked_requests"]) == ("closed", 8.0, 2.0, 6)


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"),
         "--workload", CELL] + list(args),
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)


def test_plan_resolves_every_file():
    done = run("--plan")
    assert done.returncode == 0, done.stderr
    plan = json.loads(done.stdout)
    files = [plan[key] for key in ("config_file", "traffic_file", "driver",
                                   "reference", "ops")]
    files += list(plan["per_layer"].values())
    assert all(os.path.exists(os.path.join(ROOT, f)) for f in files)
    assert sorted(plan["per_layer"]) == sorted([
        "front.refused_share.serve", "scheduler.compiles_in_window.serve",
        "scheduler.books_ms.serve", "model.decode_step_ms.serve",
        "model.serve_mfu", "kernel.decode_step_roofline.serve",
        "device.idle_share.serve", "device.hbm_peak_share.serve",
        "model.decode_cache_ms.serve", "model.decode_attend_ms.serve",
        "model.decode_matmul_ms.serve", "model.decode_head_ms.serve",
        "model.decode_unscoped_share.serve", "model.decode_gqa_ms.serve",
        "model.decode_retention_ms.serve",
        "kernel.retention_state_roofline.serve"])
    assert plan["end_to_end"] == ["serve_tokens_per_s_chip", "setup_s"]
    # the two new readers are read in the new cell alone, and are the
    # last of the list
    bench = common.load_json("BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "model.decode_retention_ms.serve",
        "kernel.retention_state_roofline.serve"]
    for metric in bench["per_layer"][-2:]:
        assert metric["workloads"] == [CELL]


def test_rehearsal_serves_and_compares():
    done = run("--rehearse", "--seed", "3000000019", "--seconds", "4",
               "--trace", "1")
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] is False
    assert line["would_be_correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["counters"]["compiles_in_window"] == 0
    # one step program whatever the slots hold
    warmed = json.loads(line["counters"]["warm_up_programs"])
    assert len(warmed["admit"]) == 12
    compared = line["compared"]
    assert compared["requests_failed_or_unanswered"]["value"] == 0
    assert 0.0 <= compared["served_logit_gap"]["value"] \
        < compared["served_logit_gap"]["limit"]


#: between what bfloat16 operands and what float8 operands read at the
#: rehearsal's widths (the cell's own limit is set on the chip, at its
#: size)
TOY_LIMIT = 0.05


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_the_control_reads_not_correct(config, seed):
    """At each position of the same prompts and answered tokens, the
    token float8 operands put first lies further below the reference's
    best than the token bfloat16 operands (what the configuration
    states) put first: the control is not correct by a limit that lets
    the stated precision pass."""
    import numpy

    from benchmark.harness import serve_generate

    toy = serve_generate.scaled(config, True)
    toy["serving"] = dict(toy["serving"], n_tokens=100)
    reference = common.load_module(toy["reference"])
    params, table = reference.init_params(seed, toy)
    rng = numpy.random.default_rng(seed)
    stated = control = 0.0
    for _ in range(3):
        prompt = rng.integers(0, toy["vocab_size"], 20).tolist()
        served = rng.integers(0, toy["vocab_size"], 100).tolist()
        stated = max(stated, reference.control_gaps(
            toy, params, table, prompt, served, "bfloat16").max())
        control = max(control, reference.control_gaps(
            toy, params, table, prompt, served, "float8_e4m3fn").max())
    assert stated <= TOY_LIMIT < control, (stated, control)


def _reader(name):
    return common.load_module("benchmark/metrics/%s.py" % name)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(config, ops, found, slots=16):
    """A traced window of 2 s with three chunks of 8 steps dispatched
    inside it over ``slots`` occupied slots."""
    held = {str(s): [s, 500] for s in range(slots)}
    return {"scoped": {"slot_step_many": found}, "config": config,
            "ops": ops, "peaks": PEAKS,
            "counters": {"chunk": 8, "slots": 16, "traced_from": 100.0,
                         "traced_s": 2.0, "dispatches": [
                             {"at": 100.2 + i * 0.2, "chunk": 8,
                              "tokens_out": 100 * i, "held": held}
                             for i in range(3)]}}


def _capture(unmatched):
    """Three modules of 8 steps; in each matched one the 8 layers'
    ``retention_step`` calls take 12 ms a step (the state's 8.72 GB at
    727 GB/s), the feature rows 0.4 and the gate 0.1. An unmatched
    module's ops carry no scope."""
    matched = 3 - unmatched
    ops = {
        ("attend", "ret.state/retention_step",
         "retention_step custom-call"): matched * 8 * 12.0e6,
        ("attend", "attn.attend/ret.state", "fusion.1 fusion"):
            matched * 8 * 0.2e6,
        ("attend", "attn.attend/ret.phi", "fusion.2 fusion"):
            matched * 8 * 0.4e6,
        ("matmul", "attn.qkv/ret.gate", "fusion.3 fusion"):
            matched * 8 * 0.1e6,
        ("matmul", "decode.dispatch/mlp", "fusion.4 fusion"):
            matched * 8 * 9.0e6}
    if unmatched:
        ops[("unscoped", "", "retention_step custom-call")] = \
            unmatched * 8 * 12.0e6
    return {"modules": 3, "unmatched": unmatched, "ops": ops}


def test_the_new_readers_over_a_made_up_capture(config, ops):
    ctx = _ctx(config, ops, _capture(0))
    assert _reader("model.decode_retention_ms.serve").read(ctx) \
        == pytest.approx(12.7)
    share = _reader("kernel.retention_state_roofline.serve").read(ctx)
    # 8,724,676,608 B at 819 GB/s = 10.65 ms of the 12.2 ms under
    # ret.state
    assert share == pytest.approx(100 * 8724676608 / 819e9 / 12.2e-3)
    assert 87.0 < share < 87.6
    # half the slots live: half the bytes over the same time
    half = _reader("kernel.retention_state_roofline.serve").read(
        _ctx(config, ops, _capture(0), slots=8))
    assert half == pytest.approx(share / 2)


def test_an_unmatched_module_reads_no_more_than_the_matched_give(
        config, ops):
    """One of three modules ran a program the scope table lacks (a
    chunk dispatched before the tracer went on): its ops read
    unscoped. The roofline divides by the modules that matched, so it
    reads what the matched ones give; divided by all three it would
    read 1.5 times that, a true 87% as 131%."""
    reader = _reader("kernel.retention_state_roofline.serve")
    whole = reader.read(_ctx(config, ops, _capture(0)))
    partly = reader.read(_ctx(config, ops, _capture(1)))
    assert partly == pytest.approx(whole) and partly <= 100.0
    # no module matched: nothing to read
    assert reader.read(_ctx(config, ops, _capture(3))) is None


def test_a_program_without_the_scopes_reads_nothing(config, ops):
    """GPT-2's block, the parent's program and counts: the line leaves
    the metrics out and nothing raises."""
    found = {"modules": 1, "unmatched": 0, "ops": {
        ("matmul", "decode.dispatch/attn.qkv", "fusion.3 fusion"): 8e6}}
    gpt2 = common.load_module("benchmark/ops/gpt2.py")
    for name in ("model.decode_retention_ms.serve",
                 "kernel.retention_state_roofline.serve"):
        assert _reader(name).read(_ctx(config, ops, found)) is None
        assert _reader(name).read(_ctx(config, ops, None)) is None
        assert _reader(name).read(_ctx(config, gpt2, found)) is None
