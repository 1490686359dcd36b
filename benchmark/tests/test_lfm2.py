"""The ``lfm2-8b-a1b`` configuration off the chip: the operation and
byte counts against their hand counts, the catalog's every number in
the file, ``--plan`` and ``--rehearse`` of its cell, the control at the
rehearsal's size, the two new readers over a made-up classification."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT
CELL = "lfm2-8b-a1b.serve_closed96"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    return common.load_json("benchmark/configs/lfm2-8b-a1b.json")


@pytest.fixture(scope="module")
def ops():
    return common.load_module("benchmark/ops/lfm2.py")


def test_hand_counts(config, ops):
    assert ops.conv_parameters(config) == ops.HAND_CONV_PARAMETERS \
        == 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert ops.attention_parameters(config) \
        == ops.HAND_ATTENTION_PARAMETERS \
        == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert ops.expert_parameters(config) == ops.HAND_EXPERT_PARAMETERS \
        == 3 * 2048 * 1792
    assert ops.kinds(config) == (10, 3) and ops.layers(config) == (1, 12)
    assert ops.outside_experts(config) == ops.HAND_OUTSIDE_EXPERTS \
        == (10 * 16783360 + 3 * 10485760 + 3 * 2048 * 7168
            + 12 * 2048 * 32 + 2048 * 65536)
    assert ops.outside_experts(config) + ops.routed_per_token(config) \
        == ops.HAND_PER_TOKEN == 906817536
    # K and V at 1,024 B a position a leaf, six leaves
    assert ops.kv_bytes_per_position(config) \
        == ops.HAND_KV_BYTES_PER_POSITION == 6 * 1024
    assert ops.state_bytes_per_slot(config) \
        == ops.HAND_STATE_BYTES_PER_SLOT == 10 * 2 * 2048 * 2
    # 64 live slots x top-4 touch every one of the 32 experts
    assert round(ops.expected_touched(config, 64), 2) == 31.99
    # every weight of the cut, the norms' gains and the selection bias
    # with the matrices, is the issue's 4,606,249,728 = 8.58 GiB
    gains = 13 * 2 * 2048 + 3 * 2 * 64 + 2048 + 12 * 32
    whole = (ops.block_parameters_outside_experts(config)
             + 2048 * 65536 + 12 * 32 * ops.expert_parameters(config)
             + gains)
    assert whole == 4606249728
    assert round(whole * 2 / 2 ** 30, 2) == 8.58


def test_a_decode_step_and_a_prefill(config, ops):
    step_ops, step_bytes = ops.decode_step(config, [499] * 64)
    assert step_ops == 2 * 906817536 * 64 \
        + 2 * 3 * 32 * 2 * 64 * 500 * 64
    touched = ops.expected_touched(config, 64)
    assert step_bytes == pytest.approx(
        378335232 * 2 + 12 * touched * 11010048 * 2
        + 6144 * 500 * 64 + 2 * 81920 * 64)
    # the experts are over four fifths of a step's bytes
    assert 12 * touched * 11010048 * 2 / step_bytes > 0.8
    assert ops.prefill(config, [256]) == (
        2 * (906817536 - 2048 * 65536) * 256
        + 2 * 3 * 32 * 2 * 64 * 256 * 257 // 2
        + 2 * 2048 * 65536)
    n_ops, nbytes = ops.expert_products(config, 256, 32)
    assert n_ops == 2 * 11010048 * 256
    assert nbytes == (11010048 * 32 + 2 * 2048 * 256) * 2


def test_the_file_holds_every_number_of_the_catalog(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fin:
        row = next(r for r in map(json.loads, fin)
                   if r["name"] == "LFM2-8B-A1B")
    assert config["source"] == row["source_url"]
    differ = sorted(key for key, value in row["config"].items()
                    if config.get(key) != value)
    assert differ == sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
    # the cut: one leading dense layer and layers 2-13 as published,
    # three whole periods
    published = row["config"]["layer_types"]
    assert config["layer_types"] == published[1:14] \
        == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    assert config["num_hidden_layers"] == 13 == len(config["layer_types"])
    assert config["num_dense_layers"] == 1
    assert config["published"]["layer_types"] == published
    bench = common.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_manifests_lines_are_lines():
    # the driver refused this PR once over a `why` of 207 characters:
    # a why, a layer and a source are one printable line of 1 to 200
    bench = common.load_json("BENCHMARK.json")
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "per_layer")
             for e in bench[group] for key in ("why", "layer", "source")
             if key in e]
    assert [(name, key, len(text)) for name, key, text in lines
            if not (1 <= len(text) <= 200 and text.isprintable()
                    and text.isascii())] == []
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_the_mix_is_the_issues(config):
    mix = common.load_json("benchmark/traffic/chat_closed96.json")
    assert mix["clients"] == 96 == 1.5 * config["serving"]["slots"]
    assert mix["pool"] % mix["clients"] == 0
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 128,
         "max": 1024},
        {"dist": "lognormal", "median": 192, "sigma": 0.4, "min": 64,
         "max": 384})
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1408 \
        <= config["serving"]["max_len"]
    assert mix["output_len"]["max"] == config["serving"]["n_tokens"]
    assert (mix["pairing_seed"], mix["lead_in_s"], mix["trace_seconds"],
            mix["checked_requests"]) == (34, 8.0, 2.0, 6)


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
    for name in ("model.decode_step_ms.serve",
                 "model.decode_moe_experts_ms.serve",
                 "model.decode_moe_route_ms.serve",
                 "model.decode_conv_ms.serve", "model.decode_gqa_ms.serve",
                 "scheduler.moe_load_max_over_mean.serve",
                 "scheduler.compiles_in_window.serve",
                 "model.serve_mfu", "kernel.decode_step_roofline.serve",
                 "device.hbm_peak_share.serve"):
        assert name in plan["per_layer"]
    # not the experts' roofline share: its reader takes a chunk whose
    # program the scope table lacks as if its ops had run in no time
    # and can read a kernel at 99% of the peak as 107% (PERF.md
    # section 7 item 8); the cell joins it when a benchmark issue has
    # mended the reader
    for name in ("scheduler.slot_occupancy.serve",
                 "model.decode_latent_ms.serve",
                 "kernel.moe_experts_roofline.serve"):
        assert name not in plan["per_layer"]
    assert plan["end_to_end"] == ["serve_tokens_per_s_chip", "setup_s"]
    # the two new readers are read in the new cell alone
    bench = common.load_json("BENCHMARK.json")
    for metric in bench["per_layer"][-2:]:
        assert metric["workloads"] == [CELL]


def test_rehearsal_serves_and_compares():
    done = run("--rehearse", "--seed", "3000000007", "--seconds", "4",
               "--trace", "1")
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] is False
    assert line["would_be_correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["counters"]["compiles_in_window"] == 0
    compared = line["compared"]
    assert compared["requests_failed_or_unanswered"]["value"] == 0
    assert 0.0 <= compared["served_logit_gap"]["value"] \
        < compared["served_logit_gap"]["limit"]


#: between what bfloat16 operands and what float8 operands read at the
#: rehearsal's widths (a toy's logits are a tenth as far apart as the
#: model's: the cell's own limit is set on the chip, at its size)
TOY_LIMIT = 0.012


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_the_control_reads_not_correct(config, seed):
    """At each position of the same prompts and answered tokens, the
    token float8 operands put first lies further below the reference's
    best, in the mean over an answer, than the token bfloat16 operands
    (what the configuration states) put first: the control is not
    correct by a limit that lets the stated precision pass."""
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


def _ctx(config, ops, found):
    return {"scoped": {"slot_step_many": found}, "config": config,
            "ops": ops, "counters": {"chunk": 8, "slots": 64}}


def test_the_new_readers_over_a_made_up_classification(config, ops):
    """Each sums the ops whose innermost scopes are its own, whatever
    accepted part holds them, over the modules' steps."""
    found = {"modules": 2, "unmatched": 0, "ops": {
        ("matmul", "attn.qkv/conv.in", "fusion.1 fusion"): 3.2e6,
        ("attend", "attn.attend/conv.mix", "fusion.2 fusion"): 1.6e6,
        ("matmul", "attn.out/conv.out", "fusion.3 fusion"): 1.6e6,
        ("cache_append", "cache.append/cache.state", "fusion.4 fusion"):
            1.6e6,
        ("matmul", "attn.qkv/gqa.norm", "fusion.5 fusion"): 0.8e6,
        ("matmul", "attn.qkv/gqa.rope", "fusion.6 fusion"): 0.8e6,
        ("matmul", "decode.dispatch/attn.qkv", "fusion.7 fusion"): 4e6,
        ("matmul", "mlp/moe.experts", "moe_streamed custom-call"): 160e6}}
    ctx = _ctx(config, ops, found)
    assert _reader("model.decode_conv_ms.serve").read(ctx) \
        == pytest.approx(8e6 / 1e6 / 16)
    assert _reader("model.decode_gqa_ms.serve").read(ctx) \
        == pytest.approx(1.6e6 / 1e6 / 16)
    assert _reader("model.decode_moe_experts_ms.serve").read(ctx) \
        == pytest.approx(10.0)


def test_a_program_without_the_scopes_reads_nothing(config, ops):
    """GPT-2's block, the parent's program: the line leaves the
    metrics out and nothing raises."""
    found = {"modules": 1, "unmatched": 0, "ops": {
        ("matmul", "decode.dispatch/attn.qkv", "fusion.3 fusion"): 8e6}}
    ctx = _ctx(config, ops, found)
    for name in ("model.decode_conv_ms.serve", "model.decode_gqa_ms.serve"):
        assert _reader(name).read(ctx) is None
        assert _reader(name).read(_ctx(config, ops, None)) is None
