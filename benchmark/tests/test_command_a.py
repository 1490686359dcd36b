"""The ``command-a-plus-05-2026`` configuration off the chip: the
operation and byte counts against their hand counts, the cut and the
published sizes the file states, ``--plan`` and ``--rehearse`` of its cell,
the control at the rehearsal's size, and the four new readers over a
made-up classification and capture. No other cell's list of metrics is
pinned here."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT
CELL = "command-a-plus-05-2026.serve_mixed72"
NEW = ["model.decode_window_ms.serve", "model.decode_global_ms.serve",
       "kernel.window_attend_roofline.serve",
       "kernel.prompt_attend_roofline.serve"]


@pytest.fixture(scope="module")
def config():
    return common.load_json("benchmark/configs/command-a-plus-05-2026.json")


@pytest.fixture(scope="module")
def ops():
    return common.load_module("benchmark/ops/command_a.py")


def test_hand_counts(config, ops):
    assert ops.attention_parameters(config) == ops.HAND_ATTENTION \
        == 2 * 4096 * 16384 + 2 * 4096 * 1024
    assert ops.expert_parameters(config) == ops.HAND_EXPERT \
        == 3 * 4096 * 4096
    assert ops.outside_experts_layer(config) == ops.HAND_OUTSIDE_LAYER \
        == 142606336 + 524288 + 4 * 50331648
    assert ops.layer_parameters(config) == ops.HAND_LAYER_HELD \
        == 344457216 + 16 * 50331648 + 4096
    assert ops.held_per_token(config) == 1.0
    assert ops.per_token(config) \
        == 4 * ops.HAND_PER_TOKEN_LAYER + 32768 * 4096
    # 4,733,292,544 parameters held = 9.47 GB
    assert ops.parameters(config) == ops.HAND_PARAMETERS == 4733292544
    assert round(ops.parameters(config) * 2 / 1e9, 2) == 9.47
    assert ops.kv_bytes(config) == ops.HAND_KV_BYTES == 4096
    assert ops.ring_bytes(config) == ops.HAND_RING_BYTES == 50331648
    # 789.6 MFLOP a prompt token a layer
    assert 2 * ops.HAND_PER_TOKEN_LAYER == 789577728
    # the whole model: 218.25 B
    whole = 32 * (344461312 + 128 * 50331648) + 262144 * 4096
    assert round(whole / 1e9, 2) == 218.25
    assert ops.HAND_OUTSIDE_LAYER + 4096 == 344461312


def test_a_decode_step_and_a_prefill(config, ops):
    # a window layer attends at most 4,096 positions, the global all
    assert ops.attended(config, 99) == 4 * 100
    assert ops.attended(config, 8191) == 3 * 4096 + 8192
    attend_ops, attend_bytes = ops.window_attend(config, [99, 8191])
    assert attend_bytes == 4096 * (400 + 3 * 4096 + 8192)
    assert attend_ops == 4 * 128 * 128 * (400 + 3 * 4096 + 8192)
    step_ops, step_bytes = ops.decode_step(config, [1900] * 48)
    touched = 16 * (1 - (15 / 16) ** 48)
    assert round(touched, 1) == 15.3
    weights = (4 * (344457216 + touched * 50331648) + 32768 * 4096) * 2
    assert step_bytes == pytest.approx(
        weights + 48 * 4096 * (3 * 1901 + 1901))
    # ~10.7 GB at the traffic's mean context: 13-14 ms at 819 GB/s
    assert 9.5e9 < step_bytes < 11.5e9
    # the window cuts the pairs: 25.2 M a window layer at 8,192, not
    # 33.6 M
    assert ops.prompt_pairs(config, 8192) == 3 * (
        4096 * 4097 // 2 + 4096 * 4096) + 8192 * 8193 // 2
    per_layer = 2 * ops.HAND_PER_TOKEN_LAYER
    assert ops.prefill(config, [8192]) == pytest.approx(
        4 * per_layer * 8192 + 4 * 128 * 128 * ops.prompt_pairs(
            config, 8192) + 2 * 32768 * 4096)
    # 33 TFLOP, 168 ms at 197 TFLOP/s at the least
    assert 32e12 < ops.prefill(config, [8192]) < 34e12


def test_the_file_states_the_cut_and_the_published_sizes(config):
    assert config["source"] == ("https://huggingface.co/CohereLabs/"
                                "command-a-plus-05-2026/blob/main/config.json")
    # every key cut is named, with its published value beside it
    assert sorted(config["published"]) == sorted(config["reduced"]) == [
        "layer_types", "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 32768)
    assert config["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert config["routed_experts"] == config["published"]["num_experts"] \
        == 128
    assert (config["published"]["num_hidden_layers"],
            config["published"]["vocab_size"]) == (32, 262144)
    # the widths are the published ones
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["num_experts_per_tok"],
            config["num_shared_experts"], config["sliding_window"]) == (
        4096, 128, 8, 128, 4096, 8, 4, 4096)
    for key in ("reduced", "assumed", "deployment", "departures",
                "precision", "init", "limits", "rehearsal"):
        assert config[key], key
    assert config["serving"] == dict(
        slots=48, max_len=8704, chunk=8, n_tokens=512, max_queue=144,
        deadline=300.0, paged=False, quantize=None, temperature=0.0,
        prefill_tokens=8192, admit_tokens=8192)
    bench = common.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "command-a-plus-05-2026")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_mix_is_the_cells(config):
    mix = common.load_json("benchmark/traffic/chat_mixed72.json")
    assert mix["clients"] == 72 == 1.5 * config["serving"]["slots"]
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 256,
         "max": 8192},
        {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
         "max": 512})
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == config["serving"]["max_len"]
    assert mix["output_len"]["max"] == config["serving"]["n_tokens"]
    assert mix["prompt_len"]["max"] == config["serving"]["prefill_tokens"]
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
    assert set(NEW) <= set(plan["per_layer"])
    # not the experts' roofline (it divides by every module) nor the
    # occupancy (silent in traced runs)
    assert "kernel.moe_experts_roofline.serve" not in plan["per_layer"]
    assert "scheduler.slot_occupancy.serve" not in plan["per_layer"]
    assert plan["end_to_end"] == ["serve_tokens_per_s_chip", "setup_s"]
    bench = common.load_json("BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW
    for metric in bench["per_layer"][-4:]:
        assert metric["workloads"] == [CELL]
    assert bench["workloads"][-1]["name"] == CELL


def test_rehearsal_serves_and_compares():
    done = run("--rehearse", "--seed", "3000000023", "--seconds", "4",
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
#: rehearsal's widths, seeds 7-9: bfloat16 1.2e-5-1.2e-3, float8
#: 5.8e-3-7.1e-3 (the cell's own limit is set on the chip, at its size)
TOY_LIMIT = 0.003


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_the_control_reads_not_correct(config, seed):
    """At each position of the same prompts and answered tokens, the
    token float8 operands put first lies further below the reference's
    best, on the mean over the answer, than the token bfloat16 operands
    (what the configuration states) put first."""
    import numpy

    from benchmark.harness import serve_generate

    toy = serve_generate.scaled(config, True)
    toy["serving"] = dict(toy["serving"], n_tokens=40)
    reference = common.load_module(toy["reference"])
    params, table = reference.init_params(seed, toy)
    rng = numpy.random.default_rng(seed)
    stated = control = 0.0
    for _ in range(3):
        prompt = rng.integers(0, toy["vocab_size"], 20).tolist()
        served = rng.integers(0, toy["vocab_size"], 40).tolist()
        stated = max(stated, reference.control_gaps(
            toy, params, table, prompt, served, "bfloat16").max())
        control = max(control, reference.control_gaps(
            toy, params, table, prompt, served, "float8_e4m3fn").max())
    assert stated <= TOY_LIMIT < control, (stated, control)


def _reader(name):
    return common.load_module("benchmark/metrics/%s.py" % name)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(config, ops, found, slots=48, admitted=(), trace=None):
    """A traced window of 2 s with one chunk of 8 steps dispatched
    before it and three inside it over ``slots`` occupied slots of
    1,000 positions, the first inside after admissions of ``admitted``
    prompt lengths into the first slots."""
    held = {str(s): [s, 1000 + 8] for s in range(slots)}
    fresh = dict(held, **{str(j): [1000 + j, n + 8]
                          for j, n in enumerate(admitted)})
    rows = [{"at": 99.9, "chunk": 8, "tokens_out": 0, "held": held}] + [
        {"at": 100.2 + i * 0.2, "chunk": 8, "tokens_out": 100 * (i + 1),
         "held": fresh} for i in range(3)]
    return {"scoped": {"slot_step_many": found}, "config": config,
            "ops": ops, "peaks": PEAKS,
            "reduced": {"window": (0.0, 2e9),
                        "trace": trace or {"devices": {}, "spans": []}},
            "counters": {"chunk": 8, "slots": 48, "traced_from": 100.0,
                         "traced_s": 2.0, "dispatches": rows}}


def _capture(unmatched):
    """Three modules of 8 steps; in each matched one the window layers'
    ring attend takes 2 ms a step, their ring's write 0.2, the global
    layer's attend 1. An unmatched module's ops carry no scope."""
    matched = 3 - unmatched
    ops = {
        ("attend", "attn.attend/swa.ring", "fusion.1 fusion"):
            matched * 8 * 2.0e6,
        ("cache_append", "cache.append/cache.ring",
         "slab_write.1 custom-call"): matched * 8 * 0.2e6,
        ("attend", "attn.attend/nope.attend", "fusion.2 fusion"):
            matched * 8 * 1.0e6,
        ("matmul", "decode.dispatch/mlp", "fusion.4 fusion"):
            matched * 8 * 9.0e6}
    if unmatched:
        ops[("unscoped", "", "fusion.1 fusion")] = unmatched * 8 * 3.0e6
    return {"modules": 3, "unmatched": unmatched, "ops": ops}


def test_the_layer_readers_over_a_made_up_capture(config, ops):
    ctx = _ctx(config, ops, _capture(0))
    assert _reader("model.decode_window_ms.serve").read(ctx) \
        == pytest.approx(2.2)
    assert _reader("model.decode_global_ms.serve").read(ctx) \
        == pytest.approx(1.0)
    share = _reader("kernel.window_attend_roofline.serve").read(ctx)
    # 48 slots at 1,003.5 positions at the chunk's mean step: each
    # attends 4 x 1,004.5 rows of 4,096 B (790 MB) in 3 ms
    want = 48 * 4 * 1004.5 * 4096 / 819e9 / 3e-3
    assert share == pytest.approx(100 * want)
    # an unmatched module reads what the matched ones give
    partly = _reader("kernel.window_attend_roofline.serve").read(
        _ctx(config, ops, _capture(1)))
    assert partly == pytest.approx(share) and partly <= 100.0


def test_the_prompt_reader_over_a_made_up_trace(config, ops):
    """Two prompts admitted in the window, of 300 (bucket 512) and
    5,000 (bucket 8,192) positions, and 200 (bucket 256: not surely the
    kernel's, left out); the kernel's calls take 0.3 s inside the
    window and one more outside it."""
    calls = [("%splash_mqa_fwd_no_residuals.3 = bf16[16,8192,128] "
              "custom-call(...)", 0.5e9 + i * 1e8, 1e8) for i in range(3)]
    calls.append(("%splash_mqa_fwd_no_residuals.3 = bf16[16,8192,128] "
                  "custom-call(...)", 3e9, 1e8))
    calls.append(("%fusion.9 = f32[4] fusion(...)", 0.4e9, 5e8))
    trace = {"devices": {0: {"ops": calls, "modules": []}}, "spans": []}
    ctx = _ctx(config, ops, _capture(0), admitted=(300, 5000, 200),
               trace=trace)
    share = _reader("kernel.prompt_attend_roofline.serve").read(ctx)
    work = ops.prompt_attend(config, 512) + ops.prompt_attend(config, 8192)
    assert share == pytest.approx(100 * work / 197e12 / 0.3)
    assert 0 < share <= 100.0


def test_a_program_without_the_scopes_reads_nothing(config, ops):
    """GPT-2's block, the parent's program and counts: the line leaves
    the metrics out and nothing raises."""
    found = {"modules": 1, "unmatched": 0, "ops": {
        ("matmul", "decode.dispatch/attn.qkv", "fusion.3 fusion"): 8e6}}
    gpt2 = common.load_module("benchmark/ops/gpt2.py")
    for name in NEW:
        assert _reader(name).read(_ctx(config, ops, found)) is None
        assert _reader(name).read(_ctx(config, ops, None)) is None
        assert _reader(name).read(_ctx(config, gpt2, found)) is None
