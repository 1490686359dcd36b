"""The ``solar-open2-250b`` configuration off the chip: the operation and
byte counts against their hand counts, the cut and the published sizes
the file states, ``--plan`` and ``--rehearse`` of its cell, the control
at the rehearsal's size, and the two new readers over a made-up
classification. No other cell's list of metrics is pinned here."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT
CELL = "solar-open2-250b.serve_long192"
NEW = ["model.decode_kda_ms.serve", "kernel.kda_state_roofline.serve"]


@pytest.fixture(scope="module")
def config():
    return common.load_json("benchmark/configs/solar-open2-250b.json")


@pytest.fixture(scope="module")
def ops():
    return common.load_module("benchmark/ops/solar_open2.py")


def test_hand_counts(config, ops):
    assert ops.expert_parameters(config) == ops.HAND_EXPERT \
        == 3 * 4096 * 1280
    assert ops.held_experts(config) == 40 * ops.HAND_EXPERT == 629145600
    assert ops.kda_mixer(config) == ops.HAND_KDA_MIXER == (
        3 * 33554432 + 33554432 + 98304 + 1572864 + 8256 + 262144
        + 1572864 + 128)
    assert ops.gqa_mixer(config) == ops.HAND_GQA_MIXER \
        == 3 * 33554432 + 2 * 4194304
    assert ops.outside_mixer(config) == ops.HAND_OUTSIDE_MIXER \
        == 1310720 + 320 + 15728640 + 8192
    layer = ops.HAND_OUTSIDE_MIXER + 629145600
    assert ops.HAND_KDA_LAYER == ops.HAND_KDA_MIXER + layer == 783925760
    assert ops.HAND_GQA_LAYER == ops.HAND_GQA_MIXER + layer == 755245376
    assert ops.HAND_PERIOD == 3 * ops.HAND_KDA_LAYER + ops.HAND_GQA_LAYER
    assert ops.HAND_EMBED_HEAD == 2 * 24576 * 4096 + 4096
    # 3,308,353,344 parameters held = 6.62 GB
    assert ops.parameters(config) == ops.HAND_PARAMETERS \
        == ops.HAND_PERIOD + ops.HAND_EMBED_HEAD
    assert round(ops.parameters(config) * 2 / 1e9, 2) == 6.62
    # the whole model: 250.3 B (the published 250B)
    expert_layer = 320 * ops.HAND_EXPERT + ops.HAND_OUTSIDE_MIXER
    whole = 36 * (ops.HAND_KDA_MIXER + expert_layer) \
        + 12 * (ops.HAND_GQA_MIXER + expert_layer) + 2 * 196608 * 4096
    assert round(whole / 1e9, 1) == 250.3
    assert ops.held_per_token(config) == 1.0
    assert ops.per_token(config) == 3 * 137625600 + 109051904 \
        + 4 * 32768000 + 24576 * 4096
    assert ops.state_bytes_per_slot(config) \
        == ops.HAND_STATE_BYTES_PER_SLOT == 3 * 64 * 128 * 128 * 4
    assert ops.conv_bytes_per_slot(config) \
        == ops.HAND_CONV_BYTES_PER_SLOT == 3 * 3 * 24576 * 2
    assert ops.kv_bytes(config) == ops.HAND_KV_BYTES == 4096
    assert round(ops.expected_touched(config, 128), 1) == 38.4


def test_a_decode_step_and_a_prefill(config, ops):
    step_ops, step_bytes = ops.decode_step(config, [2760] * 128)
    state_ops, state_bytes = ops.kda_state(config, 128)
    # each live slot's state read and written, its rows read
    assert state_bytes == 128 * (2 * 12582912 + 3 * (4 * 8192 + 64) * 4)
    assert state_ops == 128 * 3 * 7 * 64 * 128 * 128
    touched = 40 * (1 - (1 - 8 / 320) ** 128)
    weights = (ops.HAND_GQA_MIXER + 3 * ops.HAND_KDA_MIXER
               + 4 * (ops.HAND_OUTSIDE_MIXER + touched * ops.HAND_EXPERT)
               + 24576 * 4096) * 2
    assert step_bytes == pytest.approx(
        weights + 4096 * 128 * 2761 + state_bytes + 2 * 128 * 442368)
    # ~11.05 GB at the traffic's mean context: 13.5 ms at 819 GB/s,
    # 30% of it the states
    assert 10.5e9 < step_bytes < 11.5e9
    assert 0.28 < state_bytes / step_bytes < 0.31
    assert ops.prefill(config, [8192]) == pytest.approx(
        8192 * (2 * (ops.per_token(config) - 24576 * 4096)
                + ops.recurrence_ops(config))
        + 4 * 64 * 128 * 8192 * 8193 // 2 + 2 * 24576 * 4096)
    # 12 TFLOP, 61 ms at 197 TFLOP/s at the least
    assert 11.5e12 < ops.prefill(config, [8192]) < 12.5e12


@pytest.mark.parametrize("n,pairs", [(512, 0), (1024, 1024 * 1025 // 2),
                                     (8192, 8192 * 8193 // 2)])
def test_the_splash_kernel_s_prompt_work(config, ops, n, pairs):
    """The GQA layer's causal pairs at 64 heads' scores and sums, where
    one row of ``n`` takes the kernel; none at 512, which is XLA's."""
    assert ops.prompt_attend(config, n) == 2 * 64 * 2 * 128 * pairs


def test_the_file_states_the_cut_and_the_published_sizes(config):
    catalog_keys = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in catalog_keys.items():
        assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert config["source"] == ("https://huggingface.co/upstage/"
                                "Solar-Open2-250B/blob/main/config.json")
    assert sorted(config["published"]) == sorted(config["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["gqa_layers"]) == (4, 40, 24576,
                                                            [0])
    assert config["routed_experts"] \
        == config["published"]["n_routed_experts"] == 320
    assert (config["published"]["num_hidden_layers"],
            config["published"]["vocab_size"],
            config["published"]["gqa_layers"]) == (
        48, 196608, list(range(0, 48, 4)))
    for key in ("reduced", "assumed", "deployment", "departures",
                "precision", "init", "limits", "rehearsal"):
        assert config[key], key
    assert config["serving"] == dict(
        slots=128, max_len=8960, chunk=8, n_tokens=768, max_queue=384,
        deadline=300.0, paged=False, quantize=None, temperature=0.0,
        prefill_tokens=8192, admit_tokens=8192, prompt_bucket=2048)
    bench = common.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_mix_is_the_cells(config):
    from benchmark.harness import traffic

    mix = common.load_json("benchmark/traffic/chat_long192.json")
    assert mix["clients"] == 192 == 1.5 * config["serving"]["slots"]
    assert mix["pool"] == 5 * mix["clients"]
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "lognormal", "median": 2048, "sigma": 0.7, "min": 512,
         "max": 8192},
        {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 96,
         "max": 768})
    prompts, outputs = traffic.paired_lengths(mix, mix["pool"])
    # the pool holds a pair that fills a slot to max_len, none past it
    assert (prompts + outputs).max() == config["serving"]["max_len"]
    assert mix["output_len"]["max"] == config["serving"]["n_tokens"]
    assert mix["prompt_len"]["max"] == config["serving"]["prefill_tokens"]
    assert (mix["loop"], mix["lead_in_s"], mix["trace_seconds"],
            mix["checked_requests"]) == ("closed", 22.0, 3.0, 6)


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
    # nor the two that count the prompts admitted by their dispatch (the
    # slots' fill runs the device seconds behind: 59.6% and 604.6% on a
    # v5e)
    assert "model.serve_mfu" not in plan["per_layer"]
    assert "kernel.prompt_attend_roofline.serve" not in plan["per_layer"]
    assert plan["end_to_end"] == ["serve_tokens_per_s_chip", "setup_s"]
    bench = common.load_json("BENCHMARK.json")
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
    assert CELL in [w["name"] for w in bench["workloads"]]


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


@pytest.mark.parametrize("change", [
    {"kda_allow_neg_eigval": False},
    {"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 64,
                            "num_heads": 64, "num_kv_heads": None}},
], ids=["positive_eigenvalues_only", "narrower_delta_rule_heads"])
def test_the_reference_refuses_what_the_program_does_not_serve(config,
                                                                change):
    reference = common.load_module(config["reference"])
    with pytest.raises(ValueError) as refused:
        reference.arch(dict(config, **change))
    assert next(iter(change)) in str(refused.value)


#: between what bfloat16 operands and what float8 operands read at the
#: rehearsal's widths, seeds 7-9: bfloat16 0.004-0.038, float8 0.62-1.01
#: (the cell's own limit is set on the chip, at its size)
TOY_LIMIT = 0.2


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


def _ctx(config, ops, found, slots=128):
    """A traced window of 2 s with one chunk of 8 steps dispatched
    before it and three inside it over ``slots`` occupied slots of
    2,000 positions."""
    held = {str(s): [s, 2000 + 8] for s in range(slots)}
    rows = [{"at": 99.9, "chunk": 8, "tokens_out": 0, "held": held}] + [
        {"at": 100.2 + i * 0.2, "chunk": 8, "tokens_out": 100 * (i + 1),
         "held": held} for i in range(3)]
    return {"scoped": {"slot_step_many": found}, "config": config,
            "ops": ops, "peaks": PEAKS,
            "reduced": {"window": (0.0, 2e9),
                        "trace": {"devices": {}, "spans": []}},
            "counters": {"chunk": 8, "slots": 128, "traced_from": 100.0,
                         "traced_s": 2.0, "dispatches": rows}}


def _capture(unmatched):
    """Three modules of 8 steps; in each matched one the state's kernel
    takes 5 ms a step, the convolutions 0.3, the gates 0.2, the gated
    norm 0.1. An unmatched module's ops carry no scope."""
    matched = 3 - unmatched
    ops = {
        ("attend", "attn.attend/kda.state", "kda_step.1 custom-call"):
            matched * 8 * 5.0e6,
        ("matmul", "attn.qkv/kda.conv", "fusion.1 fusion"):
            matched * 8 * 0.3e6,
        ("matmul", "attn.qkv/kda.gate", "fusion.2 fusion"):
            matched * 8 * 0.2e6,
        ("matmul", "attn.out/kda.norm", "fusion.3 fusion"):
            matched * 8 * 0.1e6,
        ("matmul", "decode.dispatch/mlp", "fusion.4 fusion"):
            matched * 8 * 6.0e6}
    if unmatched:
        ops[("unscoped", "", "fusion.1 fusion")] = unmatched * 8 * 3.0e6
    return {"modules": 3, "unmatched": unmatched, "ops": ops}


def test_the_layer_readers_over_a_made_up_capture(config, ops):
    ctx = _ctx(config, ops, _capture(0))
    assert _reader("model.decode_kda_ms.serve").read(ctx) \
        == pytest.approx(5.6)
    share = _reader("kernel.kda_state_roofline.serve").read(ctx)
    _, nbytes = ops.kda_state(config, 128)
    assert share == pytest.approx(100 * nbytes / 819e9 / 5e-3)
    assert 0 < share <= 100.0
    # an unmatched module reads what the matched ones give
    partly = _reader("kernel.kda_state_roofline.serve").read(
        _ctx(config, ops, _capture(1)))
    assert partly == pytest.approx(share)


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
