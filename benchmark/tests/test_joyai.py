"""The ``joyai-llm-flash`` configuration off the chip: the operation
and byte counts against their hand counts, the catalog's every number
in the file, ``--plan`` and ``--rehearse`` of its cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import common

ROOT = common.ROOT
CELL = "joyai-llm-flash.serve_closed48"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmark/configs/joyai-llm-flash.json")) as fin:
        return json.load(fin)


@pytest.fixture(scope="module")
def ops():
    return common.load_module("benchmark/ops/joyai.py")


def test_hand_counts(config, ops):
    assert ops.attention_parameters(config) \
        == ops.HAND_ATTENTION_PARAMETERS == 26345472
    assert ops.expert_parameters(config) \
        == ops.HAND_EXPERT_PARAMETERS == 3 * 2048 * 768
    assert ops.outside_experts(config) == ops.HAND_OUTSIDE_EXPERTS \
        == 70385664 + 4 * 31588352 + 2048 * 129280
    assert ops.outside_experts(config) + ops.routed_per_token(config) \
        == ops.HAND_PER_TOKEN == 612499456
    assert ops.row_bytes_per_position(config) \
        == ops.HAND_ROW_BYTES_PER_POSITION == 5 * 576 * 2
    assert round(ops.expected_touched(config, 32), 1) == 163.3
    # every weight of the cut, embedding included, is the issue's 10.35 GiB
    whole = (ops.outside_experts(config) + 2048 * 129280
             + 4 * 256 * ops.expert_parameters(config))
    assert whole == 5558108160


def test_a_decode_step_and_a_prefill(config, ops):
    step_ops, step_bytes = ops.decode_step(config, [599] * 32)
    assert step_ops == 2 * 612499456 * 32 \
        + 2 * 5 * 32 * (2 * 512 + 64) * 600 * 32
    touched = ops.expected_touched(config, 32)
    assert step_bytes == pytest.approx(
        461504512 * 2 + 4 * touched * 4718592 * 2 + 5760 * 600 * 32)
    # the touched experts are over four fifths of a step's bytes
    assert 4 * touched * 4718592 * 2 / step_bytes > 0.8
    assert ops.prefill(config, [256]) == (
        2 * (612499456 - 2048 * 129280) * 256
        + 2 * 5 * 32 * (192 + 128) * 256 * 257 // 2
        + 2 * 2048 * 129280)
    n_ops, nbytes = ops.expert_products(config, 256, 163)
    assert n_ops == 2 * 4718592 * 256
    assert nbytes == (4718592 * 163 + 2 * 2048 * 256) * 2


def test_the_file_holds_every_number_of_the_catalog(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fin:
        row = next(r for r in map(json.loads, fin)
                   if r["name"] == "JoyAI-LLM-Flash")
    assert config["source"] == row["source_url"]
    differ = [key for key, value in row["config"].items()
              if config.get(key) != value]
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 5


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
    for name in ("model.decode_moe_experts_ms.serve",
                 "model.decode_moe_route_ms.serve",
                 "model.decode_latent_ms.serve",
                 "kernel.moe_experts_roofline.serve",
                 "scheduler.moe_load_max_over_mean.serve",
                 "model.serve_mfu", "kernel.decode_step_roofline.serve"):
        assert name in plan["per_layer"]
    assert "scheduler.slot_occupancy.serve" not in plan["per_layer"]
    assert plan["end_to_end"] == ["serve_tokens_per_s_chip", "setup_s"]


def test_rehearsal_serves_and_compares():
    done = run("--rehearse", "--seed", "3000000007", "--seconds", "4",
               "--trace", "1")
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["counters"]["compiles_in_window"] == 0
    compared = line["compared"]
    assert compared["requests_failed_or_unanswered"]["value"] == 0
    # a float32 reference against a bfloat16 program at toy widths:
    # the served token is the reference's first or near it (the limit
    # is set at the cell's own size, on the chip; this is the walk
    # through the comparison, not its calibration)
    assert 0.0 <= compared["served_logit_gap"]["value"] < 1.0


def _reader(name):
    return common.load_module("benchmark/metrics/%s.py" % name)


def _ctx(config, ops, found, books):
    """A run's context as the readers see it: one traced chunk of 8
    steps with 32 live slots, ``found`` what ``scopes.scoped`` kept."""
    return {
        "scoped": {"slot_step_many": found}, "config": config, "ops": ops,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "counters": {
            "chunk": 8, "slots": 32, "traced_from": 10.0, "traced_s": 2.0,
            "health_counters": books,
            "dispatches": [{"at": 10.5, "chunk": 8, "tokens_out": 0,
                            "held": {str(s): [s, 300] for s in range(32)}}]}}


def test_the_new_readers_over_a_made_up_classification(config, ops):
    """The grouped kernel counts as the experts' product wherever its
    adopted scope put it; the roofline takes the program's own count
    at the traced chunk's number of live slots."""
    found = {"modules": 1, "unmatched": 0, "ops": {
        ("matmul", "moe.experts/silu", "ragged-dot-none custom-call"): 24e6,
        ("matmul", "mlp/moe.combine", "ragged-dot-none.2 custom-call"): 16e6,
        ("matmul", "mlp/moe.combine", "fusion.3 fusion"): 0.8e6,
        ("matmul", "moe.dispatch/argsort", "sort.1 sort"): 0.8e6,
        ("matmul", "attn.qkv/mla.q", "fusion.9 fusion"): 1.6e6,
        ("attend", "attn.attend/mla.absorb", "fusion.7 fusion"): 0.8e6,
        ("attend", "decode.dispatch/attn.attend", "fusion.8 fusion"): 4e6}}
    books = {"moe_load_max_over_mean": 2.5,
             "moe_by_lanes": {"1": [4000, 32000, 32000],
                              "32": [400, 102400, 64000]}}
    ctx = _ctx(config, ops, found, books)
    assert _reader("model.decode_moe_experts_ms.serve").read(ctx) \
        == pytest.approx(5.0)
    assert _reader("model.decode_moe_route_ms.serve").read(ctx) \
        == pytest.approx(0.2)
    assert _reader("model.decode_latent_ms.serve").read(ctx) \
        == pytest.approx(0.3)
    assert _reader("scheduler.moe_load_max_over_mean.serve").read(ctx) \
        == 2.5
    # 256 assignments over 160 experts a block-step, 4 expert blocks
    n_ops, nbytes = ops.expert_products(config, 256, 160)
    least = 4 * max(n_ops / 197e12, nbytes / 819e9)
    assert nbytes / 819e9 > n_ops / 197e12
    assert _reader("kernel.moe_experts_roofline.serve").read(ctx) \
        == pytest.approx(100 * least / 5e-3)


def test_a_program_without_the_scopes_or_the_books_reads_nothing(
        config, ops):
    found = {"modules": 1, "unmatched": 0, "ops": {
        ("matmul", "decode.dispatch/mlp", "fusion.3 fusion"): 8e6}}
    ctx = _ctx(config, ops, found, {"admitted": 10})
    for name in ("model.decode_moe_experts_ms.serve",
                 "model.decode_moe_route_ms.serve",
                 "model.decode_latent_ms.serve",
                 "kernel.moe_experts_roofline.serve",
                 "scheduler.moe_load_max_over_mean.serve"):
        assert _reader(name).read(ctx) is None
    ctx["scoped"]["slot_step_many"] = None
    assert _reader("model.decode_latent_ms.serve").read(ctx) is None
