"""The harness is driven by data: a cell, a configuration, a traffic
mix and a per-layer metric that a later PR adds as files of their own
are found by name, with no edit to a file that is there. And what
``BENCHMARK.json`` says of each metric is what its reader's file says.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import common, traffic

ROOT = common.ROOT
BENCH = common.load_json("BENCHMARK.json")


def test_every_metric_has_its_reader():
    run = common.load_module("benchmark/run.py", "benchmark_run")
    used = set()
    for metric in BENCH["per_layer"]:
        path = run.reader_file(metric["name"])
        used.add(os.path.basename(path))
        reader = common.load_module(path)
        # a reader shared by x.train and x.serve states no MOVES
        assert (reader.LAYER, getattr(reader, "MOVES", metric["moves"]),
                reader.UNIT, reader.SOURCE) == (
            metric["layer"], metric["moves"], metric["unit"],
            metric["source"])
        assert callable(reader.read)
    on_disk = {name for name in
               os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
               if name.endswith(".py")}
    assert used == on_disk


def test_every_cell_resolves_to_files_that_exist():
    for cell in BENCH["workloads"]:
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload",
             cell["name"], "--plan"], cwd=ROOT, capture_output=True,
            text=True, check=True)
        plan = json.loads(out.stdout.splitlines()[-1])
        for key in ("config_file", "traffic_file", "driver",
                    "reference", "ops"):
            assert os.path.exists(os.path.join(ROOT, plan[key])), key
        assert "setup_s" in plan["end_to_end"]
        assert len(plan["end_to_end"]) >= 2 and plan["per_layer"]


def test_a_later_pr_adds_a_cell_as_files_only(tmp_path):
    """Copy the benchmark, ADD a configuration, a mix, a metric reader
    and their entries (no file that is there is edited), and the one
    command picks them up by name."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = common.load_json("benchmark/configs/alexnet-227.json")
    config["name"] = "dummy-net"
    (tmp_path / "benchmark/configs/dummy-net.json").write_text(
        json.dumps(config))
    mix = common.load_json("benchmark/traffic/fullbatch_mb128.json")
    mix.update(name="fullbatch_mb64", minibatch=64)
    (tmp_path / "benchmark/traffic/fullbatch_mb64.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/metrics/dummy.answer.py").write_text(
        "LAYER = 'Device (XLA on the v5e)'\n"
        "MOVES = 'train_images_per_s_chip'\nUNIT = 'count'\n"
        "SOURCE = 'program_counter'\n\n\n"
        "def read(ctx):\n    return ctx['traffic']['minibatch']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="dummy-net",
                                 file="benchmark/configs/dummy-net.json"))
    bench["workloads"].append({
        "name": "dummy-net.train_mb64", "config": "dummy-net",
        "traffic": "fullbatch_mb64", "chips": 1, "why": "test"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_images_per_s_chip":
            metric["workloads"].append("dummy-net.train_mb64")
    bench["per_layer"].append({
        "name": "dummy.answer", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Device (XLA on the v5e)",
        "moves": "train_images_per_s_chip",
        "workloads": ["dummy-net.train_mb64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "dummy-net.train_mb64", "--plan"], cwd=tmp_path,
        capture_output=True, text=True, check=True)
    plan = json.loads(out.stdout.splitlines()[-1])
    assert plan["config_file"] == "benchmark/configs/dummy-net.json"
    assert plan["traffic_file"] == "benchmark/traffic/fullbatch_mb64.json"
    assert plan["per_layer"] == {
        "dummy.answer": "benchmark/metrics/dummy.answer.py"}
    assert plan["end_to_end"] == ["train_images_per_s_chip", "setup_s"]
    sys.path.insert(0, str(tmp_path))
    try:
        reader = common.load_module(
            str(tmp_path / "benchmark/metrics/dummy.answer.py"),
            "dummy_answer_reader")
    finally:
        sys.path.remove(str(tmp_path))
    assert reader.read({"traffic": mix}) == 64


def test_no_result_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files
    under ``paths`` the command exits non-zero and prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_a_run_without_a_tpu_prints_no_result():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 2
    assert '"correct"' not in out.stdout
    assert "TPU" in out.stderr


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = common.load_json("benchmark/traffic/chat_closed24.json")
    plans = [traffic.request_plan(mix, seed, 50257, 10.0)
             for seed in (3, 3000000019)]
    sizes = [sorted((len(r["tokens"]), r["n_tokens"])
                    for r in plan["requests"]) for plan in plans]
    assert sizes[0] == sizes[1]
    assert [r["n_tokens"] for r in plans[0]["requests"]] \
        != [r["n_tokens"] for r in plans[1]["requests"]]
    lengths = [n for n, _ in sizes[0]]
    assert min(lengths) >= 65 and max(lengths) <= 832
    assert all(16 <= n <= 192 for _, n in sizes[0])
    again = traffic.request_plan(mix, 3, 50257, 10.0)
    assert again["requests"] == plans[0]["requests"]


def test_an_open_loop_puts_the_same_work_in_every_window():
    """The generator's open loop (no cell uses it yet: PERF.md, Open
    questions) on a mix as a later PR would add it."""
    closed = common.load_json("benchmark/traffic/chat_closed24.json")
    mix = {"generator": "requests", "loop": "open", "rate_per_s": 9.0,
           "pairing_seed": 26, "prompt_len": closed["prompt_len"],
           "output_len": closed["output_len"], "lead_in_s": 8.0}
    lead_in, seconds = mix["lead_in_s"], 30.0
    inside = []
    for seed in (3, 3000000019):
        plan = traffic.request_plan(mix, seed, 50257, seconds)
        due = [r["due_s"] for r in plan["requests"]]
        assert due == sorted(due) and due[-1] < lead_in + seconds
        inside.append([r for r in plan["requests"]
                       if r["due_s"] >= lead_in])
    sizes = [sorted((len(r["tokens"]), r["n_tokens"]) for r in part)
             for part in inside]
    assert len(sizes[0]) == round(mix["rate_per_s"] * seconds)
    assert sizes[0] == sizes[1]
    gaps = [sorted(round(b["due_s"] - a["due_s"], 9)
                   for a, b in zip(part, part[1:])) for part in inside]
    assert gaps[0] != [] and [r["due_s"] for r in inside[0]] \
        != [r["due_s"] for r in inside[1]]


def serving_context(dispatches, programs):
    """What the serving readers get: the decoder's dispatches, a trace
    with ``slot_step_many`` modules, a traced window of 2 s from 100."""
    config = common.load_json("benchmark/configs/gpt2-medium.json")
    window = (0.0, 2e9)
    return {
        "config": config, "peaks": common.peaks_for("TPU v5 lite"),
        "ops": common.load_module(config["ops"]),
        "counters": {"traced_from": 100.0, "traced_s": 2.0, "slots": 16,
                     "chunk": 8, "dispatches": dispatches},
        "reduced": {"window": window, "window_s": 2.0, "trace": {
            "devices": {0: {"ops": [], "modules": programs}},
            "spans": []}}}


def test_the_decode_roofline_reads_the_decoders_own_slots():
    """Two slots that hold 100 and 300 positions at a chunk's first
    step: a step reads the weights once and the K/V of the positions
    cached at the chunk's mean step (3.5 more) plus the new one, by
    hand 706,906,112 + 98,304 x (104.5 + 304.5) bytes; at 819 GB/s
    that is 0.9122 ms, 9.122% of a 10 ms step. Requests that wait for
    a slot are in nobody's books here."""
    held = {"0": [7, 108], "1": [9, 308]}
    dispatches = [
        {"at": 99.0, "chunk": 8, "tokens_out": 0,
         "held": {"0": [7, 100], "1": [5, 300]}},
        {"at": 100.5, "chunk": 8, "tokens_out": 10, "held": held},
        {"at": 103.0, "chunk": 8, "tokens_out": 26,
         "held": {"0": [7, 116], "1": [9, 316]}}]
    programs = [("jit_slot_step_many(1)", 1e8, 8e7)]   # 8 steps, 80 ms
    ctx = serving_context(dispatches, programs)
    from benchmark.harness import readings

    chunks = readings.chunks_in(ctx)
    # slot 1 changed hands since the chunk before: a prompt of 300
    assert chunks == [{"steps": 8, "lengths": [100, 300],
                       "admitted": [300], "tokens_out": 10}]
    roofline = common.load_module(
        "benchmark/metrics/kernel.decode_step_roofline.serve.py")
    nbytes = 706906112 + 98304 * (104.5 + 304.5)
    assert roofline.read(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.010, rel=1e-9)
    assert roofline.read(ctx) == pytest.approx(9.122, rel=1e-3)
    ctx["counters"]["dispatches"][1]["held"] = {
        str(slot): [slot, 50] for slot in range(17)}
    with pytest.raises(RuntimeError):
        roofline.read(ctx)


def test_serve_mfu_counts_the_tokens_the_decoder_delivered():
    """Two chunks of 8 steps over 2 slots inside the window, 12 of the
    first chunk's 16 lane-steps delivered as answer tokens: the
    decode operations count at 12/16, the admitted prompt in full."""
    dispatches = [
        {"at": 99.0, "chunk": 8, "tokens_out": 0,
         "held": {"0": [7, 100], "1": [5, 300]}},
        {"at": 100.5, "chunk": 8, "tokens_out": 10,
         "held": {"0": [7, 108], "1": [9, 308]}},
        {"at": 101.5, "chunk": 8, "tokens_out": 22,
         "held": {"0": [7, 116], "1": [9, 316]}}]
    ctx = serving_context(dispatches, [])
    ops = ctx["ops"]
    config = ctx["config"]
    first, _ = ops.decode_step(config, [103.5, 303.5])
    second, _ = ops.decode_step(config, [111.5, 311.5])
    want = (ops.prefill(config, [300])
            + 8 * (first + second) * 12 / 16) / 2.0 / 197e12
    reader = common.load_module("benchmark/metrics/model.serve_mfu.py")
    assert reader.read(ctx) == pytest.approx(100.0 * want, rel=1e-9)
