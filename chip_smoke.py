#!/usr/bin/env python3
"""chip_smoke.py: train and serve end to end on the TPU, once.

The quickest proof that the system still starts on the chip. It drives
the two main paths through the entry points a user calls — training
through the CLI (``python -m veles_tpu <workflow> <config>`` →
``Launcher`` → fused tick) and serving through ``GenerateAPI`` over
HTTP — at the full width of models the repo supports, with weights and
data generated from a seed, and checks what comes out by the repo's
own means (losses finite and falling, tokens in range, health and
metrics surfaces clean, the fused paged kernel agreeing with the
gather path).

Run it on the TPU:  python chip_smoke.py
It refuses any other platform; there is no CPU mode and no switch that
adds one. CPU debugging goes through the phase functions below, which
``tests/test_chip_smoke.py`` calls at toy widths.

One process owns the chip at a time, so this parent never imports
``jax`` or ``veles_tpu``: every phase is a sequential child
(``chip_smoke.py --phase NAME``) with a timeout, and a child's non-zero
exit, timeout or missing result line fails the run. The children share
one compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache`` (``veles_tpu/core/config.py``).

Each phase prints one JSON line naming the device it ran on, then comes
a ``{"summary": ...}`` line (versions, cache, phases, ``"claim":
null``), and the LAST line of stdout is the verdict and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as JAX reports it — exactly those keys. A phase that
fails makes it ``"ok": false`` with exit 1; when no TPU is found there
is no verdict line at all. Timings in the lines are smoke timings
(first dispatch cold, then warm), never metrics. Children's full output
lands in ``chiprun_out/chip_smoke/``.
"""

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: the contract's wall limit is 1200 s, compilation included
DEADLINE_S = 1150.0


# -- what every line says about the device ---------------------------------

def device_facts():
    """Platform, kind and count as JAX reports them, the versions of the
    one installation there is, where the compile cache lives, and
    whether anything from outside the tree was picked up (a site
    config: on a fresh machine, none)."""
    from importlib import metadata

    import jax
    import jaxlib

    from veles_tpu.core.config import site_config_paths

    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "site_config": any(os.path.exists(p)
                           for p in site_config_paths()),
    }


def check(cond, what, *args):
    """A failed check fails the phase, saying what was checked."""
    if not cond:
        raise AssertionError(what % args if args else what)


# -- training phases -----------------------------------------------------------

def train_phase(model, mesh=None, overrides=()):
    """One ``BASELINE.json`` workflow through the CLI's own ``Main``
    (what ``python -m veles_tpu`` runs): the synthetic twin
    ``samples/synthetic_twins.py`` at full width unless ``overrides``
    (``root.synthetic.*=...`` CLI strings) shrink it. ``mesh`` is a
    ``--mesh`` spec. Checks: exit 0, every epoch recorded, losses
    finite, last-epoch train loss below the first; under a mesh, the
    launcher's pod-mode line and the parameters laid out over that
    many distinct devices."""
    import logging
    import math
    import tempfile

    from veles_tpu.__main__ import Main

    epochs = []        # (wall, epoch, class, loss), from DecisionGD
    pod_lines = []

    class Tap(logging.Handler):
        def emit(self, record):
            msg = str(record.msg)
            if msg.startswith("epoch %d %s: errors"):
                epochs.append((record.created, record.args[0],
                               record.args[1], float(record.args[-1])))
            elif msg.startswith("pod mode: mesh"):
                pod_lines.append(record.getMessage())

    tap = Tap()
    logging.getLogger().addHandler(tap)
    fd, result_file = tempfile.mkstemp(suffix=".json", prefix="smoke_")
    os.close(fd)
    argv = [os.path.join(HERE, "samples", "synthetic_twins.py"), "-",
            "root.synthetic.model=%s" % model, *overrides,
            "--seed", "1234", "--result-file", result_file]
    if mesh:
        argv += ["--mesh", mesh]
    started = time.time()
    try:
        main = Main()
        returncode = main.run(argv)
        finished = time.time()
        with open(result_file) as fin:
            results = json.load(fin)
    finally:
        logging.getLogger().removeHandler(tap)
        os.unlink(result_file)
    check(not returncode, "CLI exit status %r", returncode)
    train = [(wall, epoch, loss) for wall, epoch, klass, loss in epochs
             if klass == "train"]
    check(len(train) >= 2 and len(train) == results["epochs"],
          "epochs recorded: %d train summaries, result file says %r",
          len(train), results.get("epochs"))
    check(all(math.isfinite(loss) for _, _, _, loss in epochs),
          "non-finite loss in %r", epochs)
    check(train[-1][2] < train[0][2],
          "train loss did not fall: first %r, last %r",
          train[0][2], train[-1][2])
    out = {
        "ran": {"epochs": len(train),
                "samples": results.get("total_samples")},
        "train_loss": [round(loss, 6) for _, _, loss in train],
        # start -> first epoch summary (data, upload, compile, epoch
        # 0), then that summary -> the end of the run; the pipelined
        # engine reports epochs late and in bursts, so neither is an
        # epoch time
        "smoke_timing_first_dispatch_s":
            round(train[0][0] - started, 3),
        "smoke_timing_warm_s": round(finished - train[0][0], 3),
    }
    if mesh:
        n_devices = main.workflow.mesh_.devices.size
        check(pod_lines and "over %d devices" % n_devices
              in pod_lines[0], "no pod-mode line: %r", pod_lines)
        # the tick shards each minibatch's INDICES over the data axis
        # and gathers from the dataset inside the program, so the
        # parameters are the state to look at; where the dataset sits
        # is reported, not required
        placed = _device_sets(
            {"params": main.workflow.fused_tick._params_,
             "dataset": main.workflow.loader.original_data.data})
        check(placed["params"] == n_devices,
              "parameters on %d distinct devices, mesh has %d",
              placed["params"], n_devices)
        out.update(pod_mode=pod_lines[0], devices_holding=placed)
    return out


def _device_sets(trees):
    """{name: number of distinct devices its leaves' shards sit on}."""
    import jax

    out = {}
    for name, tree in trees.items():
        devices = set()
        for leaf in jax.tree.leaves(tree):
            devices |= set(leaf.sharding.device_set)
        out[name] = len(devices)
    return out


# -- serving phases ------------------------------------------------------------

def serve_phase(quantize=None, paged=False, mesh=None, blocks=4,
                embed=1024, heads=16, vocab=32768, slots=8, prompt=512,
                n_tokens=64, chunk=64, n_requests=16, clients=8,
                page_size=128, seed=0):
    """``GenerateAPI(...).start()`` over HTTP at the serving shape of
    record (e1024/h16/v32768, 8 slots x 512-token prompts; depth and
    the random weights are the only cuts): two waves of ``n_requests``
    POSTs from ``clients`` threads, so the second half of a wave
    admits mid-flight. The first wave pays the compiles, the second (fresh
    prompts, same shapes) is the warm figure. ``paged`` and ``mesh`` go
    through ``root.common.serve`` — the ``--serve-paged`` /
    ``--serve-mesh`` landing spots — and the paged tier adds one prompt
    sent again (hit) and one sharing a 3/4 prefix (tail).

    Checks: every answer 200 with ``n_tokens`` tokens in [0, vocab);
    ``/healthz`` ready with zero breaker trips, shed requests and swap
    failures (a healed breaker is not a pass); ``/metrics`` carrying
    the admit and dispatch compile counters and, on the TPU, the
    allocator's ``bytes_limit`` rather than the live-buffer fallback."""
    import threading
    import urllib.request

    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu.core.config import root
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI

    root.common.serve.paged = bool(paged)
    root.common.serve.page_size = page_size if paged else None
    root.common.serve.mesh = mesh
    rng = numpy.random.RandomState(seed)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02).astype(jnp.bfloat16)
    api = GenerateAPI(params, table, heads, slots=slots,
                      max_len=prompt + n_tokens + 2 * chunk,
                      n_tokens=n_tokens, chunk=chunk, quantize=quantize,
                      port=0).start()
    base = "http://127.0.0.1:%d" % api.port

    def post(tokens):
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": tokens,
                             "n_tokens": n_tokens}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            return resp.status, json.loads(resp.read().decode())

    def get(path):
        with urllib.request.urlopen(base + path, timeout=60) as resp:
            return resp.read().decode()

    def answered(status, body):
        tokens = body.get("tokens", ())
        check(status == 200 and len(tokens) == n_tokens
              and all(0 <= t < vocab for t in tokens),
              "bad answer: status %r, %d tokens", status, len(tokens))

    def wave():
        prompts = [rng.randint(0, vocab, prompt).tolist()
                   for _ in range(n_requests)]
        answers, errors = [], []

        def client(mine):
            try:
                answers.extend(post(p) for p in mine)
            except Exception as exc:  # reported below, never swallowed
                errors.append(repr(exc))

        threads = [threading.Thread(target=client,
                                    args=(prompts[i::clients],))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=900)
        seconds = time.perf_counter() - t0
        check(not errors and len(answers) == n_requests,
              "wave: %d/%d answers, errors %r", len(answers),
              n_requests, errors)
        for status, body in answers:
            answered(status, body)
        return seconds, prompts

    try:
        first_s, prompts = wave()
        warm_s, _ = wave()
        requests = 2 * n_requests
        if paged:
            again = prompts[0]
            tail = again[:prompt * 3 // 4] + rng.randint(
                0, vocab, prompt - prompt * 3 // 4).tolist()
            for tokens in (again, again, tail):
                answered(*post(tokens))
            requests += 3
        health = json.loads(get("/healthz"))
        metrics = get("/metrics")
        counts = dict(api.decoder.dispatch_counts)
        placed = _device_sets({"kv_state": {
            "k": api.decoder.state["k"], "v": api.decoder.state["v"]}})
        formulation = ("dense slab" if not paged else
                       "pallas kernel" if api.decoder.paged_kernel
                       else "gather")
    finally:
        api.stop()
    counters = health["counters"]
    check(health["ready"] and health["breaker"] == "closed",
          "not ready: %r", health)
    for name in ("trips", "shed", "swap_failures", "errors"):
        check(not counters.get(name, 0), "%s = %r in %r", name,
              counters.get(name), counters)
    family = "paged" if paged else "decode"
    for program in (family + ".admit", family + ".dispatch"):
        check('veles_xla_compiles_total{program="%s"}' % program
              in metrics, "no compile counter for %s", program)
    on_tpu = jax.devices()[0].platform == "tpu"
    check("veles_device_memory_bytes" in metrics
          and ('kind="bytes_limit"' in metrics) == on_tpu,
          "device memory gauge: allocator bytes_limit must be present "
          "on the TPU and only there")
    if paged:
        for name in ("admit", "admit_hit", "admit_tail"):
            check(counts.get(name, 0) >= 1, "no %s booked: %r", name,
                  counts)
    if mesh:
        n_devices = api.decoder.mesh.devices.size
        check("veles_mesh_devices %d" % n_devices in metrics,
              "veles_mesh_devices %d not on /metrics", n_devices)
        check(placed["kv_state"] == n_devices,
              "KV state on %r devices, mesh has %d", placed, n_devices)
    return {
        "ran": {"requests": requests, "tokens": requests * n_tokens,
                "prompt_tokens": prompt, "clients": clients},
        "config": "s%d_p%d_b%d_c%d_e%d_h%d_L%d_v%d" % (
            slots, prompt, n_tokens, chunk, embed, heads, blocks, vocab),
        "quantize": quantize, "attend": formulation,
        "dispatch_counts": counts, "devices_holding": placed,
        "smoke_timing_first_dispatch_s": round(first_s, 3),
        "smoke_timing_warm_s": round(warm_s, 3),
    }


def paged_kernel_agreement(heads=16, head_dims=(64, 128), page_size=128,
                           slots=8, pages_per_slot=6, seed=0):
    """The fused paged kernel against the gather path it replaces
    (``_gather_block_float``/``_cache_attend`` and
    ``_gather_block_int8``/``int8_cache_attend``) on random pools with
    ragged lengths, at the serving head shapes. Max abs error must stay
    within 2e-2 for bf16 and int8 pools and 1e-4 for f32 — the tiers
    round differently, so streams are not compared, outputs are."""
    import jax.numpy as jnp
    import numpy

    from veles_tpu.ops import paged_attention as pgatt
    from veles_tpu.ops.quant import int8_cache_attend
    from veles_tpu.parallel import kv_pool
    from veles_tpu.parallel.decode import _cache_attend, _positions_last

    rng = numpy.random.RandomState(seed)
    pool_pages = slots * pages_per_slot + 1
    span = pages_per_slot * page_size
    # every slot its own pages in shuffled physical order, scratch page
    # 0 behind the live ones; lengths from 0 up to the full span
    page_table = 1 + rng.permutation(pool_pages - 1).reshape(
        slots, pages_per_slot).astype(numpy.int32)
    lengths = numpy.linspace(0, span - 1, slots).astype(numpy.int32)
    for s in range(slots):
        page_table[s, lengths[s] // page_size + 1:] = kv_pool.SCRATCH_PAGE
    page_table, lengths = jnp.asarray(page_table), jnp.asarray(lengths)
    visible = jnp.arange(span)[None, :] <= lengths[:, None]
    errors = {}
    for head_dim in head_dims:
        kv_shape = (1, pool_pages, page_size, heads, head_dim)
        for dtype, bound in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
            q = jnp.asarray(rng.randn(slots, heads, head_dim), dtype)
            pool = {"k": jnp.asarray(rng.randn(*kv_shape), dtype),
                    "v": jnp.asarray(rng.randn(*kv_shape), dtype)}
            k_g, v_g = kv_pool._gather_block_float(pool, 0, page_table)
            want = _cache_attend(q[:, None], _positions_last(k_g),
                                 _positions_last(v_g),
                                 visible[:, None, None, :])[:, 0]
            got = pgatt.paged_attend(q, pool["k"][0], pool["v"][0],
                                     page_table, lengths,
                                     page_size=page_size)
            errors["%s_d%d" % (jnp.dtype(dtype).name, head_dim)] = (
                float(jnp.max(jnp.abs(got - want))), bound)
        q8_shape = (1, pool_pages, heads, head_dim, page_size)
        q = jnp.asarray(rng.randn(slots, heads, head_dim)
                        * head_dim ** -0.5, jnp.bfloat16)
        pool = {name: jnp.asarray(rng.randint(-127, 128, q8_shape),
                                  jnp.int8) for name in ("k", "v")}
        for name in ("k_scale", "v_scale"):
            pool[name] = jnp.asarray(
                rng.rand(1, pool_pages, heads, page_size) / 64.0,
                jnp.float32)
        want = int8_cache_attend(
            q[:, None], *kv_pool._gather_block_int8(pool, 0, page_table),
            jnp.where(visible, 0.0, -1e30).astype(jnp.float32))[:, 0]
        got = pgatt.paged_attend_int8(
            q, pool["k"][0], pool["k_scale"][0], pool["v"][0],
            pool["v_scale"][0], page_table, lengths,
            page_size=page_size)
        errors["int8_d%d" % head_dim] = (
            float(jnp.max(jnp.abs(got - want))), 2e-2)
    for name, (err, bound) in errors.items():
        check(err <= bound, "paged kernel vs gather, %s: max abs error "
              "%.3g > %.3g", name, err, bound)
    return {name: float("%.3g" % err) for name, (err, _) in errors.items()}


# -- the children ----------------------------------------------------------------

def _serve_paged():
    agreement = paged_kernel_agreement()
    return dict(serve_phase(paged=True),
                kernel_vs_gather_max_abs_err=agreement)


#: child -> (timeout seconds, ((phase line, what produces it), ...)):
#: ONE table for the parent (which lines to expect) and the child (what
#: to run). The int8 tier rides serve_dense's process: same traffic
#: through the one other Pallas kernel on a default TPU path, the int8
#: matvec.
CHILDREN = {
    "probe": (180, (("probe", dict),)),
    "train_mnist784": (300, (
        ("train_mnist784", lambda: train_phase("mnist784")),)),
    "train_alexnet": (420, (
        ("train_alexnet", lambda: train_phase("alexnet")),)),
    "serve_dense": (540, (
        ("serve_dense", serve_phase),
        ("serve_int8", lambda: serve_phase(quantize="int8", seed=1)))),
    "serve_paged": (420, (("serve_paged", _serve_paged),)),
    "train_mesh4": (300, (
        ("train_mesh4",
         lambda: train_phase("mnist784", mesh="data=4")),)),
    "serve_mesh4": (420, (
        ("serve_mesh4", lambda: serve_phase(mesh="model=4")),)),
}
SINGLE_CHIP = ("train_mnist784", "train_alexnet", "serve_dense",
               "serve_paged")
MULTI_CHIP = ("train_mesh4", "serve_mesh4")


def child_main(name):
    """Entry of ``chip_smoke.py --phase NAME``: refuse anything but the
    TPU, run the phase, print its line(s)."""
    sys.path.insert(0, HERE)
    facts = device_facts()
    if facts["platform"] != "tpu":
        print("chip_smoke: JAX platform is %r, not 'tpu' — refusing to "
              "run (there is no CPU mode; CPU debugging goes through "
              "tests/test_chip_smoke.py)" % facts["platform"],
              file=sys.stderr)
        return 2
    for phase, run in CHILDREN[name][1]:
        print(json.dumps(dict({"phase": phase, "ok": True}, **facts,
                              **run())), flush=True)
    return 0


# -- the parent (never imports jax or veles_tpu) ---------------------------------

def run_child(name, timeout, log):
    """Run one child to the end, keep its output under OUT_DIR, and
    return the phase lines ``CHILDREN`` says it prints. Raises on a
    non-zero exit, a timeout or a missing line; the child's whole
    process group is stopped either way."""
    expect = [phase for phase, _ in CHILDREN[name][1]]
    out_path = os.path.join(OUT_DIR, name + ".stdout")
    err_path = os.path.join(OUT_DIR, name + ".stderr")
    started = time.perf_counter()
    with open(out_path, "wb") as out, open(err_path, "wb") as err:
        # run artifacts (incidents, black boxes) land beside the logs,
        # inside the tree, so they come back with chiprun_out/
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", name],
            cwd=HERE, stdout=out, stderr=err, start_new_session=True,
            env=dict(os.environ,
                     VELES_TPU_HOME=os.path.join(OUT_DIR, "home")))
        try:
            returncode = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            returncode = None
        finally:
            try:
                os.killpg(proc.pid, 9)
            except ProcessLookupError:
                pass
            proc.wait()
    seconds = time.perf_counter() - started
    with open(err_path, errors="replace") as fin:
        err_tail = fin.read()[-3000:]
    lines = {}
    with open(out_path, errors="replace") as fin:
        for line in fin:
            if line.startswith('{"phase"'):
                row = json.loads(line)
                lines[row["phase"]] = row
    passed = [lines[phase] for phase in expect
              if lines.get(phase, {}).get("ok")
              and lines[phase].get("platform") == "tpu"]
    for row in passed:  # what did pass is said even if a later line failed
        row["smoke_timing_process_s"] = round(seconds, 1)
        log(json.dumps(row))
    if returncode != 0:
        raise RuntimeError(
            "phase child %s %s after %.0f s; the end of its stderr:\n%s"
            % (name, "timed out" if returncode is None
               else "exited %d" % returncode, seconds, err_tail))
    if len(passed) != len(expect):
        raise RuntimeError(
            "phase child %s printed no passing line on the tpu for %s; "
            "the end of its stderr:\n%s"
            % (name, sorted(set(expect) - {r["phase"] for r in passed}),
               err_tail))
    return passed


def main():
    os.makedirs(OUT_DIR, exist_ok=True)
    started = time.perf_counter()

    def log(text):
        print(text, flush=True)

    def remaining(cap):
        return max(1.0, min(cap, DEADLINE_S
                            - (time.perf_counter() - started)))

    try:
        probe, = run_child("probe", remaining(CHILDREN["probe"][0]), log)
    except RuntimeError as exc:
        print("chip_smoke: FAILED: %s" % exc, file=sys.stderr)
        return 1
    # the verdict names the device as JAX reported it to the probe
    device = {"platform": probe["platform"],
              "kind": probe["device_kind"],
              "count": probe["device_count"]}
    phases, failures = {}, []
    plan = list(SINGLE_CHIP)
    if probe["device_count"] >= 4:
        plan += MULTI_CHIP
    else:
        log("multichip: skipped, %d device(s)" % probe["device_count"])
    for name in plan:
        # a failed phase does not stop the others: one run names every
        # phase that fails; the deadline still bounds the whole
        try:
            for row in run_child(name, remaining(CHILDREN[name][0]),
                                 log):
                phases[row["phase"]] = "passed"
        except RuntimeError as exc:
            failures.append(str(exc))
    if failures:
        print("chip_smoke: FAILED (%d of %d children):\n%s"
              % (len(failures), len(plan), "\n\n".join(failures)),
              file=sys.stderr)
    log(json.dumps({
        "summary": "chip_smoke",
        "versions": {key: probe[key]
                     for key in ("jax", "jaxlib", "libtpu")},
        "compile_cache": probe["compile_cache"],
        "phases": phases,
        "failed_children": len(failures),
        "smoke_timing_total_s": round(time.perf_counter() - started, 1),
        "claim": None}))
    # the last line: the verdict, these two keys and no others
    log(json.dumps({"ok": not failures, "device": device}))
    return 1 if failures else 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--phase" \
            and sys.argv[2] in CHILDREN:
        sys.exit(child_main(sys.argv[2]))
    if len(sys.argv) != 1:
        sys.exit("usage: python chip_smoke.py   (no options; "
                 "--phase NAME is the internal child entry)")
    sys.exit(main())
