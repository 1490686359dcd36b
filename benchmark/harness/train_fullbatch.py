"""Driver for configurations of kind ``train_fullbatch``: a
``StandardWorkflow`` model trained through the CLI's own ``Main`` on a
device-resident synthetic set, timed epoch by epoch.

One process: it holds the chip, runs ``Main().run([workflow file,
...])`` (``Launcher`` -> ``StandardWorkflow`` -> ``FusedTick``) and,
when the run has ended and the program's state is freed, the plain
reference. The ``Session`` is the benchmark's clock inside the run:
it wraps the fused tick's and the decision's ``run`` and

- epoch 0 (checked): keeps the losses of the validation sweep at the
  initial values and of the train sweep, the rows the loader served,
  and the per-leaf norms of the parameters' change and of the
  velocity after the train sweep; keeps epoch 1's validation loss;
- warms up ``warmup_epochs`` whole epochs (the checked one among
  them), so every program and the decision's one-epoch-late
  bookkeeping have run before the window;
- opens the window at an epoch boundary with the device drained,
  counts epochs, and once ``--seconds`` have passed lets the epoch in
  flight finish, has the decision stop the workflow, and closes the
  window when the device is drained again.
"""

import json
import os
import sys
import time

import numpy

from benchmark.harness import common, data as data_lib

TRAIN, VALID = 2, 1


def scaled(config, rehearse):
    """The configuration as run: itself, or for the CPU rehearsal its
    ``rehearsal`` overrides with every width shrunk as
    ``AlexNetWorkflow(scale=...)`` shrinks it."""
    if not rehearse:
        return config
    toy = dict(config)
    small = config["rehearsal"]
    for key in ("input_shape", "n_classes", "dataset"):
        toy[key] = small[key]
    scale = small["scale"]
    layers = []
    for layer in config["layers"]:
        layer = dict(layer)
        if "kernels" in layer:
            layer["kernels"] = max(4, int(layer["kernels"] * scale))
        if "units" in layer:
            layer["units"] = max(16, int(layer["units"] * scale))
        layers.append(layer)
    layers[-1]["units"] = toy["n_classes"]
    toy["layers"] = layers
    return toy


def gaps_between(got, want):
    """The five numbers compared, ``got`` against the reference's
    ``want`` (both as ``follow_first_epoch`` returns them): the three
    losses' relative gaps, and by the worst leaf the gap of the
    velocity's norm (the gradients as the optimizer got them, summed
    with the momentum's weights) and of the norm of the parameters'
    change. Leaves whose velocity in the reference is under a
    thousandth of the median leaf's move by round-off alone and are
    left out of the change. Returns (gaps, leaves left out)."""
    out = {key + "_gap": common.relative_gap(got[key], want[key])
           for key in ("loss_valid0", "loss_train0", "loss_valid1")}
    ref_velocity = want["velocity_norms"]
    floor = 1e-3 * float(numpy.median(ref_velocity))
    keep = [v >= floor for v in ref_velocity]
    out["velocity_norm_gap"] = common.worst_leaf_gap(
        got["velocity_norms"], ref_velocity)
    out["dparam_norm_gap"] = common.worst_leaf_gap(
        got["dparam_norms"], want["dparam_norms"], keep)
    return out, len(keep) - sum(keep)


class Session:
    """The benchmark's side of one training run (see the module)."""

    def __init__(self, settings):
        self.settings = settings
        self.rehearse = bool(settings["rehearse"])
        self.full_config = common.load_json(settings["config"])
        self.config = scaled(self.full_config, self.rehearse)
        self.traffic = common.load_json(settings["traffic"])
        self.seed = int(settings["seed"])
        self.seconds = float(settings["seconds"])
        self.trace = bool(settings["trace"])
        self.started = float(settings["started"])
        self.minibatch = (self.full_config["rehearsal"]["minibatch"]
                          if self.rehearse
                          else self.traffic["minibatch"])
        self.reference = common.load_module(self.config["reference"])
        self.compiles = common.CompileCounter()
        self.phase = "warm"
        self.epochs_served = 0
        self.sweeps_in_epoch = 0
        self.series = []
        self.host_s = {"tick": 0.0, "decide": 0.0}
        self.checked = {}
        self.window = None
        self.tracer = None
        self.marks = {}

    # -- before the run ------------------------------------------------
    def workflow_kwargs(self):
        cfg, sizes = self.config, self.config["dataset"]
        t0 = time.perf_counter()
        self.data, self.labels = data_lib.dataset(
            self.seed, cfg["input_shape"], sizes["n_valid"],
            sizes["n_train"], sizes["label_classes"])
        self.marks["generate_s"] = time.perf_counter() - t0
        rehearsal = self.full_config["rehearsal"]
        return dict(
            name=cfg["name"], n_classes=cfg["n_classes"],
            scale=rehearsal["scale"] if self.rehearse else 1.0,
            learning_rate=cfg["learning_rate"],
            gradient_moment=cfg["gradient_moment"],
            weights_decay=cfg["weights_decay"],
            loader_kwargs=dict(
                data=self.data, labels=self.labels,
                class_lengths=[0, sizes["n_valid"], sizes["n_train"]],
                minibatch_size=self.minibatch,
                normalization_type=cfg["normalization"]),
            decision_kwargs=dict(cfg["decision"]))

    def install(self, workflow):
        """Hand every layer its initial values (made by the reference
        module from the seed) before the units initialize, and time
        the loader's load + upload."""
        workflow.bench = self
        start = self.reference.init_params(self.seed, self.config)
        with_params = [p for p in start if p]
        forwards = [f for f in workflow.forwards
                    if hasattr(f, "weights")]
        if len(with_params) != len(forwards):
            raise RuntimeError("the configuration has %d layers with "
                               "parameters, the workflow %d"
                               % (len(with_params), len(forwards)))
        for unit, p in zip(forwards, with_params):
            unit.weights.data = p["w"]
            unit.bias.data = p["b"]
        self.start = start
        loader = workflow.loader
        load = loader.load_data

        def timed_load():
            import jax
            t0 = time.perf_counter()
            load()
            jax.block_until_ready(loader.original_data.data)
            self.marks["upload_s"] = time.perf_counter() - t0

        loader.load_data = timed_load

    def attach(self, workflow):
        """Called as the workflow's run starts: put the clock around
        the fused tick and the decision."""
        tick = workflow.fused_tick
        if tick is None or not workflow.loader.sweep_serving:
            raise RuntimeError("the fused sweep engine is not engaged; "
                               "the cell times that path")
        for unit, p in zip([f for f in workflow.forwards
                            if hasattr(f, "weights")],
                           [p for p in self.start if p]):
            if unit.weights.shape != p["w"].shape:
                raise RuntimeError("%s has weights %s, the reference %s"
                                   % (unit.name, unit.weights.shape,
                                      p["w"].shape))
        self.workflow = workflow
        self._tick_run, tick.run = tick.run, self._tick
        decision = workflow.decision
        self._decision_run, decision.run = decision.run, self._decide

    # -- inside the run ------------------------------------------------
    def _tick(self):
        import jax

        wf = self.workflow
        tick, loader = wf.fused_tick, wf.loader
        klass = loader.minibatch_class
        if self.sweeps_in_epoch == 0 and self.phase == "warm" \
                and self.epochs_served >= self.traffic["warmup_epochs"]:
            jax.block_until_ready(tick._params_)
            if self.trace:
                self.tracer = common.TracedWindow(
                    self.settings["workload"])
                self.tracer.start()
            self.phase = "window"
            self.marks.update(self.compiles.set_up_marks())
            self.heartbeat = common.Heartbeat()
            self.heartbeat.start()
            self.window = {"compiles_before": self.compiles.count,
                           "epochs": 0, "open": time.perf_counter()}
        t0 = time.perf_counter()
        self._tick_run()
        self.host_s["tick"] += time.perf_counter() - t0
        self.sweeps_in_epoch += 1
        if self.epochs_served == 0:
            name = "loss_valid0" if klass == VALID else "loss_train0"
            self.checked[name] = wf.evaluator.loss.data
            if klass == TRAIN:
                self.checked["rows"] = numpy.array(
                    loader.minibatch_indices.data)
                self.checked["norms"] = self._norms(tick._params_,
                                                    self.start)
                self.start = None
        elif self.epochs_served == 1 and klass == VALID:
            self.checked["loss_valid1"] = wf.evaluator.loss.data

    def _norms(self, params, start):
        """Per-leaf norms of the change from ``start`` and of the
        velocity, dispatched behind the sweep that made ``params``."""
        import jax

        leaf_norms = self.reference.leaf_norms

        @jax.jit
        def norms(params, start):
            moved = [p["p"] if p else {} for p in params]
            velocity = [p["v"] if p else {} for p in params]
            return leaf_norms(moved, start), leaf_norms(velocity)

        return norms(params, start)

    def _decide(self):
        import jax

        wf = self.workflow
        ended = bool(wf.loader.epoch_ended)
        last = False
        if ended and self.phase == "window":
            now = time.perf_counter() - self.window["open"]
            length = min(self.seconds, self.traffic.get(
                "trace_seconds", 5.0)) if self.trace else self.seconds
            if now >= length:
                # the decision then settles every epoch it holds back
                # and stops the workflow on this very tick
                wf.decision.max_epochs = self.epochs_served + 1
                last = True
        t0 = time.perf_counter()
        self._decision_run()
        self.host_s["decide"] += time.perf_counter() - t0
        if not ended:
            return
        self.epochs_served += 1
        self.sweeps_in_epoch = 0
        if self.phase == "window":
            if last:
                jax.block_until_ready(wf.fused_tick._params_)
            now = time.perf_counter()
            self.window["epochs"] += 1
            # where the host spent the epoch: inside the tick's and
            # the decision's own calls (either may wait for the
            # device), the rest in the engine between them
            self.series.append({
                "t": round(now - self.window["open"], 4),
                "epochs": self.window["epochs"],
                "tick_s": round(self.host_s["tick"], 4),
                "decide_s": round(self.host_s["decide"], 4),
                "compiles": self.compiles.count
                - self.window["compiles_before"]})
            if last:
                self.marks.update(self.heartbeat.stop())
                self.window["close"] = now
                self.window["compiles"] = (
                    self.compiles.count
                    - self.window["compiles_before"])
                if self.tracer is not None:
                    self.tracer.stop()
                self.phase = "closed"
        self.host_s = {"tick": 0.0, "decide": 0.0}

    # -- after the run -------------------------------------------------
    def measured(self):
        """End-to-end numbers of the closed window."""
        if self.phase != "closed":
            raise RuntimeError("the window never closed (phase %r)"
                               % self.phase)
        seconds = self.window["close"] - self.window["open"]
        images = self.window["epochs"] * self.config["dataset"]["n_train"]
        return {"window_s": seconds, "images": images,
                "epochs": self.window["epochs"],
                "train_images_per_s_chip": images / seconds,
                "setup_s": self.window["open"] - self.started}

    def compare(self, workflow):
        """The reference over epoch 0 and epoch 1's validation sweep,
        against what the timed path produced there. Called with the
        window closed, the peak read and the program's parameters
        freed; the set on the device is the benchmark's own bytes
        (uploaded by the loader), checked against the host copy on
        rows drawn from the seed."""
        import jax
        import jax.numpy as jnp

        cfg, limits = self.config, self.full_config["limits"]
        sizes = cfg["dataset"]
        device_data = workflow.loader.original_data.data
        got = {key: float(self.checked[key]) for key in
               ("loss_valid0", "loss_train0", "loss_valid1")}
        dparam, velocity = (numpy.asarray(v, numpy.float64)
                            for v in self.checked["norms"])
        rows = self.checked["rows"].reshape(-1)
        out = common.Comparison()
        want_rows = numpy.arange(sizes["n_valid"],
                                 sizes["n_valid"] + sizes["n_train"])
        out.add("feed_rows_not_a_permutation",
                float(not numpy.array_equal(numpy.sort(rows),
                                            want_rows)), 0.0)
        rng = numpy.random.Generator(numpy.random.PCG64(self.seed))
        sample = numpy.sort(rng.choice(len(self.data), 32,
                                       replace=False))
        on_device = numpy.asarray(jnp.take(device_data, sample, axis=0))
        out.add("data_rows_differ_from_seed",
                float(numpy.sum(on_device != self.data[sample])), 0.0)
        t0 = time.perf_counter()
        want = self.reference.follow_first_epoch(
            cfg, self.seed, device_data, jnp.asarray(self.labels),
            rows, self.minibatch)
        self.marks["reference_s"] = time.perf_counter() - t0
        got.update(dparam_norms=dparam, velocity_norms=velocity)
        found, left_out = gaps_between(got, want)
        for key, value in found.items():
            out.add(key, value, limits[key])
        self.marks["left_out_of_dparam"] = left_out
        self.control = None
        if self.settings["control"]:
            # calibration: the reference at the lower operand type in
            # the program's place, held to the same limits
            low, _ = gaps_between(self.reference.follow_first_epoch(
                cfg, self.seed, device_data, jnp.asarray(self.labels),
                rows, self.minibatch,
                operands=self.settings["control"]), want)
            self.control = common.Comparison()
            for key, value in low.items():
                self.control.add(key, value, limits[key])
        return out


def run(cell, args, started):
    """One run of a ``train_fullbatch`` cell; returns the result
    dict ``benchmark/run.py`` prints."""
    import gc

    facts = common.device_facts(cell["chips"],
                                require_tpu=not args.rehearse)
    from veles_tpu.__main__ import Main

    main = Main()
    config = cell["config"]
    argv = [os.path.join(common.ROOT, config["workflow"]), "-",
            "--seed", str(args.seed % (1 << 63))]
    for key, value in (("config", cell["config_file"]),
                       ("traffic", cell["traffic_file"]),
                       ("seed", args.seed), ("seconds", args.seconds),
                       ("trace", int(args.trace)), ("started", started),
                       ("rehearse", int(args.rehearse)),
                       ("control", args.control or ""),
                       ("workload", cell["name"])):
        argv.append("root.benchmark.%s=%r" % (key, value))
    status = main.run(argv)
    if status:
        raise RuntimeError("the CLI returned %r" % status)
    workflow = main.workflow
    session = workflow.bench
    measured = session.measured()
    memory = common.memory_stats()
    # free what the program holds on the device except the data set
    tick = workflow.fused_tick
    tick._params_ = tick._eval_stash_ = tick._rollback_ = None
    for unit in list(workflow.forwards) + list(workflow.gds):
        for slot in vars(unit).values():
            if hasattr(slot, "reset") and hasattr(slot, "to_device"):
                slot.reset()
    gc.collect()
    compared = session.compare(workflow)
    counters = dict(session.marks)
    counters.update(
        compiles_in_window=session.window["compiles"],
        minibatch=session.minibatch,
        steps_per_train_sweep=(session.config["dataset"]["n_train"]
                               // session.minibatch),
        images_per_s=measured["train_images_per_s_chip"],
        window_s=measured["window_s"], epochs=measured["epochs"])
    return {
        "facts": facts, "memory": memory, "compared": compared,
        "control": session.control,
        "attempted": measured["epochs"], "failed": 0,
        "end_to_end": {
            "train_images_per_s_chip":
                measured["train_images_per_s_chip"],
            "setup_s": measured["setup_s"]},
        "counters": counters, "series": session.series,
        "tracer": session.tracer, "config": session.config,
    }
