"""Command-line entry point: ``python -m veles_tpu <workflow.py> <config.py>``.

Reference ``veles/__main__.py`` + ``cmdline.py``. The workflow module
contract is preserved (reference ``__main__.py:799-818``): the user module
defines ``run(load, main)`` where

    load(WorkflowClass, **kwargs) -> (workflow, snapshot_loaded)
    main(**kwargs)  # initializes and runs the launcher

Config files are executable Python mutating ``root`` (reference
``__main__.py:426-472``); trailing ``root.a.b=value`` CLI overrides are
applied after. ``-l/--listen`` makes this process the fleet master,
``-m/--master-address`` a slave, neither → standalone; ``-w`` resumes from
a snapshot.
"""

import argparse
import importlib.util
import json
import os
import runpy
import sys

from veles_tpu.core import prng
from veles_tpu.core.config import root
from veles_tpu.core.logger import Logger, setup_logging
from veles_tpu.launcher import Launcher
from veles_tpu.snapshotter import SnapshotterToFile


class Main(Logger):
    """CLI driver (reference ``__main__.py:136``)."""

    def __init__(self):
        super().__init__(logger_name="Main")
        self.launcher = None
        self.workflow = None
        self.snapshot_path = None
        self.visualize = None
        self.dump_unit_attributes = False
        self.profile_dir = None

    @staticmethod
    def init_parser():
        parser = argparse.ArgumentParser(
            prog="veles_tpu",
            description="TPU-native dataflow deep-learning framework")
        parser.add_argument("workflow", help="workflow python file")
        parser.add_argument("config", nargs="?", default=None,
                            help="config python file ('-' to skip)")
        parser.add_argument("overrides", nargs="*", default=[],
                            help="root.path=value config overrides")
        parser.add_argument("-l", "--listen", default=None,
                            metavar="HOST:PORT",
                            help="run as fleet master, listening here")
        parser.add_argument("-m", "--master-address", default=None,
                            metavar="HOST:PORT",
                            help="run as fleet slave of this master")
        parser.add_argument("-w", "--snapshot", default=None,
                            help="resume from a snapshot file")
        parser.add_argument("--result-file", default=None,
                            help="write IResultProvider metrics JSON here")
        parser.add_argument("--seed", default=None,
                            help="seed for the named PRNG streams "
                                 "(int, or key=int,key=int)")
        parser.add_argument("--train-ratio", type=float, default=None,
                            help="use only this fraction of the train set")
        parser.add_argument("--optimize", default=None,
                            metavar="SIZE:GENERATIONS",
                            help="genetic hyperparameter search over "
                                 "Range() config values")
        parser.add_argument("--optimize-fleet", default=None,
                            metavar="HOST:PORT",
                            help="distribute --optimize evaluations to "
                                 "fleet slaves (run them with "
                                 "`python -m veles_tpu.fleet.farm "
                                 "HOST:PORT --name genetics`)")
        parser.add_argument("--optimize-representation", default="numeric",
                            choices=("numeric", "gray"),
                            help="chromosome representation for --optimize")
        parser.add_argument("--ensemble-train", default=None,
                            metavar="N:RATIO",
                            help="train N instances on RATIO of the train "
                                 "set each; write ensemble.json")
        parser.add_argument("--ensemble-test", default=None, metavar="FILE",
                            help="re-evaluate the snapshots of a trained "
                                 "ensemble")
        parser.add_argument("--async-slave", action="store_true",
                            help="pipelined slave mode")
        parser.add_argument("--mesh", default=None,
                            metavar="AXIS=N[,AXIS=N...]",
                            help="pod mode: shard the workflow tick over "
                                 "a device mesh, e.g. --mesh data=8 or "
                                 "--mesh data=4,model=2 (axes: pipe, "
                                 "data, expert, seq, model; -1 absorbs "
                                 "the remaining devices)")
        parser.add_argument("--coordinator", default=None,
                            metavar="HOST:PORT",
                            help="multi-host pod: jax.distributed "
                                 "coordination service address (run the "
                                 "same command on every host)")
        parser.add_argument("--num-processes", type=int, default=None,
                            help="multi-host pod: total process count")
        parser.add_argument("--process-id", type=int, default=None,
                            help="multi-host pod: this process's index "
                                 "(0 owns snapshots/plots/results)")
        parser.add_argument("-n", "--nodes", action="append",
                            metavar="HOST[,HOST...]",
                            help="master mode: spawn a slave on each "
                                 "host at startup (ssh; localhost runs "
                                 "a detached subprocess)")
        parser.add_argument("--respawn", action="store_true",
                            help="master: relaunch dead slaves on their "
                                 "hosts; slave: ship the relaunch recipe")
        parser.add_argument("--slave-death-probability", type=float,
                            default=0.0, help="fault injection")
        parser.add_argument("--fleet-plane", default=None,
                            choices=("data", "control"),
                            help="fleet wire plane (set IDENTICALLY on "
                                 "master and slaves): 'data' ships "
                                 "weights in every job/update frame "
                                 "(reference protocol); 'control' "
                                 "ships batch assignments + scalar "
                                 "metrics only — the gradient merge "
                                 "runs in-program on the slave's mesh "
                                 "(parallel/mapreduce.py) and weights "
                                 "cross the wire only at handshake and "
                                 "epoch fences (docs/compiler_fleet"
                                 ".md)")
        parser.add_argument("--fleet-reduce", default=None,
                            choices=("f32", "bf16", "int8"),
                            help="in-program gradient all-reduce wire "
                                 "tier for meshed ticks: f32 (exact, "
                                 "default), bf16 (half the bytes), or "
                                 "int8 (quantized all-reduce with "
                                 "per-leaf scales, ~4x fewer bytes — "
                                 "see docs/compiler_fleet.md for the "
                                 "convergence caveats)")
        chaos = parser.add_argument_group(
            "chaos harness", "slave-side deterministic fault injection "
            "(fleet/chaos.py; probabilities in [0,1], one seeded RNG "
            "stream so a given seed replays the same fault schedule)")
        chaos.add_argument("--chaos-seed", type=int, default=None,
                           metavar="N", help="chaos RNG seed")
        chaos.add_argument("--chaos-frame-drop", type=float, default=None,
                           metavar="P", help="drop a frame (connection "
                           "reset) with probability P")
        chaos.add_argument("--chaos-frame-delay", type=float, default=None,
                           metavar="P", help="delay a frame with "
                           "probability P")
        chaos.add_argument("--chaos-slow-job", type=float, default=None,
                           metavar="P", help="stretch a job (straggler) "
                           "with probability P")
        chaos.add_argument("--chaos-duplicate-update", type=float,
                           default=None, metavar="P",
                           help="replay an update frame with probability "
                           "P (the master must fence the duplicate)")
        chaos.add_argument("--chaos-death", type=float, default=None,
                           metavar="P", help="die mid-job with "
                           "probability P (disconnect in-process; "
                           "root.common.fleet.chaos.death_mode=exit for "
                           "the reference os._exit)")
        serve = parser.add_argument_group(
            "serving survival", "admission control, deadlines and "
            "chaos for the serving tier (serving.py / serving_chaos.py;"
            " docs/serving_robustness.md)")
        serve.add_argument("--serve-max-queue", type=int, default=None,
                           metavar="N", help="bound on staged + "
                           "in-flight serving requests; beyond it new "
                           "arrivals get 429 + Retry-After (0 disables "
                           "the bound)")
        serve.add_argument("--serve-deadline", type=float, default=None,
                           metavar="S", help="default per-request "
                           "serving deadline in seconds; an expired "
                           "request frees its decoder slot (504)")
        serve.add_argument("--serve-mesh", default=None,
                           metavar="AXIS=N[,AXIS=N...]",
                           help="serve the slot engine sharded over a "
                           "device mesh, e.g. --serve-mesh model=8 "
                           "(params tensor-parallel, slot KV sharded "
                           "over heads; -1 absorbs the remaining "
                           "devices — docs/sharded_serving.md)")
        serve.add_argument("--serve-paged", action="store_true",
                           default=None,
                           help="back the slot engine with the paged "
                           "KV pool + shared-prefix admission instead "
                           "of the dense per-slot slab "
                           "(docs/paged_kv.md)")
        serve.add_argument("--serve-page-size", type=int, default=None,
                           metavar="N", help="positions per KV page "
                           "(default SLOT_SPAN_TILE=128; must be a "
                           "multiple of the span tile on TPU)")
        serve.add_argument("--serve-aot", default=None, metavar="PATH",
                           help="boot GenerateAPI from an AOT "
                           "compiled-program bundle (veles_tpu aot "
                           "build): cold start becomes deserialize + "
                           "execute, zero retracing; a stale bundle "
                           "is refused by name and serving falls "
                           "back to live compilation "
                           "(docs/aot_artifacts.md)")
        serve.add_argument("--serve-pool-pages", type=int, default=None,
                           metavar="N", help="total pages in the KV "
                           "pool incl. the scratch page (default: the "
                           "dense-slab-equivalent slots x "
                           "ceil((max_len + 2*n_tokens)/page_size) + 1 "
                           "— sized for dispatch chunks up to "
                           "n_tokens)")
        serve.add_argument("--serve-slo", default=None,
                           metavar="OBJ=TARGET[,OBJ=TARGET...]",
                           help="SLO objectives for the request "
                           "ledger, e.g. --serve-slo ttft_p95_ms=250,"
                           "tpot_p95_ms=50,availability=0.999 — "
                           "evaluated over multi-window rolling "
                           "buckets and exported as veles_slo_* "
                           "burn-rate gauges "
                           "(root.common.observe.slo; "
                           "docs/observability.md)")
        serve.add_argument("--serve-governor", default=None,
                           metavar="KEY=VALUE[,KEY=VALUE...]",
                           help="enable the closed-loop serving "
                           "governor: SLO-burn-driven graceful "
                           "degradation down the bf16->int8->int8-kv "
                           "ladder with hysteresis bands, admission "
                           "resize + Retry-After priced from the KV "
                           "pool release rate, AOT hot-bucket prewarm "
                           "and a proactive breaker guard — e.g. "
                           "--serve-governor demote_burn=2,"
                           "recover_burn=1,cooldown_s=10,"
                           "ladder=int8+int8-kv "
                           "(root.common.serve.governor; "
                           "docs/serving_robustness.md)")
        serve.add_argument("--serve-history", default=None,
                           metavar="KEY=VALUE[,KEY=VALUE...]",
                           help="tune (or disable) the metric flight "
                           "recorder: a bounded in-process time-series "
                           "history sampled off the registry wherever "
                           "/metrics is mounted, with anomaly rules "
                           "and incident autopsies — e.g. "
                           "--serve-history interval_s=0.5,"
                           "capacity=600 or --serve-history off "
                           "(default: on, 1s cadence; "
                           "root.common.observe.history; "
                           "docs/observability.md)")
        serve.add_argument("--chaos-serve-seed", type=int, default=None,
                           metavar="N", help="serving chaos RNG seed")
        serve.add_argument("--chaos-serve-step-fail", type=float,
                           default=None, metavar="P",
                           help="inject a decoder-step failure with "
                           "probability P (trips the circuit breaker)")
        serve.add_argument("--chaos-serve-step-fail-max", type=int,
                           default=None, metavar="N",
                           help="cap on injected step failures (the "
                           "chaos run provably settles)")
        serve.add_argument("--chaos-serve-slow-step", type=float,
                           default=None, metavar="P",
                           help="stretch a decode step with "
                           "probability P (straggling device)")
        parser.add_argument("--dry-run",
                            choices=("load", "init"), default=None,
                            help="stop after loading/initializing")
        parser.add_argument("--visualize", default=None, metavar="PATH",
                            help="write the workflow unit graph as "
                                 "Graphviz DOT after initialize")
        parser.add_argument("--dump-unit-attributes", action="store_true",
                            help="print every unit's post-init state as "
                                 "JSON lines")
        parser.add_argument("--profile", default=None, metavar="DIR",
                            help="capture a jax profiler trace of the "
                                 "run (view in TensorBoard/Perfetto); "
                                 "host spans are annotated into the "
                                 "device trace by name")
        parser.add_argument("--trace-events", default=None,
                            metavar="PATH",
                            help="enable span tracing: trace_id'd span "
                                 "events append to this JSONL file "
                                 "(export with `veles_tpu observe "
                                 "export-trace PATH`)")
        parser.add_argument("--manhole", action="store_true",
                            help="serve a live debug console on a unix "
                                 "socket (<dirs.run>/manhole-<pid>.sock;"
                                 " attach: python -m "
                                 "veles_tpu.core.manhole <path>)")
        parser.add_argument("--dump-config", action="store_true")
        parser.add_argument("-b", "--background", action="store_true",
                            help="daemonize: run detached with stdio "
                                 "redirected to <cache>/daemon.log")
        parser.add_argument("-v", "--verbose", action="count", default=0)
        return parser

    def _daemonize(self):
        """Detach by RE-EXEC, not fork (reference ``-b`` daemonized via
        double-fork): by the time the flag is handled the workflow module
        import has initialized JAX/XLA worker threads, and a forked child
        inherits their wedged mutexes — its first dispatch dies. A fresh
        detached process of the same command (minus ``-b``) is fork-safe
        by construction."""
        import subprocess
        log_path = os.path.join(root.common.dirs.get("cache", "."),
                                "daemon.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        argv = [a for a in sys.argv[1:]
                if a not in ("-b", "--background")]
        with open(log_path, "ab") as log, \
                open(os.devnull, "rb") as devnull:
            proc = subprocess.Popen(
                [sys.executable, "-m", "veles_tpu"] + argv,
                stdin=devnull, stdout=log, stderr=log,
                start_new_session=True)
        self.info("daemonized as pid %d, logging to %s", proc.pid,
                  log_path)
        os._exit(0)

    # -- config handling (reference __main__.py:426-481) ---------------------
    def apply_config(self, config_path):
        if config_path in (None, "-"):
            return
        runpy.run_path(config_path, init_globals={"root": root})

    def override_config(self, overrides):
        for item in overrides:
            if "=" not in item:
                raise ValueError("override %r is not root.path=value" % item)
            path, value = item.split("=", 1)
            parts = path.split(".")
            if parts[0] != "root":
                raise ValueError("override must start with 'root.': %r"
                                 % item)
            node = root
            for part in parts[1:-1]:
                node = getattr(node, part)
            try:
                import ast
                value = ast.literal_eval(value)
            except (ValueError, SyntaxError):
                pass  # keep as string
            setattr(node, parts[-1], value)

    def seed_random(self, spec):
        """Seed named streams (reference ``_seed_random``,
        ``__main__.py:483-537``)."""
        if spec is None:
            return
        if "=" in spec:
            for part in spec.split(","):
                key, _, value = part.partition("=")
                prng.get(key).seed(int(value))
        else:
            prng.get("default").seed(int(spec))
            prng.get("loader").seed(int(spec) + 1)

    # -- workflow module loading (reference _load_model) ---------------------
    def load_module(self, path):
        name = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(name, path)
        if spec is None:
            raise ImportError("cannot import workflow from %r" % path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
        if not hasattr(module, "run"):
            raise ValueError(
                "workflow module %s lacks run(load, main)" % path)
        return module

    def _resolve_snapshot(self, path):
        """Support ``-w http(s)://...`` snapshot sources: download to a
        temp file first (reference ``__main__.py:572-581``)."""
        if not path or not path.startswith(("http://", "https://")):
            return path
        import shutil
        import tempfile
        import urllib.request
        suffix = os.path.splitext(path)[1] or ".pickle"
        fd, local = tempfile.mkstemp(suffix=suffix, prefix="snapshot_")
        self.info("downloading snapshot %s", path)
        try:
            with urllib.request.urlopen(path, timeout=60) as resp, \
                    os.fdopen(fd, "wb") as fout:
                shutil.copyfileobj(resp, fout)  # stream, don't buffer
        except Exception:
            try:
                os.unlink(local)
            except OSError:
                pass
            raise
        return local

    # -- the load/main pair handed to the module -----------------------------
    def _load(self, workflow_class, **kwargs):
        snapshot_loaded = False
        if self.snapshot_path:
            self.info("resuming from %s", self.snapshot_path)
            if self.snapshot_path.startswith("sqlite://"):
                from veles_tpu.snapshotter import SnapshotterToDB
                self.workflow = SnapshotterToDB.import_(self.snapshot_path)
            else:
                self.workflow = SnapshotterToFile.import_(
                    self.snapshot_path)
            self.workflow.workflow = self.launcher
            snapshot_loaded = True
        else:
            self.workflow = workflow_class(self.launcher, **kwargs)
        return self.workflow, snapshot_loaded

    def _main(self, **kwargs):
        if self.dry_run == "load":
            return
        self.launcher.initialize(**kwargs)
        if self.visualize:
            path = self.visualize
            with open(path, "w") as fout:
                fout.write(self.workflow.generate_graph())
            self.info("workflow graph written to %s (render with "
                      "`dot -Tsvg`)", path)
        if self.dump_unit_attributes:
            self._dump_unit_attributes()
        if self.dry_run == "init":
            return
        manhole = None
        if getattr(self, "manhole_requested", False):
            # live debug console (reference --manhole,
            # thread_pool.py:137): attach to THIS running process
            from veles_tpu.core.manhole import Manhole
            manhole = Manhole(namespace=dict(
                main=self, launcher=self.launcher,
                workflow=self.workflow)).start()
        try:
            self._run_launcher()
        finally:
            # always reclaim the socket file — a crashed run's pid never
            # comes back, so nothing else would ever unlink it
            if manhole is not None:
                manhole.stop()

    def _run_launcher(self):
        if self.profile_dir:
            # device-level timeline (the reference's Mongo event spans /
            # web timeline role, done the TPU way): a jax profiler trace
            # viewable in TensorBoard / Perfetto; profile_window also
            # turns on span-named TraceAnnotations so the host span
            # timeline shares the XLA device trace's clock
            # (docs/observability.md)
            from veles_tpu.observe.profile import profile_window
            self.info("profiling to %s (open with tensorboard or "
                      "ui.perfetto.dev)", self.profile_dir)
            # strict: the capture was asked for by name, so a profiler
            # that cannot start fails the run rather than the request
            with profile_window(self.profile_dir, strict=True):
                self.launcher.run()
        else:
            self.launcher.run()
        self.launcher.stop()

    def _dump_unit_attributes(self):
        """Post-init unit state dump (reference ``--dump-unit-attributes``,
        ``__main__.py:663-685``)."""
        for unit in self.workflow.units:
            attrs = {}
            for key, value in sorted(vars(unit).items()):
                if key.startswith("_") or key.endswith("_"):
                    continue
                if isinstance(value, (int, float, str, bool, type(None))):
                    attrs[key] = value
                elif isinstance(value, (list, tuple)) and len(value) < 16:
                    attrs[key] = repr(value)
                else:
                    attrs[key] = type(value).__name__
            print(json.dumps({"unit": unit.name,
                              "type": type(unit).__name__,
                              "attrs": attrs}))

    # -- entry ----------------------------------------------------------------
    def run(self, argv=None):
        parser = self.init_parser()
        args = parser.parse_args(argv)
        import logging
        setup_logging(level=logging.DEBUG if args.verbose else logging.INFO)
        # black box on SIGTERM (observe/flight.py): an orchestrator
        # killing this run leaves the last spans/dispatches on disk —
        # CLI runs only, library embedders keep their own signal policy
        from veles_tpu.observe.flight import install_signal_handlers
        install_signal_handlers()
        if args.coordinator:
            # BEFORE the workflow module import (whose jax use would
            # initialize the backend single-process)
            if args.num_processes is None or args.process_id is None:
                parser.error("--coordinator requires --num-processes "
                             "and --process-id")
            from veles_tpu.parallel.mesh import initialize_distributed
            self.info("joining pod: coordinator %s, process %d/%d",
                      args.coordinator, args.process_id,
                      args.num_processes)
            initialize_distributed(args.coordinator, args.num_processes,
                                   args.process_id)
        self.dry_run = args.dry_run
        self.manhole_requested = args.manhole
        self.snapshot_path = self._resolve_snapshot(args.snapshot)
        self.visualize = args.visualize
        self.dump_unit_attributes = args.dump_unit_attributes
        self.profile_dir = args.profile
        if args.trace_events:
            # opt-in tracing: span events (trace_id/span_id/mono) append
            # to the JSONL file; export with `veles_tpu observe
            # export-trace` (docs/observability.md)
            from veles_tpu.core.logger import enable_event_recording
            from veles_tpu.observe.tracing import get_tracer
            enable_event_recording(args.trace_events)
            get_tracer().enable()
            self.info("span tracing to %s", args.trace_events)
        # plugins BEFORE the workflow module: a ``veles_tpu_*`` package /
        # ``veles_tpu.plugins`` entry point registers its units through
        # the registry metaclasses, making them constructible by name in
        # the workflow being loaded (reference ``veles.__plugins__``
        # namespace scan, ``__init__.py:191-215``)
        import veles_tpu
        plugins = veles_tpu.scan_plugins()
        if plugins:
            self.info("plugins: %s",
                      ", ".join(getattr(p, "__name__", repr(p))
                                for p in plugins))
        # module FIRST (its import-time root.* updates are defaults), then
        # the config file, then CLI overrides — the reference's layering
        # (__main__.py:396,426-481)
        module = self.load_module(args.workflow)
        self.apply_config(args.config)
        self.override_config(args.overrides)
        if args.mesh:
            # after the config layering: the flag wins over config files
            from veles_tpu.parallel.mesh import parse_axes
            try:
                overrides = parse_axes(args.mesh, flag="--mesh")
            except ValueError as exc:
                parser.error(str(exc))
            for axis, size in overrides.items():
                setattr(root.common.mesh.axes, axis, size)
        if args.serve_slo:
            # validate NOW (same early-failure contract as --mesh); the
            # string lands in root.common.observe.slo below and the
            # SLO engine re-parses it at GenerateAPI construction
            from veles_tpu.observe.slo import parse_objectives
            try:
                parse_objectives(args.serve_slo, flag="--serve-slo")
            except ValueError as exc:
                parser.error(str(exc))
        if args.serve_governor:
            # validate NOW (same early-failure contract as --serve-slo);
            # the string lands in root.common.serve.governor below and
            # GenerateAPI re-parses it at construction
            from veles_tpu.observe.governor import parse_governor_spec
            try:
                parse_governor_spec(args.serve_governor,
                                    flag="--serve-governor")
            except ValueError as exc:
                parser.error(str(exc))
        if args.serve_history:
            # validate NOW (same early-failure contract as
            # --serve-slo); the string lands in
            # root.common.observe.history below and the history store
            # re-parses it when /metrics first mounts
            from veles_tpu.observe.history import parse_history_spec
            try:
                parse_history_spec(args.serve_history,
                                   flag="--serve-history")
            except ValueError as exc:
                parser.error(str(exc))
        if args.serve_mesh:
            # validate NOW (same early-failure contract as --mesh); the
            # string itself lands in config below and GenerateAPI
            # re-parses it via serving.build_serve_mesh
            from veles_tpu.parallel.mesh import parse_axes
            try:
                parse_axes(args.serve_mesh, flag="--serve-mesh")
            except ValueError as exc:
                parser.error(str(exc))
        # chaos flags AFTER the config layering: the CLI wins over
        # root.common.fleet.chaos.* set by config files
        for flag, key in (("chaos_seed", "seed"),
                          ("chaos_frame_drop", "frame_drop"),
                          ("chaos_frame_delay", "frame_delay"),
                          ("chaos_slow_job", "slow_job"),
                          ("chaos_duplicate_update", "duplicate_update"),
                          ("chaos_death", "death")):
            value = getattr(args, flag)
            if value is not None:
                setattr(root.common.fleet.chaos, key, value)
        # serving survival flags, same layering rule
        for flag, node, key in (
                ("fleet_plane", root.common.fleet, "plane"),
                ("fleet_reduce", root.common.fleet, "reduce"),
                ("serve_max_queue", root.common.serve, "max_queue"),
                ("serve_deadline", root.common.serve, "deadline"),
                ("serve_mesh", root.common.serve, "mesh"),
                ("serve_paged", root.common.serve, "paged"),
                ("serve_page_size", root.common.serve, "page_size"),
                ("serve_pool_pages", root.common.serve, "pool_pages"),
                ("serve_aot", root.common.serve, "aot"),
                ("serve_slo", root.common.observe, "slo"),
                ("serve_governor", root.common.serve, "governor"),
                ("serve_history", root.common.observe, "history"),
                ("chaos_serve_seed", root.common.serve.chaos, "seed"),
                ("chaos_serve_step_fail", root.common.serve.chaos,
                 "step_fail"),
                ("chaos_serve_step_fail_max", root.common.serve.chaos,
                 "step_fail_max"),
                ("chaos_serve_slow_step", root.common.serve.chaos,
                 "slow_step")):
            value = getattr(args, flag)
            if value is not None:
                setattr(node, key, value)
        if args.background:
            # AFTER config layering: daemon.log must honor a cache dir
            # set by the config file or CLI overrides
            self._daemonize()
        if args.dump_config:
            root.print_()
            return 0
        if args.train_ratio is not None:
            root.common.train_ratio = args.train_ratio
        # meta-workflow dispatch (reference _run_core, __main__.py:716-734)
        if args.optimize:
            return self._run_optimize(args)
        if args.ensemble_train:
            return self._run_ensemble_train(args)
        if args.ensemble_test:
            return self._run_ensemble_test(args)
        from veles_tpu.genetics.config import fix_config
        fix_config(root)  # strip any Range() declarations for normal runs
        self.seed_random(args.seed)
        self.launcher = Launcher(
            listen_address=args.listen,
            master_address=args.master_address,
            result_file=args.result_file,
            async_slave=args.async_slave,
            respawn=args.respawn,
            nodes=[h for spec in (args.nodes or [])
                   for h in spec.split(",") if h],
            slave_death_probability=args.slave_death_probability)
        module.run(self._load, self._main)
        return 0


    # -- meta-workflows (reference --optimize / --ensemble-*) ----------------
    def _run_optimize(self, args):
        from veles_tpu.genetics import GeneticsOptimizer, process_config
        size, _, gens = args.optimize.partition(":")
        genes = process_config(root)
        if not genes:
            self.error("no Range() values found in the config — nothing "
                       "to optimize")
            return 1
        self.info("optimizing %d genes: %s", len(genes),
                  [path for path, _ in genes])
        optimizer = GeneticsOptimizer(
            args.workflow, args.config, genes=genes,
            population_size=int(size or 12),
            generations=int(gens or 5), seed=args.seed,
            fleet=args.optimize_fleet,
            representation=args.optimize_representation)
        best = optimizer.run()
        if best is None:
            return 1
        print(json.dumps({
            "best_fitness": best.fitness,
            "best_values": {path: value for (path, _), value in
                            zip(best.genes, best.values)}}, indent=1))
        return 0

    def _run_ensemble_train(self, args):
        from veles_tpu.ensemble import EnsembleTrainer
        count, _, ratio = args.ensemble_train.partition(":")
        trainer = EnsembleTrainer(
            args.workflow, args.config, instances=int(count),
            train_ratio=float(ratio or 0.8))
        trainer.run()
        return 0

    def _run_ensemble_test(self, args):
        from veles_tpu.ensemble import EnsembleTester
        tester = EnsembleTester(args.ensemble_test, args.workflow,
                                args.config)
        print(json.dumps(tester.run(), indent=1, default=str))
        return 0


def main(argv=None):
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    # `veles_tpu forge ...` subcommand dispatch (reference
    # __main__.py:230-241 special-arg handling)
    if argv and argv[0] == "forge":
        from veles_tpu.forge.client import main as forge_main
        return forge_main(argv[1:])
    if argv and argv[0] == "parity":
        from veles_tpu.parity import main as parity_main
        return parity_main(argv[1:])
    if argv and argv[0] == "observe":
        from veles_tpu.observe.trace_export import main as observe_main
        return observe_main(argv[1:])
    if argv and argv[0] == "aot":
        from veles_tpu.aot.cli import main as aot_main
        return aot_main(argv[1:])
    if argv and argv[0] == "analyze":
        from veles_tpu.analyze.cli import main as analyze_main
        return analyze_main(argv[1:])
    if argv and argv[0] == "route":
        from veles_tpu.router import main as route_main
        return route_main(argv[1:])
    if argv and argv[0] == "deploy":
        from veles_tpu.deploy_cli import main as deploy_main
        return deploy_main(argv[1:])
    return Main().run(argv)


if __name__ == "__main__":
    sys.exit(main())
