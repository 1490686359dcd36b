"""StandardWorkflow: declarative model assembly from a layer-spec list.

The Znicz StandardWorkflow pattern: reference model configs declare
topologies as lists of layer dicts and the workflow wires
loader → forwards → evaluator → decision → gds automatically. Layer types:

    {"type": "all2all_tanh", "output_sample_shape": 100, ...}
    {"type": "conv_relu", "n_kernels": 32, "kx": 3, "ky": 3, ...}
    {"type": "max_pooling", "kx": 2, "ky": 2}
    {"type": "softmax", "output_sample_shape": 10}

Per-layer trainer kwargs (learning_rate, weights_decay, gradient_moment,
l1_vs_l2) may be embedded in each spec under "trainer"; workflow-level
defaults apply otherwise.
"""

from veles_tpu.core.workflow import Workflow
from veles_tpu.core.plumbing import Repeater
from veles_tpu.loader.fullbatch import FullBatchLoader, FullBatchLoaderMSE
from veles_tpu.nn.all2all import (
    All2All, All2AllRELU, All2AllSigmoid, All2AllSoftmax,
    All2AllStrictRELU, All2AllTanh)
from veles_tpu.nn.conv import (
    Conv, ConvRELU, ConvStrictRELU, ConvTanh, GDConv, GDConvRELU,
    GDConvStrictRELU, GDConvTanh)
from veles_tpu.nn.decision import DecisionGD, DecisionMSE
from veles_tpu.nn.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.nn.gd import (
    GDRELU, GDSigmoid, GDSoftmax, GDStrictRELU, GDTanh, GradientDescent,
    link_err_output)
from veles_tpu.nn.attention import (
    GDLayerNorm, GDSelfAttention, GDTokenFFN, LayerNorm, SelfAttention,
    TokenFFN)
from veles_tpu.nn.pooling import (
    AvgPooling, GDPooling, MaxAbsPooling, MaxPooling)

FORWARD_TYPES = {
    "self_attention": (SelfAttention, GDSelfAttention),
    "ffn": (TokenFFN, GDTokenFFN),
    "layer_norm": (LayerNorm, GDLayerNorm),
    "all2all": (All2All, GradientDescent),
    "all2all_tanh": (All2AllTanh, GDTanh),
    "all2all_relu": (All2AllRELU, GDRELU),
    "all2all_strict_relu": (All2AllStrictRELU, GDStrictRELU),
    "all2all_sigmoid": (All2AllSigmoid, GDSigmoid),
    "softmax": (All2AllSoftmax, GDSoftmax),
    "conv": (Conv, GDConv),
    "conv_tanh": (ConvTanh, GDConvTanh),
    "conv_relu": (ConvRELU, GDConvRELU),
    "conv_strict_relu": (ConvStrictRELU, GDConvStrictRELU),
    "max_pooling": (MaxPooling, GDPooling),
    "maxabs_pooling": (MaxAbsPooling, GDPooling),
    "avg_pooling": (AvgPooling, GDPooling),
}

TRAINER_KEYS = ("learning_rate", "learning_rate_bias", "weights_decay",
                "l1_vs_l2", "gradient_moment", "solver", "adam_beta1",
                "adam_beta2", "adam_epsilon")


class StandardWorkflow(Workflow):
    """Declarative topology workflow (the Znicz StandardWorkflow role)."""

    def __init__(self, workflow, layers=(), loader_kwargs=None,
                 loader_cls=None, decision_kwargs=None, **kwargs):
        self.layer_defaults = {k: kwargs.pop(k) for k in TRAINER_KEYS
                               if k in kwargs}
        # fused tick mode: True/False or "auto" (use it whenever the
        # topology supports it and we run standalone); mesh_ is not
        # pickled (jax Device objects) — resumed pod runs fall back to
        # the single-device fused tick
        self.fused = kwargs.pop("fused", "auto")
        # sweep serving: one XLA dispatch per class sweep (lax.scan over
        # the minibatches) instead of one per minibatch
        self.fused_sweep = kwargs.pop("fused_sweep", True)
        # pipelined epochs (default): metrics materialize one epoch late
        # with their device->host copies prefetched, so the per-epoch
        # sync overlaps the next epoch's compute — outputs are proven
        # identical incl. the stop paths (tests/test_fused.py); log
        # lines/plotters lag one epoch. Disable with
        # fused_pipeline=False. (see parallel/fused.py FusedTick docs)
        self.fused_pipeline = kwargs.pop("fused_pipeline", True)
        self.mesh_ = kwargs.pop("mesh", None)
        #: "softmax" (classification) or "mse" (regression): selects the
        #: evaluator/decision pair and the default loader (the Znicz
        #: model families both existed — EvaluatorMSE + DecisionMSE
        #: drove the approximator/autoencoder workflows)
        self.evaluator_kind = kwargs.pop("evaluator", "softmax")
        if self.evaluator_kind not in ("softmax", "mse"):
            raise ValueError("evaluator must be 'softmax' or 'mse', got "
                             "%r" % self.evaluator_kind)
        self.fused_tick = None
        super().__init__(workflow, **kwargs)
        loader_cls = loader_cls or (
            FullBatchLoaderMSE if self.evaluator_kind == "mse"
            else FullBatchLoader)
        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)
        self.loader = loader_cls(self, **(loader_kwargs or {}))
        self.loader.link_from(self.repeater)
        self.forwards = []
        self.gds = []
        self._specs = [dict(spec) for spec in layers]
        self._build_forwards()
        self._build_evaluator_and_decision(decision_kwargs or {})
        self._build_gds()
        self.repeater.link_from(self.gds[0])
        self.end_point.link_from(self.decision)
        # the completing tick's backward chain still runs — its minibatch
        # is real train data, and the fused engine, sweep tier, and fleet
        # slave path all apply that update.  The EndPoint's AND-gate
        # therefore waits for BOTH the decision and the gd chain, so the
        # final update lands before on_workflow_finished; the LOADER (not
        # the gds) is stop-gated for the tick after.  Kohonen uses the
        # same pattern (models/kohonen.py).
        self.end_point.link_from(self.gds[0])
        self.end_point.gate_block = ~self.decision.complete
        self.loader.gate_block = self.decision.complete
        # fleet: the loader's job stream dries up when the decision says so
        # (same Bool object, so the master's NoMoreJobs check follows it)
        self.loader.complete = self.decision.complete

    def initialize(self, **kwargs):
        if self.is_slave:
            # decide fusibility on the INTACT graph (the chain check in
            # supports() needs the repeater cycle), then rewire
            from veles_tpu.parallel import fused
            mesh = getattr(self, "mesh_", None)
            use_fused = bool(self.fused) and self.fused_tick is None \
                and fused.supports(self, mesh)
            if bool(self.fused) and self.fused_tick is None \
                    and not use_fused:
                # same contract as the standalone path (_enable_fused):
                # an explicit fused=True must not silently degrade, and
                # an explicitly configured mesh must not silently run
                # the per-unit graph on one device at 1/Nth speed
                if self.fused is True:
                    raise ValueError(
                        "fused=True but the topology/loader is not "
                        "fusible on this slave")
                if mesh is not None:
                    raise ValueError(
                        "a device mesh is configured but this slave's "
                        "topology/loader cannot run the sharded fused "
                        "tick (minibatch size must divide by the data "
                        "axis; see parallel/fused.py supports()); drop "
                        "--mesh / root.common.mesh.axes or fix the "
                        "topology")
            # a slave executes exactly ONE tick per job: break the repeater
            # loop-back and fire the EndPoint right after the backward chain
            # so the job callback ships the update (reference
            # workflow.py:554-569)
            from veles_tpu.fleet import fleet_control_plane
            if fleet_control_plane() and not use_fused:
                # the control-plane wire carries no weights: the slave's
                # params live in the fused tick's device-resident tree
                # (with its one-slot rollback). A graph-mode slave
                # mutates unit Arrays in place with no rollback — a
                # re-issued job would silently double-apply
                raise ValueError(
                    "control-plane fleet mode (root.common.fleet.plane"
                    "=control) requires the fused tick on the slave, "
                    "but this topology/loader is not fusible (see "
                    "parallel/fused.py supports()) — use the data "
                    "plane for graph-mode slaves")
            self.repeater.unlink_from(self.gds[0])
            self.end_point.unlink_from(self.decision)
            self.end_point.link_from(self.gds[0])
            from veles_tpu.core.mutable import Bool
            self.end_point.gate_block = Bool(False)
            if use_fused:
                self._enable_fused_slave(mesh)
        elif self.fused and self.is_standalone:
            self._enable_fused()
        return super().initialize(**kwargs)

    def apply_initial_data_from_master(self, data):
        """Handshake application + fused-tick residency reset: in
        control-plane mode a (re)handshake that ships initial weights
        (first join, or a master restart under a new epoch) must make
        the next tick refresh its device-resident params from the unit
        Arrays instead of continuing from the pre-handshake replica."""
        super().apply_initial_data_from_master(data)
        tick = self.fused_tick
        if data and tick is not None \
                and hasattr(tick, "reset_residency"):
            tick.reset_residency()

    def _enable_fused_slave(self, mesh):
        """Fleet x pod composition (SURVEY §5's stated translation): the
        slave's one-tick job becomes the fused step — shard_map-ped over
        the slave's LOCAL mesh when one is configured. Jobs and merged
        updates ride DCN through the fleet protocol exactly as before;
        the gradient merge inside the tick psums over ICI. (Reference
        slave job execution: ``workflow.py:554-569``.)"""
        from veles_tpu.parallel import fused

        self.fused_tick = fused.FusedTick(self, mesh=mesh,
                                          name="fused_tick",
                                          pipelined=False)
        self.forwards[0].unlink_from(self.loader)
        self.end_point.unlink_from(self.gds[0])
        self.fused_tick.link_from(self.loader)
        self.end_point.link_from(self.fused_tick)
        self.loader.fill_data = False
        self.info(
            "slave fused tick%s",
            "" if mesh is None else
            " over local mesh %s" % dict(zip(mesh.axis_names,
                                             mesh.devices.shape)))

    def _disable_fused_slave(self):
        """Reverse the slave splice (loader HBM-OOM fallback)."""
        tick = self.fused_tick
        if tick is None:
            return
        self.fused_tick = None
        tick.unlink_from(self.loader)
        self.end_point.unlink_from(tick)
        self.del_ref(tick)
        self.forwards[0].link_from(self.loader)
        self.end_point.link_from(self.gds[0])
        self.loader.fill_data = True

    def _enable_fused(self):
        """Splice the FusedTick in place of the per-unit compute chain:
        loader → FusedTick → decision (see parallel/fused.py). Graph mode
        units stay constructed — they own the weights and serve the fleet
        and export paths."""
        from veles_tpu.parallel import fused

        if self.fused_tick is not None:  # resumed snapshot: already wired
            return
        mesh = getattr(self, "mesh_", None)
        if not fused.supports(self, mesh):
            if self.fused is True:
                raise ValueError(
                    "fused=True but the topology/loader is not fusible")
            if mesh is not None:
                # the user explicitly asked for pod mode (--mesh /
                # config); a single-device fallback would look like a
                # pod run at 1/Nth speed
                raise ValueError(
                    "a device mesh is configured but this topology/"
                    "loader cannot run the sharded fused tick "
                    "(minibatch size must divide by the data axis; see "
                    "parallel/fused.py supports()); drop --mesh / "
                    "root.common.mesh.axes or fix the topology")
            self._enable_segments()
            return
        self.fused_tick = fused.FusedTick(
            self, mesh=mesh, name="fused_tick",
            pipelined=bool(getattr(self, "fused_pipeline", False)
                           and getattr(self, "fused_sweep", True)))
        # detach the graph-mode compute chain from the control path
        self.forwards[0].unlink_from(self.loader)
        self.decision.unlink_from(self.evaluator)
        self.gds[-1].unlink_from(self.decision)
        self.repeater.unlink_from(self.gds[0])
        # the detached chain can't fire the EndPoint's AND-gate; the
        # decision link alone finishes the fused run
        self.end_point.unlink_from(self.gds[0])
        # splice the fused tick in
        self.fused_tick.link_from(self.loader)
        self.decision.link_from(self.fused_tick)
        self.repeater.link_from(self.decision)
        self.loader.gate_block = self.decision.complete
        self.loader.fill_data = False
        self.loader.sweep_serving = bool(getattr(self, "fused_sweep",
                                                 True))
        self.info("fused tick mode: %d-layer chain compiled into one "
                  "XLA computation per %s", len(self.forwards),
                  "class sweep" if self.loader.sweep_serving else "tick")

    def _enable_segments(self):
        """Lower fusion tiers (the graph-mode-cliff fix) for chains the
        full fused engine declines — an unrecognized/custom layer type,
        a custom unit spliced into the chain:

        - sweep tier (``parallel/sweep.py``): the whole cycle scanned
          over class sweeps when every mid-chain host unit is
          sweep-transparent — full-engine-class dispatch counts for ANY
          JitUnit chain;
        - segment tier (``parallel/segments.py``): runs of consecutive
          JitUnits collapse into composite per-tick dispatches when a
          host unit needs true per-tick slot access."""
        from veles_tpu.parallel import segments as seg_mod
        from veles_tpu.parallel import sweep as sweep_mod

        if any(isinstance(u, (seg_mod.FusedSegment, sweep_mod.FusedSweep))
               for u in self.units):
            return  # resumed snapshot: the splice is already in place
        swept = None
        if getattr(self, "fused_sweep", True):
            # fused_sweep=False is the user's opt-out of sweep serving
            # (per-minibatch decision cadence) — honor it here too
            swept = sweep_mod.enable(
                self,
                pipelined=bool(getattr(self, "fused_pipeline", False)))
        if swept is not None:
            self.info("sweep-tier fusion: %d compute unit(s) scanned "
                      "per class sweep (%d host unit(s) fire per tick)",
                      len(swept.members), len(swept.hosts))
            return
        created = seg_mod.enable(self)
        if created:
            self.info("partial fusion: %d segment(s) — %s",
                      len(created), ", ".join(s.name for s in created))

    def add_standard_plotters(self, confusion=True, weights=False):
        """Attach the stock live-training plotters (the reference model
        workflows wired these by hand in every sample): a validation
        error curve, optionally the confusion matrix (graph mode only —
        the fused tick publishes loss/n_err) and a weights
        multi-histogram. Call BEFORE initialize(); the launcher's
        GraphicsServer renders them."""
        from veles_tpu.plotting import (AccumulatingPlotter,
                                        MatrixPlotter, MultiHistogram)

        self.plotters = []
        err = AccumulatingPlotter(self, name="%s: validation errors"
                                  % self.name, last=0)
        # last_epoch_* are FROZEN per-epoch snapshots: the live
        # accumulators are already zeroed when a leaf plotter fires
        err.link_attrs(self.decision, ("input", "last_epoch_n_err"))
        err.input_field = 1  # VALID class
        err.gate_skip = ~self.decision.epoch_ended
        err.link_from(self.decision)
        self.plotters.append(err)
        if confusion:
            # the decision accumulates the VALID confusion over each
            # epoch; both graph mode and the fused tick's eval passes
            # publish the per-pass increments
            cm = MatrixPlotter(self, name="%s: confusion" % self.name)
            cm.link_attrs(self.decision, ("input", "last_epoch_confusion"))
            cm.link_attrs(self.loader, "reversed_labels_mapping")
            cm.gate_skip = ~self.decision.epoch_ended
            cm.link_from(self.decision)
            self.plotters.append(cm)
        if weights:
            # at the epoch tick the unit Arrays hold the weights the
            # epoch's metrics were MEASURED on (the eval-tick write-back
            # in fused sweep mode) — so this histogram is consistent
            # with the error/confusion plots of the same tick
            wh = MultiHistogram(self, name="%s: weights" % self.name)
            wh.link_attrs(self.forwards[0], ("input", "weights"))
            wh.gate_skip = ~self.decision.epoch_ended
            wh.link_from(self.decision)
            self.plotters.append(wh)
        return self.plotters

    def run(self):
        if bool(self.decision.complete):
            # e.g. a FINISHED snapshot was restored: the loader gate is
            # blocked, so firing the start point would hang forever —
            # finish cleanly instead (raise decision.max_epochs and
            # unset decision.complete to continue training)
            self.warning("workflow is already complete; nothing to run")
            self._finished = False
            self.on_workflow_finished()
            return self
        return super().run()

    def on_workflow_finished(self):
        # fused mode writes unit-Array weights back on EVAL ticks (the
        # evaluated state, for snapshot-on-improved parity); the final
        # post-train state lands here so exports/results see it
        sync_owner = self.fused_tick or getattr(self, "sweep_unit", None)
        if sync_owner is not None:
            try:
                sync_owner.sync_params()
            except Exception:
                # also reached via on_error: a failed train step leaves
                # _params_ pointing at donated (deleted) buffers — a
                # raise here would swallow _sync_event_.set() and hang
                # run() forever, masking the original failure
                self.exception("final fused param sync failed")
        super().on_workflow_finished()

    def _disable_fused(self):
        """Reverse the FusedTick splice (e.g. the loader's HBM-OOM host
        fallback made in-tick gather counterproductive)."""
        tick = self.fused_tick
        if tick is None:
            return
        self.fused_tick = None
        tick.unlink_from(self.loader)
        self.decision.unlink_from(tick)
        self.repeater.unlink_from(self.decision)
        self.del_ref(tick)
        self.forwards[0].link_from(self.loader)
        self.decision.link_from(self.evaluator)
        self.gds[-1].link_from(self.decision)
        self.repeater.link_from(self.gds[0])
        self.end_point.link_from(self.gds[0])
        self.loader.gate_block = self.decision.complete
        self.loader.fill_data = True
        self.loader.sweep_serving = False

    def _build_forwards(self):
        src = self.loader
        for i, spec in enumerate(self._specs):
            spec = dict(spec)
            ltype = spec.pop("type")
            spec.pop("trainer", None)
            fwd_cls, _ = FORWARD_TYPES[ltype]
            fwd = fwd_cls(self, name="fwd%d" % i, **spec)
            fwd.link_from(src)
            if i == 0:
                fwd.link_attrs(self.loader, ("input", "minibatch_data"))
            else:
                fwd.link_attrs(self.forwards[-1], ("input", "output"))
            self.forwards.append(fwd)
            src = fwd

    def _build_evaluator_and_decision(self, decision_kwargs):
        if self.evaluator_kind == "mse":
            self.evaluator = EvaluatorMSE(self)
            self.evaluator.link_from(self.forwards[-1])
            self.evaluator.link_attrs(self.forwards[-1],
                                      ("input", "output"))
            self.evaluator.link_attrs(self.loader,
                                      ("target", "minibatch_targets"),
                                      "sample_mask")
            self.decision = DecisionMSE(self, **decision_kwargs)
        else:
            self.evaluator = EvaluatorSoftmax(self)
            self.evaluator.link_from(self.forwards[-1])
            self.evaluator.link_attrs(self.forwards[-1],
                                      ("input", "output"))
            self.evaluator.link_attrs(self.loader,
                                      ("labels", "minibatch_labels"),
                                      "sample_mask")
            self.decision = DecisionGD(self, **decision_kwargs)
        self.decision.link_from(self.evaluator)
        self.decision.loader = self.loader
        self.decision.evaluator = self.evaluator

    def _build_gds(self):
        self.gds = [None] * len(self.forwards)
        err_src = self.evaluator
        prev = self.decision
        for i in reversed(range(len(self.forwards))):
            spec = self._specs[i]
            _, gd_cls = FORWARD_TYPES[spec["type"]]
            trainer = dict(self.layer_defaults)
            trainer.update(spec.get("trainer", {}))
            if gd_cls is GDPooling:
                gd = GDPooling(self, name="gd%d" % i)
                gd.link_pooling(self.forwards[i], err_src)
            elif issubclass(gd_cls, GDSelfAttention):
                # covers GDTokenFFN too (same four-leaf slot contract)
                gd = gd_cls(self, name="gd%d" % i, **trainer)
                gd.link_attention(self.forwards[i], err_src)
            elif issubclass(gd_cls, GDConv):
                gd = gd_cls(self, name="gd%d" % i, **trainer)
                gd.link_conv(self.forwards[i], err_src)
            else:
                gd = gd_cls(self, name="gd%d" % i, **trainer)
                gd.link_forward(self.forwards[i], err_src)
            gd.link_from(prev)
            gd.gate_skip = self.decision.gd_skipped
            self.gds[i] = gd
            err_src = gd
            prev = gd
