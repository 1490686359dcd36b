"""Sweep-tier partial fusion: ``lax.scan`` ANY JitUnit chain over whole
class sweeps.

The third fusion tier. The full engine
(:mod:`veles_tpu.parallel.fused`) recognizes the standard forward/GD
topology and compiles hand-written sweep steps; the segment tier
(:mod:`veles_tpu.parallel.segments`) fuses runs of consecutive JitUnits
but still dispatches and serves per minibatch — which leaves any
workflow the full engine declines ~40x off the flagship path, because
per-tick host serving + dispatch dominates a small step (the
reference ran EVERY topology at full engine speed,
``veles/workflow.py:347-365``).

This tier closes that gap for any linear repeater cycle whose compute
units are JitUnits — including custom user layers the full engine has
never heard of — by composing the units' OWN ``compute()`` functions
into one per-minibatch body (dataflow derived from the shared Array
slots, exactly like the segment planner) and scanning that body over an
entire class sweep in ONE XLA dispatch per chunk:

- the loader switches to sweep serving (one index matrix per class per
  epoch — the fused engine's serving mode);
- the in-scan gather + normalize replicates the loader's jitted fill
  (``FullBatchLoader._fill_jit``) exactly;
- slots written by one iteration and read by the next (weights,
  velocities, Adam moments — anything the slot graph says) ride the
  scan carry; everything else stays intra-iteration dataflow;
- TRAIN sweeps include the units gated on ``decision.gd_skipped``; eval
  sweeps trace a variant without them — the same class-constant gate
  decision graph mode makes per tick;
- the Decision consumes sweep-aggregated metrics through its existing
  sweep-serving branch (the fused engine's contract).

Host units in the cycle still fire once per tick, between scanned runs:
the sweep executes in chunks (``root.common.engine.sweep_chunk``
minibatches per dispatch), and after each chunk is dispatched —
asynchronously, XLA computes while the host works — every mid-chain
host unit runs once per minibatch of that chunk, in chain order. This
is only observably identical to graph mode when those units do not read
or write device Array slots, so they must declare it:
``sweep_transparent = True`` (see :class:`veles_tpu.core.units.Unit`).
A non-transparent host unit makes the workflow fall back to the
per-tick segment tier — correctness beats speed.

Weight semantics match every other tier: the stopping epoch's last
TRAIN minibatch applies its update before the run finishes (graph mode
wires the EndPoint's AND-gate behind the gd chain for the same effect —
see StandardWorkflow.__init__). Metrics are bit-identical to graph mode
throughout — every metric sweep precedes the updates.
"""

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.core.config import root
from veles_tpu.core.units import Unit
from veles_tpu.loader.base import TRAIN
from veles_tpu.memory import Array
from veles_tpu.ops.gather import gather_minibatch
from veles_tpu.parallel.segments import (_default_block, _default_skip,
                                         _fusible, chain_of)

#: loader slot attr -> lane name produced inside the scan body
_LANES = (("minibatch_data", "data"), ("minibatch_labels", "labels"),
          ("minibatch_targets", "targets"), ("sample_mask", "mask"),
          ("minibatch_indices", "indices"))


def classify(workflow):
    """Sweep eligibility: returns ``(members, hosts)`` or None.

    ``members`` is the ordered list of ``(unit, train_only)`` compute
    steps (the Decision excluded — it is hoisted out of the cycle and
    fed sweep aggregates); ``hosts`` the ordered transparent host
    units. Gate rule: a member carries its birth gates, or the standard
    Decision wiring (``gate_skip is decision.gd_skipped`` => TRAIN-only,
    ``gate_block is decision.complete`` => stop-gated, which sweep mode
    subsumes by stopping the serving loop)."""
    from veles_tpu.loader.fullbatch import FullBatchLoader

    loader = getattr(workflow, "loader", None)
    decision = getattr(workflow, "decision", None)
    if decision is None or loader is None:
        return None
    if not isinstance(loader, FullBatchLoader) or not loader.on_device:
        return None
    if getattr(loader, "has_fill_transforms", False):
        # in-fill augmentation draws per-minibatch randomness the scan
        # does not replicate (the full engine special-cases "mirror")
        return None
    chain = chain_of(workflow)
    if not chain or decision not in chain:
        return None
    allowed = set(chain) | {loader, workflow.repeater, decision}
    members, hosts = [], []
    for unit in chain:
        if unit is decision:
            continue
        # the EndPoint hangs off the LAST chain unit (its AND-gate holds
        # the final update before finish — StandardWorkflow wiring); the
        # sweep splice subsumes that by stopping the serving loop, and
        # disable() restores exactly this link. An end_point link from
        # any OTHER unit is custom finish wiring the splice could not
        # restore — those chains stay on the segment tier.
        permitted = allowed | ({workflow.end_point}
                               if unit is chain[-1] else set())
        outside = [u for u in list(unit.links_from) + list(unit.links_to)
                   if u not in permitted]
        if outside:
            # a monitor/provider hangs off a cycle unit: per-sweep
            # execution would change when it fires — segment tier keeps
            # per-tick semantics for it
            return None
        if _fusible(unit):
            if any(not isinstance(getattr(unit, n), Array)
                   for n in unit.OUTPUTS):
                return None  # non-Array outputs: can't carry through scan
            train_only = False
            if not _default_skip(unit):
                if unit.gate_skip is decision.gd_skipped:
                    train_only = True
                else:
                    return None
            if not _default_block(unit) \
                    and unit.gate_block is not decision.complete:
                return None
            members.append((unit, train_only))
        elif getattr(unit, "sweep_transparent", False):
            if not (_default_skip(unit) and _default_block(unit)):
                return None
            hosts.append(unit)
        else:
            return None
    if not members:
        return None
    evaluator = getattr(workflow, "evaluator", None)
    if evaluator is None or evaluator not in (u for u, _ in members):
        return None  # the Decision's sweep branch needs the aggregates
    return members, hosts


def _lane_ids(loader):
    lanes = {}
    for attr, lane in _LANES:
        slot = getattr(loader, attr, None)
        if isinstance(slot, Array):
            lanes[id(slot)] = lane
    return lanes


class _Plan:
    """Static dataflow plan for one gate variant (train or eval).

    ``steps``: ``(unit, in_refs, outs)`` in chain order, where in_refs
    tag each compute argument as ``("env", pos)`` intra-iteration,
    ``("lane", name)`` loader-served, ``("carry", idx)`` previous
    iteration's write, or ``("const", idx)`` per-sweep constant.
    ``writes``: ordered ``(unit, attr)`` — every slot the body produces,
    deduped by Array identity (the scan carry and the post-sweep
    scatter). ``carry_reads``: positions in ``writes`` that seed
    cross-iteration reads. ``consts``: ``(unit, attr)`` read once per
    sweep dispatch (weights in the eval variant, hyper vectors, .)."""

    def __init__(self, members, lanes):
        written = {}  # id(Array) -> write index
        writes = []   # (unit, attr) representative
        for unit, _ in members:
            for name in unit.OUTPUTS:
                slot = getattr(unit, name)
                key = id(slot)
                if key not in written:
                    written[key] = len(writes)
                    writes.append((unit, name))
        consts, const_index = [], {}
        steps = []
        produced = {}  # id(Array) -> env position (this iteration)
        carry_read_set = {}
        n_values = 0
        for unit, _ in members:
            in_refs = []
            for name in unit.INPUTS:
                slot = getattr(unit, name)
                if isinstance(slot, Array):
                    key = id(slot)
                    if key in produced:
                        in_refs.append(("env", produced[key]))
                        continue
                    if key in lanes:
                        in_refs.append(("lane", lanes[key]))
                        continue
                    if key in written:
                        # read before this iteration's write: previous
                        # iteration's value rides the carry
                        idx = carry_read_set.setdefault(key, written[key])
                        in_refs.append(("carry", idx))
                        continue
                else:
                    key = (id(unit), name)
                if key not in const_index:
                    const_index[key] = len(consts)
                    consts.append((unit, name))
                in_refs.append(("const", const_index[key]))
            outs = []
            for name in unit.OUTPUTS:
                slot = getattr(unit, name)
                pos = n_values
                n_values += 1
                produced[id(slot)] = pos
                outs.append((pos, written[id(slot)]))
            steps.append((unit, in_refs, outs))
        self.steps = steps
        self.writes = writes
        self.written = written
        #: carry slots that must hold REAL values before iteration 0
        self.carry_reads = sorted(set(carry_read_set.values()))
        self.consts = consts
        self.n_values = n_values


class FusedSweep(Unit):
    """One class sweep of the whole repeater cycle as chunked
    ``lax.scan`` dispatches over the units' own computes.

    Spliced like the FusedTick: ``loader -> FusedSweep -> decision ->
    repeater``; the member units stay constructed (weights, exports,
    snapshots all read their Array slots — final values are scattered
    back after every sweep) but leave the control graph.
    """

    hide_from_registry = True
    VIEW_GROUP = "WORKER"
    #: execution strategy, not topology (see Workflow.checksum)
    EPHEMERAL = True

    def __init__(self, workflow, members, hosts, chain_units,
                 pipelined=False, **kwargs):
        kwargs.setdefault("name", "sweep[%d units]" % len(members))
        super().__init__(workflow, **kwargs)
        self.members = list(members)  # [(unit, train_only)]
        self.hosts = list(hosts)
        #: the original linear cycle order (incl. the Decision) — the
        #: exact restore recipe for disable()
        self.chain_units = list(chain_units)
        self.chunk = int(root.common.engine.get("sweep_chunk", 64))
        #: pipelined epochs (the FusedTick design): the Decision
        #: materializes metrics one epoch late so the per-epoch
        #: device->host sync overlaps the next epoch's compute; the
        #: sweep keeps a one-slot state history so the unit Arrays
        #: always hold the weights the currently-attributed metrics
        #: scored, and a lagged stop rolls back the one speculative
        #: epoch — outputs identical to the unpipelined run.
        self.pipelined = pipelined
        self.ticks = 0

    def initialize(self, **kwargs):
        wf = self.workflow
        loader = wf.loader
        if not loader.on_device:
            # the loader's HBM-OOM fallback kicked in during load_data:
            # in-scan gather from host originals would re-upload the
            # dataset every chunk — restore per-tick graph mode
            self.warning("dataset fell back to host: disabling the "
                         "sweep tier")
            self.disable()
            return
        if self.pipelined:
            from veles_tpu.loader.base import VALID
            if loader.effective_class_lengths[VALID] == 0:
                # lagged improvement tracking needs a VALID sweep
                self.warning("pipelined sweeps need a validation split:"
                             " disabling pipelining")
                self.pipelined = False
            wf.decision.pipeline_depth = 1 if self.pipelined else 0

    def disable(self):
        """Undo the splice: relink the original linear cycle (classify
        guaranteed the chain had no outside links beyond the EndPoint
        gate, so a sequential relink + the finish gate is a complete
        restoration)."""
        wf = self.workflow
        loader = wf.loader
        self.unlink_all()
        wf.repeater.unlink_from(wf.decision)  # the splice's loop-back
        prev = loader
        for unit in self.chain_units:
            unit.link_from(prev)
            prev = unit
        wf.repeater.link_from(prev)
        # restore the finish gate EXACTLY as it was at enable() time: a
        # StandardWorkflow chain had the EndPoint AND-gated on the last
        # gd (the completing tick's update lands before finish); a
        # custom chain gated on the decision alone must NOT gain a
        # second AND input it never fires
        if getattr(self, "restore_finish_link", True):
            wf.end_point.link_from(prev)
        saved_gate = getattr(self, "saved_loader_gate", None)
        # `is not None`, not truthiness: a saved Bool(False) is falsy
        # but is exactly what must come back
        loader.gate_block = (saved_gate if saved_gate is not None
                             else wf.decision.complete)
        loader.fill_data = True
        loader.sweep_serving = False
        if getattr(wf, "sweep_unit", None) is self:
            wf.sweep_unit = None
        wf.del_ref(self)

    def init_unpickled(self):
        super().init_unpickled()
        self._plans_ = None
        self._fns_ = {}
        self._norm_ = None
        if not hasattr(self, "pipelined"):
            self.pipelined = False
        #: the TRUE current value of every written slot, keyed by
        #: id(Array) — reads prefer it over slot.data so the Arrays can
        #: lag one epoch in pipelined mode; volatile, so a resumed
        #: snapshot falls back to the slots (which then hold the
        #: restored state)
        self._state_ = {}
        self._eval_stash_ = None
        self._stashed_this_epoch_ = False
        self._wrote_eval_params_ = False

    # -- plan + compile -------------------------------------------------------
    def _build(self):
        loader = self.workflow.loader
        lanes = _lane_ids(loader)
        train_plan = _Plan(self.members, lanes)
        eval_plan = _Plan([(u, t) for u, t in self.members if not t],
                          lanes)
        self._plans_ = {True: train_plan, False: eval_plan}
        self._norm_ = {k: jnp.asarray(v) for k, v in
                       loader.normalizer.jit_state().items()}
        evaluator = self.workflow.evaluator
        self._metric_slots_ = {
            name: id(getattr(evaluator, name))
            for name in evaluator.OUTPUTS
            if name in ("loss", "n_err", "confusion_matrix")
            and isinstance(getattr(evaluator, name), Array)}
        self._with_confusion_ = (
            "confusion_matrix" in self._metric_slots_
            and getattr(evaluator, "compute_confusion", True))

    def _chunk_fn(self, training):
        """The jitted chunk executor for one gate variant (built once;
        jax retraces per chunk length)."""
        fn = self._fns_.get(training)
        if fn is not None:
            return fn
        plan = self._plans_[training]
        loader = self.workflow.loader
        norm_cls = type(loader.normalizer)
        metric = self._metric_slots_
        with_cm = self._with_confusion_
        loss_w = plan.written.get(metric.get("loss"))
        err_w = plan.written.get(metric.get("n_err"))
        cm_w = plan.written.get(metric.get("confusion_matrix"))

        def body(reads, consts, data, labels, targets, norm, row, valid):
            # the loader's jitted fill, replicated in-scan (same
            # gather + normalizer.apply_state math => same numerics)
            batch, lab = gather_minibatch(data, row, labels)
            batch = norm_cls.apply_state(jnp, batch, norm)
            mask = (jnp.arange(row.shape[0]) < valid).astype(jnp.float32)
            lane_vals = {"data": batch, "labels": lab, "mask": mask,
                         "indices": row}
            if targets is not None:
                lane_vals["targets"] = jnp.take(targets, row, axis=0)
            env = [None] * plan.n_values
            writes = list(reads)
            for unit, in_refs, outs in plan.steps:
                args = []
                for tag, ref in in_refs:
                    if tag == "env":
                        args.append(env[ref])
                    elif tag == "lane":
                        args.append(lane_vals[ref])
                    elif tag == "carry":
                        args.append(writes[ref])
                    else:
                        args.append(consts[ref])
                res = unit.compute(*args)
                if len(outs) == 1:
                    res = (res,)
                for (pos, widx), val in zip(outs, res):
                    env[pos] = val
                    writes[widx] = val
            valid_f = valid.astype(jnp.float32)
            loss_sum = (writes[loss_w] * valid_f
                        if loss_w is not None else jnp.float32(0))
            n_err = (writes[err_w] if err_w is not None
                     else jnp.int32(0))
            cm = (writes[cm_w] if with_cm and cm_w is not None
                  else jnp.zeros((1, 1), jnp.int32))
            return tuple(writes), (loss_sum, n_err, cm)

        def chunk(init_reads, consts, data, labels, targets, norm, rows,
                  valids):
            """``init_reads`` seed only the cross-iteration carry slots;
            iteration 0 populates the full write set, which then carries
            through the scan (write-only slots never need a pre-value)."""
            writes0 = [None] * len(plan.writes)
            for i, idx in enumerate(plan.carry_reads):
                writes0[idx] = init_reads[i]
            writes0, met0 = body(writes0, consts, data, labels, targets,
                                 norm, rows[0], valids[0])
            if rows.shape[0] == 1:
                return writes0, met0

            def scan_body(carry, xs):
                row, valid = xs
                return body(carry, consts, data, labels, targets, norm,
                            row, valid)

            writes, mets = lax.scan(scan_body, writes0,
                                    (rows[1:], valids[1:]))
            loss = met0[0] + jnp.sum(mets[0])
            n_err = met0[1] + jnp.sum(mets[1])
            cm = met0[2] + jnp.sum(mets[2], axis=0)
            return writes, (loss, n_err, cm)

        fn = jax.jit(chunk, static_argnames=())
        self._fns_[training] = fn
        return fn

    # -- per-sweep execution --------------------------------------------------
    def _gates_mutated(self):
        for unit, _ in self.members:
            if (_default_skip(unit) and bool(unit.gate_skip)) or \
                    (_default_block(unit) and bool(unit.gate_block)):
                return True
        for unit in self.hosts:
            if bool(unit.gate_skip) or bool(unit.gate_block):
                return True
        return False

    def run(self):
        wf = self.workflow
        loader = wf.loader
        if self._plans_ is None:
            self._build()
        klass = loader.minibatch_class
        training = klass == TRAIN
        matrix = numpy.asarray(loader.minibatch_indices.data)
        valids = numpy.asarray(loader.sweep_valid_sizes, numpy.int32)
        total_valid = max(int(loader.minibatch_valid_size), 1)
        if self._gates_mutated():
            if not getattr(self, "_warned_slow_", False):
                self.warning("%s: a member's default gate was mutated "
                             "after the sweep splice; running per-unit",
                             self.name)
                self._warned_slow_ = True
            # the slow path runs the units against their SLOTS: flush
            # the (possibly lagging) state first, and drop pipelining
            # for good — the slots are always current from here on, and
            # a later advance/rollback must not scatter a stale stash
            self._scatter_state(self._state_)
            self._state_ = {}
            self._eval_stash_ = None
            self._stashed_this_epoch_ = False
            self._wrote_eval_params_ = False
            if self.pipelined:
                self.pipelined = False
                wf.decision.pipeline_depth = 0
            self._run_slow(matrix, valids, training, total_valid)
            self.ticks += 1
            return
        plan = self._plans_[training]
        data = loader.original_data.data
        labels = loader.labels_for_gather()
        targets = getattr(getattr(loader, "original_targets", None),
                          "data", None)
        state = self._state_
        consts = []
        for unit, name in plan.consts:
            slot = getattr(unit, name)
            if isinstance(slot, Array):
                value = state.get(id(slot), slot.data)
                if value is None:
                    raise ValueError("%s: const slot %s.%s is empty"
                                     % (self.name, unit.name, name))
                consts.append(value)
            else:
                consts.append(slot)
        consts = tuple(consts)
        reads = []
        for idx in plan.carry_reads:
            unit, name = plan.writes[idx]
            slot = getattr(unit, name)
            value = state.get(id(slot), slot.data)
            if value is None:
                raise ValueError(
                    "%s: carry slot %s.%s is uninitialized"
                    % (self.name, unit.name, name))
            reads.append(value)
        fn = self._chunk_fn(training)
        chunk = self.chunk if self.hosts else len(matrix)
        chunk = max(chunk, 1)
        loss_sum = n_err_sum = cm_sum = None
        writes = None
        for start in range(0, len(matrix), chunk):
            rows = matrix[start:start + chunk]
            vrow = valids[start:start + chunk]
            writes, (loss, err, cm) = fn(tuple(reads), consts, data,
                                         labels, targets, self._norm_,
                                         rows, vrow)
            reads = [writes[i] for i in plan.carry_reads]
            # lazy device adds: a handful per sweep, settled by the
            # Decision's batched epoch read
            loss_sum = loss if loss_sum is None else loss_sum + loss
            n_err_sum = err if n_err_sum is None else n_err_sum + err
            cm_sum = cm if cm_sum is None else cm_sum + cm
            # host units fire once per tick, between scanned runs — the
            # chunk dispatch above is asynchronous, so the device is
            # already computing while these run
            for _ in range(len(rows)):
                for host in self.hosts:
                    host.run()
        for (unit, name), value in zip(plan.writes, writes):
            state[id(getattr(unit, name))] = value
        if not self.pipelined:
            # scatter every written slot's final value back into the
            # unit Arrays (lazy assignments — snapshotter/export/
            # plotters see graph-mode state at every sweep boundary)
            for (unit, name), value in zip(plan.writes, writes):
                getattr(unit, name).data = value
        else:
            self._rotate_pipelined(loader, training)
        self._publish_metrics(loader, training, loss_sum, n_err_sum,
                              cm_sum, total_valid)
        self.ticks += 1

    def _rotate_pipelined(self, loader, training):
        """Pipelined Array semantics (the FusedTick one-slot history):
        the unit Arrays lag one epoch, holding the weights the
        CURRENTLY-ATTRIBUTED metrics scored, so a Snapshotter firing on
        the lagged ``improved`` captures exactly the scoring state."""
        from veles_tpu.loader.base import VALID
        if not training and loader.epoch_ended_for_class:
            if not self._stashed_this_epoch_:
                current = dict(self._state_)
                if self._eval_stash_ is not None:
                    self._scatter_state(self._eval_stash_)
                self._eval_stash_ = current
                self._stashed_this_epoch_ = True
            self._wrote_eval_params_ = True
        if loader.epoch_ended:
            eval_covers = (self._wrote_eval_params_ and
                           loader.effective_class_lengths[VALID] > 0)
            if training and not eval_covers:
                self._scatter_state(self._state_)
            self._wrote_eval_params_ = False
            self._stashed_this_epoch_ = False

    def _scatter_state(self, state):
        """Write a state snapshot into the unit Arrays (train-plan
        writes are the superset of all written slots)."""
        if not state:
            return
        plan = self._plans_[True] if self._plans_ else None
        if plan is None:
            return
        for unit, name in plan.writes:
            slot = getattr(unit, name)
            value = state.get(id(slot))
            if value is not None:
                slot.data = value

    def advance_eval_params(self):
        """Decision drain hook (see FusedTick.advance_eval_params): a
        multi-epoch drain is about to attribute an improvement to the
        NEWER epoch — advance the Arrays to the state its eval scored."""
        if self._eval_stash_ is not None:
            self._scatter_state(self._eval_stash_)
            self._eval_stash_ = None

    def rollback_speculative(self):
        """A lagged stop arrived after one more epoch was speculatively
        trained: restore the state to the stopping epoch's evaluated
        weights (the one-slot stash holds exactly them)."""
        if self._eval_stash_ is not None:
            self._state_ = self._eval_stash_
            self._eval_stash_ = None

    def sync_params(self):
        """Workflow finished: the final (post-train) state lands in the
        unit Arrays so exports/results/final snapshots see it."""
        self._scatter_state(self._state_)

    def _publish_metrics(self, loader, training, loss_sum, n_err_sum,
                         cm_sum, total_valid):
        """The Decision's sweep-serving contract (the fused engine's):
        ``loss`` holds the sweep AVERAGE, ``n_err``/confusion the sweep
        sums."""
        evaluator = self.workflow.evaluator
        if "loss" in self._metric_slots_:
            evaluator.loss.data = loss_sum / total_valid
        if "n_err" in self._metric_slots_:
            evaluator.n_err.data = n_err_sum
        if not training and self._with_confusion_ and cm_sum is not None:
            evaluator.confusion_matrix.data = cm_sum

    def _run_slow(self, matrix, valids, training, total_valid):
        """Per-row fallback honoring live gate state (a birth gate was
        mutated after the splice): graph-mode unit execution per
        minibatch, sweep-aggregated metrics for the Decision."""
        loader = self.workflow.loader
        evaluator = self.workflow.evaluator
        # the ORIGINAL cycle order saved at enable() time — chain_of
        # would walk the rewired (spliced) graph here
        host_set = set(self.hosts)
        order = [u for u in self.chain_units
                 if u is not self.workflow.decision]
        loss_sum = n_err_sum = cm_sum = None
        for row, valid in zip(matrix, valids):
            loader.fill_minibatch(numpy.asarray(row), int(valid))
            for unit in order:
                if bool(unit.gate_block):
                    break
                if bool(unit.gate_skip):
                    continue
                if unit in host_set:
                    unit.run()
                    continue
                train_only = next(t for u, t in self.members if u is unit)
                if train_only and not training:
                    continue
                unit.run()
            valid_f = float(valid)
            if "loss" in self._metric_slots_:
                part = evaluator.loss.data * valid_f
                loss_sum = part if loss_sum is None else loss_sum + part
            if "n_err" in self._metric_slots_:
                n_err_sum = (evaluator.n_err.data if n_err_sum is None
                             else n_err_sum + evaluator.n_err.data)
            if not training and self._with_confusion_:
                cm = evaluator.confusion_matrix.data
                cm_sum = cm if cm_sum is None else cm_sum + cm
        self._publish_metrics(loader, training, loss_sum, n_err_sum,
                              cm_sum, total_valid)


def enable(workflow, pipelined=False):
    """Splice a FusedSweep over the repeater cycle. Returns the unit, or
    None when the workflow is not sweep-eligible (the caller then tries
    the per-tick segment tier). Call between construction and
    ``initialize()``."""
    info = classify(workflow)
    if info is None:
        return None
    members, hosts = info
    loader = workflow.loader
    decision = workflow.decision
    chain = chain_of(workflow)
    sweep = FusedSweep(workflow, members, hosts, chain,
                       pipelined=pipelined)
    # record what disable() must put back EXACTLY: whether the last
    # chain unit held the EndPoint finish gate (StandardWorkflow wiring;
    # a custom chain may gate the EndPoint on the decision alone), and
    # the loader's original stop gate
    sweep.restore_finish_link = (
        workflow.end_point in chain[-1].links_to)
    sweep.saved_loader_gate = loader.gate_block
    # detaching every non-Decision chain unit also clears its links INTO
    # the repeater and the Decision (unlink_all is bidirectional); the
    # repeater keeps its start_point provider, the Decision keeps its
    # outward links (end_point gate, plotters)
    for unit in chain:
        if unit is not decision:
            unit.unlink_all()
    # the cycle becomes: start -> repeater -> loader -> sweep ->
    # decision -> repeater (end_point keeps its decision link + gate)
    sweep.link_from(loader)
    decision.link_from(sweep)
    workflow.repeater.link_from(decision)
    loader.gate_block = decision.complete
    loader.fill_data = False
    loader.sweep_serving = True
    workflow.sweep_unit = sweep
    return sweep
