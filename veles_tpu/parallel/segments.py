"""Partial fusion: compile maximal jit-able runs of the tick chain.

SURVEY §7's hard part "tick fusion vs dynamic gates", second tier. The
full :mod:`veles_tpu.parallel.fused` engine recognizes the standard
forward/GD topology and compiles whole class sweeps; everything it
declines used to fall all the way to per-unit graph dispatch (a
"170x cliff"). This module closes the gap for ANY workflow
whose compute units are :class:`~veles_tpu.nn.jit_unit.JitUnit`\\ s:

- the repeater cycle is extracted as a linear unit chain;
- maximal runs of consecutive JitUnits with compatible gates collapse
  into one :class:`FusedSegment` each — a single jitted composite of the
  member ``compute()`` functions, chained through the shared Array
  slots, ONE XLA dispatch per tick instead of one per unit;
- host units (a custom unit spliced into the chain, the Decision, a
  non-standard evaluator's host logic) stay host-side between segments,
  preserving the reference's per-unit control semantics
  (``workflow.py:347-365``) exactly.

The partition rule for gates mirrors the reference's runtime gate
checks: members may join a segment only when they carry the IDENTICAL
``(gate_skip, gate_block)`` signature — the very same workflow-assigned
Bool objects, or both untouched birth gates. The per-tick gate decision
then applies to the whole segment at once — identical to graph mode,
where the shared Bool would have gated every member individually.

Numerical identity with graph mode is structural: the composite calls
the same bound ``compute()`` methods on the same inputs in the same
order — only the dispatch granularity changes (``tests/test_segments.py``
proves weight equality).
"""

import jax

from veles_tpu.core.mutable import Bool
from veles_tpu.core.units import Unit
from veles_tpu.memory import Array
from veles_tpu.nn.jit_unit import JitUnit


def chain_of(workflow):
    """The repeater cycle as an ordered unit list, starting at the unit
    the loader fires (the loader itself stays host — it owns serving).
    Returns None when the cycle is not a linear chain (fan-out inside
    the cycle is graph-mode territory)."""
    loader = getattr(workflow, "loader", None)
    repeater = getattr(workflow, "repeater", None)
    if loader is None or repeater is None:
        return None
    # "unit can reach the repeater along links_to without passing
    # through the loader" == one reverse BFS from the repeater over
    # links_from that never expands THROUGH the loader: O(V+E) once,
    # instead of a fresh forward DFS per query
    reaches = {repeater}
    frontier = [repeater]
    while frontier:
        node = frontier.pop()
        if node is loader:
            continue  # the loader may start a path, never sit inside one
        for prev in node.links_from:
            if prev not in reaches:
                reaches.add(prev)
                frontier.append(prev)

    chain = []
    current = loader
    while True:
        successors = [u for u in current.links_to
                      if u is not repeater and u in reaches]
        if current.links_to.get(repeater) and not successors:
            return chain  # closed the cycle
        if len(successors) != 1:
            return None  # fan-out inside the cycle (or a dead end)
        current = successors[0]
        if current in chain or current is loader:
            return None  # inner cycle that is not the repeater loop
        chain.append(current)


def _default_skip(unit):
    """True when the unit still carries its untouched birth gate — the
    workflow never assigned a control Bool, so in graph mode nothing
    would flip it between ticks. (Identity, not value: a shared control
    Bool like ``decision.gd_skipped`` is False at enable() time but
    toggles every tick.) A runtime safety net in FusedSegment.run still
    catches direct ``.set()`` mutation of a birth gate."""
    return unit.gate_skip is getattr(unit, "_born_gate_skip", None)


def _default_block(unit):
    return unit.gate_block is getattr(unit, "_born_gate_block", None)


def _gate_signature(unit):
    return (None if _default_skip(unit) else id(unit.gate_skip),
            None if _default_block(unit) else id(unit.gate_block))


def _fusible(unit):
    """A unit the composite can trace: a JitUnit with a real compute()
    and declared slots (custom JitUnits qualify automatically)."""
    return (isinstance(unit, JitUnit)
            and type(unit).compute is not JitUnit.compute
            and not getattr(unit, "no_fusion", False))


def partition(chain):
    """Split the chain into runs: ``[("segment", [units...]) |
    ("host", unit), ...]``. A segment extends while members are fusible
    and their gates are compatible (same non-default Bool objects, or
    constant-false defaults)."""
    result = []
    run = []
    run_sig = None

    def flush():
        nonlocal run, run_sig
        if len(run) >= 2:
            result.append(("segment", run))
        else:
            result.extend(("host", u) for u in run)
        run, run_sig = [], None

    for unit in chain:
        if not _fusible(unit):
            flush()
            result.append(("host", unit))
            continue
        sig = _gate_signature(unit)
        if run and sig != run_sig:
            # EXACT signature match only: letting a default-gate unit
            # join a run that adopts a neighbor's control Bool would
            # skip/block it when that Bool fires — graph mode would have
            # run it (correctness beats fusion greed here)
            flush()
        run.append(unit)
        run_sig = sig
    flush()
    return result


class FusedSegment(Unit):
    """One jitted composite of a run of consecutive JitUnits.

    The members stay constructed (they own the weights, serve the fleet
    and export paths, and remain the user's composition API) but are
    detached from the control graph; this unit takes their place and
    executes their chained computes as one XLA dispatch. Slot traffic is
    preserved: external inputs are read from the members' Array slots at
    call time, results are scattered back into the members' output
    slots, so everything outside the segment (Decision accumulators,
    plotters, Snapshotter, the fleet's generate/apply) sees exactly the
    graph-mode state.
    """

    hide_from_registry = True
    VIEW_GROUP = "WORKER"
    #: execution strategy, not topology (see Workflow.checksum)
    EPHEMERAL = True

    def __init__(self, workflow, members, **kwargs):
        kwargs.setdefault("name", "segment[%s..%s]"
                          % (members[0].name, members[-1].name))
        super().__init__(workflow, **kwargs)
        self.members = list(members)

    def init_unpickled(self):
        super().init_unpickled()
        self._plan_ = None
        self._jitted_ = None

    def _build_plan(self):
        """Static dataflow plan over the members' slot graph. Array slots
        are keyed by OBJECT identity — ``link_attrs`` shares the Array
        objects, so a producer's output slot IS the consumer's input
        slot."""
        ext = []        # (unit, attr) fetched at call time
        ext_index = {}  # id(Array) | (unit id, attr) -> ext position
        produced = {}   # id(Array) -> value-env position (last writer)
        steps = []      # (unit, in_refs, out_positions)
        n_values = 0
        for unit in self.members:
            in_refs = []
            for name in unit.INPUTS:
                slot = getattr(unit, name)
                if isinstance(slot, Array):
                    key = id(slot)
                    if key in produced:
                        in_refs.append((True, produced[key]))
                        continue
                else:
                    key = (id(unit), name)
                if key not in ext_index:
                    ext_index[key] = len(ext)
                    ext.append((unit, name))
                in_refs.append((False, ext_index[key]))
            outs = []
            for name in unit.OUTPUTS:
                slot = getattr(unit, name)
                pos = n_values
                n_values += 1
                if isinstance(slot, Array):
                    produced[id(slot)] = pos
                outs.append(pos)
            steps.append((unit, in_refs, outs))
        # scatter the FINAL value of every written slot (identity-deduped:
        # a slot rewritten later in the chain scatters once)
        scatter = []
        seen = set()
        for unit, _, outs in steps:
            for name, pos in zip(unit.OUTPUTS, outs):
                slot = getattr(unit, name)
                key = id(slot) if isinstance(slot, Array) else (id(unit),
                                                                name)
                if isinstance(slot, Array) and produced[key] != pos:
                    continue  # overwritten later in the segment
                if key in seen:
                    continue
                seen.add(key)
                scatter.append((unit, name, pos))
        self._plan_ = (ext, steps, scatter, n_values)

    def _build_jitted(self):
        ext, steps, scatter, n_values = self._plan_

        def composite(ext_values):
            env = [None] * n_values
            for unit, in_refs, outs in steps:
                args = [env[i] if internal else ext_values[i]
                        for internal, i in in_refs]
                res = unit.compute(*args)
                if len(outs) == 1:
                    res = (res,)
                for pos, val in zip(outs, res):
                    env[pos] = val
            return tuple(env[pos] for _, _, pos in scatter)

        self._jitted_ = jax.jit(composite)

    def run(self):
        for member in self.members:
            if (member.gate_skip is getattr(member, "_born_gate_skip",
                                            None)
                    and bool(member.gate_skip)) or (
                    member.gate_block is getattr(member,
                                                 "_born_gate_block", None)
                    and bool(member.gate_block)):
                # somebody .set() a birth gate the partition classified
                # as constant: honor graph semantics on the slow path
                if not getattr(self, "_warned_slow_", False):
                    self.warning("%s: a member's default gate was "
                                 "mutated after fusion; falling back to "
                                 "per-unit dispatch", self.name)
                    self._warned_slow_ = True
                for unit in self.members:
                    if bool(unit.gate_block):
                        return
                    if not bool(unit.gate_skip):
                        unit.run()
                return
        if self._plan_ is None:
            self._build_plan()
            self._build_jitted()
        ext, steps, scatter, _ = self._plan_
        values = []
        for unit, name in ext:
            slot = getattr(unit, name)
            if isinstance(slot, Array):
                if slot.data is None:
                    raise ValueError("%s: input slot %s.%s is empty"
                                     % (self.name, unit.name, name))
                values.append(slot.data)
            else:
                values.append(slot)
        results = self._jitted_(tuple(values))
        for (unit, name, _), value in zip(scatter, results):
            slot = getattr(unit, name)
            if isinstance(slot, Array):
                slot.data = value
            else:
                setattr(unit, name, value)


def enable(workflow):
    """Splice FusedSegments into the workflow's repeater cycle. Returns
    the list of created segments ([] when nothing fused — not a linear
    cycle, or no run of 2+ compatible JitUnits). Call between
    construction and ``initialize()`` (StandardWorkflow does this
    automatically when the full fused engine declines)."""
    chain = chain_of(workflow)
    if not chain:
        return []
    parts = partition(chain)
    if not any(kind == "segment" for kind, _ in parts):
        return []
    repeater = workflow.repeater
    segments = []
    # rebuild the cycle's control links: predecessors of the first
    # member outside the segment now fire the segment, and the segment
    # fires the last member's outside successors
    for kind, payload in parts:
        if kind != "segment":
            continue
        members = payload
        member_set = set(members)
        segment = FusedSegment(workflow, members)
        # segment gates = the members' shared (non-default) gates
        # (partition guarantees every member carries the SAME pair)
        for member in members:
            if not _default_skip(member):
                segment.gate_skip = member.gate_skip
            if not _default_block(member):
                segment.gate_block = member.gate_block
        # rewire ALL outside links of EVERY member, not just the chain
        # endpoints: a monitor hanging off a mid-segment member must
        # still fire (after the segment — its data is final then), and
        # an outside provider into a mid-segment member still holds the
        # segment's AND gate
        predecessors, successors = [], []
        for member in members:
            predecessors.extend(u for u in member.links_from
                                if u not in member_set
                                and u not in predecessors)
            successors.extend(u for u in list(member.links_to)
                              if u not in member_set
                              and u not in successors)
        segment.link_from(*predecessors)
        for successor in successors:
            successor.link_from(segment)
        for member in members:
            member.unlink_all()
        segments.append(segment)
    _ = repeater  # the cycle closes through the existing repeater links
    return segments
