"""Launcher: process-orchestration for standalone / master / slave runs.

Reference ``veles/launcher.py``. Mode detection mirrors the CLI contract
(``launcher.py:333-342``): ``listen_address`` → master, ``master_address``
→ slave, neither → standalone. The launcher owns the thread pool, builds
the fleet Server/Client, runs the workflow and coordinates shutdown. The
Twisted-reactor main loop becomes a simple event wait — jit dispatch owns
the main thread and asyncio lives in the fleet threads.
"""

import json
import threading

from veles_tpu.core.config import root
from veles_tpu.core.executor import ThreadPool
from veles_tpu.core.logger import Logger


def discover_yarn_nodes(rm_address, timeout=10.0):
    """Resolve a Hadoop/YARN ResourceManager address to the cluster's
    RUNNING node hostnames via its REST API (reference YARN discovery,
    ``launcher.py:887-906`` — the reference asked the RM so ``-n`` could
    target a whole Hadoop cluster without listing hosts by hand)."""
    from urllib.request import urlopen

    url = "http://%s/ws/v1/cluster/nodes?states=RUNNING" % rm_address
    with urlopen(url, timeout=timeout) as resp:
        payload = json.load(resp)
    nodes = (payload.get("nodes") or {}).get("node") or []
    return [n["nodeHostName"] for n in nodes if n.get("nodeHostName")]


class Launcher(Logger):
    """Workflow process driver (reference ``launcher.py:100``)."""

    def __init__(self, listen_address=None, master_address=None,
                 result_file=None, slave_power=1.0, async_slave=False,
                 slave_death_probability=0.0, respawn=False, nodes=None,
                 chaos=None, **kwargs):
        super().__init__(logger_name="Launcher")
        self.respawn = respawn
        #: chaos-harness overrides (dict merged into
        #: root.common.fleet.chaos at initialize; see fleet/chaos.py)
        self.chaos = dict(chaos or {})
        #: hosts to spawn slaves on at master startup (reference
        #: ``-n host`` specs, ``launcher.py:617-660``)
        self.nodes = list(nodes or [])
        self.listen_address = listen_address
        self.master_address = master_address
        self.result_file = result_file
        self.slave_power = slave_power
        self.async_slave = async_slave
        self.slave_death_probability = slave_death_probability
        self.thread_pool = ThreadPool(name="launcher")
        self.workflow = None
        self.agent = None  # Server or Client
        self.graphics_server = None
        self.status_notifier = None
        self._units = []
        self._finished = threading.Event()
        self.stopped = False

    # -- mode flags (reference launcher.py:333-342) --------------------------
    @property
    def is_master(self):
        return self.listen_address is not None

    @property
    def is_slave(self):
        return self.master_address is not None

    @property
    def is_standalone(self):
        return not self.is_master and not self.is_slave

    @property
    def mode(self):
        return ("master" if self.is_master else
                "slave" if self.is_slave else "standalone")

    # -- workflow containment -------------------------------------------------
    def add_ref(self, unit):
        self._units.append(unit)
        self.workflow = unit

    def del_ref(self, unit):
        if unit in self._units:
            self._units.remove(unit)

    # -- lifecycle ------------------------------------------------------------
    def initialize(self, **kwargs):
        if self.workflow is None:
            raise ValueError("no workflow attached to the launcher")
        self.info("launcher mode: %s", self.mode)
        from veles_tpu.parallel.mesh import is_primary, mesh_configured
        primary = is_primary()
        if not root.common.disable.get("plotting", False) \
                and not self.is_slave and primary:
            from veles_tpu.plotting.server import GraphicsServer
            self.graphics_server = GraphicsServer()
        if root.common.web.get("enabled", False) and not self.is_slave \
                and primary:
            from veles_tpu.web_status import StatusNotifier
            self.status_notifier = StatusNotifier(self).start()
        if mesh_configured() and self.is_master:
            self.warning(
                "a device mesh is configured (--mesh / "
                "root.common.mesh.axes) but the master does not run the "
                "compute tick — the mesh is ignored here; configure it "
                "on the slaves (fleet x pod composition)")
        elif mesh_configured():
            # standalone pod mode, or fleet x pod: a SLAVE's local tick
            # runs the shard_map-ped fused step over its own mesh
            # pod mode is a PRODUCT mode: --mesh / root.common.mesh.axes
            # builds the mesh into the workflow before initialize (the
            # fused-tick splice reads it there). In a multi-host pod
            # (jax.distributed) the device list already spans every
            # process. A workflow "supports a mesh" when it carries the
            # mesh_ slot — or, after a snapshot resume, a fused_tick
            # (mesh_ ends in '_' and is stripped by the pickle).
            wf = self.workflow
            supports_mesh = (hasattr(wf, "mesh_")
                             or hasattr(wf, "fused_tick"))
            if not supports_mesh:
                # a requested mesh that cannot be honoured raises (as
                # fused=True does): running on one device would look
                # like a pod run at 1/Nth speed
                raise ValueError(
                    "a device mesh is configured (--mesh / "
                    "root.common.mesh.axes) but %s has no mesh support; "
                    "drop the mesh or run a mesh-capable workflow"
                    % type(wf).__name__)
            if getattr(wf, "mesh_", None) is None:
                import jax
                from veles_tpu.parallel.mesh import build_mesh
                mesh = build_mesh()
                wf.mesh_ = mesh
                tick = getattr(wf, "fused_tick", None)
                if tick is not None:
                    # resumed snapshot: the tick rebuilds its compiled
                    # steps at initialize from this mesh
                    tick.mesh_ = mesh
                self.info(
                    "pod mode: mesh %s over %d devices (%d process(es))",
                    dict(zip(mesh.axis_names, mesh.devices.shape)),
                    mesh.devices.size, jax.process_count())
        self.workflow.initialize(**kwargs)
        if self.is_master:
            from veles_tpu.nn.gd import fleet_merge_mode
            fleet_merge_mode()  # fail fast on a merge-mode typo
            from veles_tpu.fleet.server import Server
            self.agent = Server(
                self.listen_address, self.workflow,
                job_timeout=root.common.fleet.get("job_timeout", 120.0),
                respawn=self.respawn)
            self.agent.on_finished = self._on_agent_finished
            self.agent.start()
            if self.nodes:
                self._launch_nodes()
        elif self.is_slave:
            if self.chaos:
                # launcher-level chaos knobs land in the config tree the
                # Client builds its ChaosMonkey from
                root.common.fleet.chaos.update(self.chaos)
            from veles_tpu.fleet.client import Client
            self.agent = Client(
                self.master_address, self.workflow,
                power=self.slave_power, async_mode=self.async_slave,
                death_probability=self.slave_death_probability,
                enable_respawn=self.respawn,
                max_reconnect_attempts=root.common.fleet.get(
                    "max_reconnect_attempts", 7))
            self.agent.on_finished = self._on_agent_finished
        return self

    def _launch_nodes(self):
        """Spawn a slave on every ``-n`` host at master startup
        (reference SSH slave launch, ``launcher.py:617-660``): this
        process's argv is transformed from master form to slave form
        (drop ``-l``/``-n``, add ``-m <master>``) and launched through
        the respawn spawner — ssh for remote hosts, a detached local
        subprocess for ``localhost``/``127.0.0.1``."""
        import socket
        from veles_tpu.fleet.respawn import (build_command,
                                             default_spawner,
                                             respawn_recipe, spawn_env)

        recipe = respawn_recipe()
        host_part = self.agent.host
        if host_part in ("", "0.0.0.0", "::"):
            host_part = socket.gethostname()
        master = "%s:%d" % (host_part, self.agent.port)
        # master->slave argv transform. Dropped (both the space- and
        # =/fused-separated forms): -l/--listen (the slave must not be
        # a second master), -n/--nodes (no recursive spawning),
        # --result-file (results belong to the master), -b (the spawner
        # already detaches). --respawn is KEPT: it makes the slave ship
        # its relaunch recipe so the master can respawn it on death.
        drop_with_value = ("-l", "--listen", "-n", "--nodes",
                           "--result-file")
        argv = []
        skip = False
        for arg in recipe["argv"]:
            if skip:
                skip = False
                continue
            if arg in drop_with_value:
                skip = True
                continue
            if arg.startswith(tuple(o + "=" for o in drop_with_value)) \
                    or (arg[:2] in ("-l", "-n") and len(arg) > 2
                        and not arg.startswith("--")):
                continue  # --opt=value / fused -lVALUE forms
            if arg in ("-b", "--background"):
                continue
            argv.append(arg)
        argv += ["-m", master]
        command = build_command(recipe["executable"], argv)
        env = spawn_env(recipe["pythonpath"]) or {}
        # env-/explicitly-sourced secrets don't travel with the workflow
        # source the way config/checksum ones do — forward them
        # (getattr: test fakes implement only the Server surface they use)
        env.update(getattr(self.agent, "secret_spawn_env", dict)())
        for host in self._expand_node_specs(self.nodes):
            self.info("launching slave on %s", host)
            default_spawner(host, command, cwd=recipe["cwd"], env=env)

    def _expand_node_specs(self, specs):
        """``yarn://rm-host:port`` entries expand to the cluster's
        RUNNING nodes via the ResourceManager REST API; plain hosts pass
        through. A failed discovery logs and skips the spec rather than
        killing the master — the fleet is elastic, hosts can be added
        later."""
        hosts = []
        for spec in specs:
            if spec.startswith("yarn://"):
                try:
                    found = discover_yarn_nodes(spec[len("yarn://"):])
                    self.info("yarn discovery %s: %d node(s)", spec,
                              len(found))
                    hosts.extend(found)
                except Exception as e:
                    self.warning("yarn discovery %s failed: %s", spec, e)
            else:
                hosts.append(spec)
        return hosts

    def run(self):
        """Blocks until the workflow completes (reference ran the reactor
        here). Never clears ``_finished`` — the fleet agent started by
        ``initialize()`` may legitimately complete before run() is called."""
        if self.is_standalone:
            self.workflow.run()
            self._write_results()
            return self
        if self.is_slave:
            self.agent.start()
        # master: the Server thread drives everything; wait for the
        # EndPoint/agent to signal completion
        self._finished.wait()
        self._write_results()
        return self

    def on_workflow_finished(self):
        """Called by the workflow's EndPoint chain (master/standalone)."""
        self._finished.set()

    def _on_agent_finished(self):
        self._finished.set()

    def stop(self):
        if self.stopped:
            return
        self.stopped = True
        if self.agent is not None:
            self.agent.stop()
        if self.status_notifier is not None:
            self.status_notifier.stop()
        if self.graphics_server is not None:
            self.graphics_server.flush()
            self.graphics_server.shutdown()
        self.thread_pool.shutdown()
        self._finished.set()

    # -- results (reference --result-file) ------------------------------------
    def _write_results(self):
        if not self.result_file or self.is_slave:
            return
        from veles_tpu.parallel.mesh import is_primary
        if not is_primary():
            return  # one result file per pod, owned by process 0
        results = self.workflow.gather_results()
        with open(self.result_file, "w") as fout:
            json.dump(results, fout, indent=1, default=str)
        self.info("results written to %s", self.result_file)
