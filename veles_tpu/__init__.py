"""veles_tpu — a TPU-native dataflow machine-learning framework.

A from-scratch re-design of the capabilities of Samsung VELES
(``gujunli/veles``) for TPUs: models are Workflows — directed graphs of Units
linked by control and data edges — whose accelerated segments compile into
fused XLA computations via JAX (jit/pjit), with a Pallas kernel where a
rule over the platform and the shapes picks one (``ops/platform.py``),
data/tensor/sequence parallelism over a ``jax.sharding.Mesh`` (ICI
collectives), an elastic host-orchestrated fleet mode over TCP (DCN),
whole-workflow snapshot/resume, plotting/web-status/REST services, genetic
hyperparameter optimization, ensembles, a model hub, and a C++ inference
runtime for exported workflow packages.

Importable API (reference ``veles/__init__.py:126-189``): the package is
callable — ``import veles_tpu; veles_tpu("wf.py", config...)`` runs a
workflow with kwargs mirroring the CLI flags.
"""

import sys

__version__ = "0.1.0"
__license__ = "Apache 2.0"

from veles_tpu.core.config import root, Config  # noqa: F401
from veles_tpu.core.mutable import Bool, LinkableAttribute  # noqa: F401
from veles_tpu.core import prng  # noqa: F401


def __run__(workflow_file, config_file=None, **kwargs):
    from veles_tpu.cli import run_workflow_file
    return run_workflow_file(workflow_file, config_file, **kwargs)


#: discovered plugin modules (reference ``veles.__plugins__`` — the
#: package scanned installed ``veles.*`` namespace packages,
#: ``__init__.py:191-215``); populated lazily by :func:`scan_plugins`
__plugins__ = None


def scan_plugins():
    """Discover and import installed plugins, returning the module list.

    Two conventions (both additive — a plugin only needs to be
    installed, no registration call):

    - top-level modules named ``veles_tpu_<name>`` (the TPU-era
      namespace-package equivalent of the reference's ``veles.*`` scan);
    - ``veles_tpu.plugins`` entry points (the modern packaging idiom).

    Importing a plugin registers its units/loaders through the same
    registry metaclasses every in-tree unit uses, so discovered units
    are immediately constructible by name (StandardWorkflow layer specs,
    mapped loaders, CLI flags). Scanning is lazy — the CLI calls this
    once at startup; library users call it when they want plugins.
    """
    global __plugins__
    if __plugins__ is not None:
        return __plugins__
    import importlib
    import pkgutil

    plugins = []
    for info in pkgutil.iter_modules():
        if info.name.startswith("veles_tpu_"):
            try:
                plugins.append(importlib.import_module(info.name))
            except Exception as e:  # a broken plugin must not kill the CLI
                sys.stderr.write("veles_tpu: plugin %s failed to import: "
                                 "%s\n" % (info.name, e))
    try:
        from importlib.metadata import entry_points
        eps = entry_points()
        group = (eps.select(group="veles_tpu.plugins")
                 if hasattr(eps, "select")
                 else eps.get("veles_tpu.plugins", ()))
        for ep in group:
            try:
                plugins.append(ep.load())
            except Exception as e:
                sys.stderr.write("veles_tpu: plugin entry point %s failed:"
                                 " %s\n" % (ep.name, e))
    except Exception as e:
        # one unrelated distribution with broken metadata can make
        # entry_points() itself raise — say so instead of silently
        # skipping the whole entry-point convention
        sys.stderr.write("veles_tpu: plugin entry-point scan failed: %s\n"
                         % (e,))
    __plugins__ = plugins
    return plugins


class _VelesTPUModule(sys.modules[__name__].__class__):
    """Callable module (reference ``VelesModule``, ``__init__.py:126``)."""

    def __call__(self, workflow_file, config_file=None, **kwargs):
        return __run__(workflow_file, config_file, **kwargs)


sys.modules[__name__].__class__ = _VelesTPUModule
