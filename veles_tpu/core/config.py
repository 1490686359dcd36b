"""Self-materializing dotted configuration tree.

TPU-native re-design of the reference config system (``veles/config.py:52-290``
and ``veles/site_config.py``): a ``Config`` node materializes child nodes on
attribute access so workflow config files can write ``root.mnist.learning_rate
= 0.01`` without declaring intermediate nodes. Supports nested ``update()``,
``protect()``-ed read-only keys, layered site overrides, and pretty printing.

Unlike the reference, engine defaults here describe the XLA/TPU engine
(precision/dtype policy, mesh defaults) instead of
OpenCL/CUDA block sizes.
"""

import json
import os
import pprint

from veles_tpu.core.errors import VelesError


class ConfigError(VelesError):
    pass


_PROTECTED = "_protected_"
_NAME = "_name_"


class Config:
    """A node in the configuration tree (reference ``config.py:52``)."""

    def __init__(self, path):
        object.__setattr__(self, _NAME, path)
        object.__setattr__(self, _PROTECTED, set())

    # -- materialization ----------------------------------------------------
    def __getattr__(self, name):
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        child = Config("%s.%s" % (object.__getattribute__(self, _NAME), name))
        object.__setattr__(self, name, child)
        return child

    def __setattr__(self, name, value):
        if name in object.__getattribute__(self, _PROTECTED):
            raise ConfigError(
                "Config key %s.%s is protected" % (self.__path__, name))
        object.__setattr__(self, name, value)

    # -- public API ---------------------------------------------------------
    @property
    def __path__(self):
        return object.__getattribute__(self, _NAME)

    def update(self, value=None, **kwargs):
        """Deep-merge a nested dict (or kwargs) into this subtree
        (reference ``config.py:156-176``)."""
        if value is None:
            value = kwargs
        if isinstance(value, Config):
            value = value.__content__()
        if not isinstance(value, dict):
            raise ConfigError(
                "Can only update %s from a dict, got %r"
                % (self.__path__, value))
        for key, val in value.items():
            if isinstance(val, dict):
                try:
                    node = object.__getattribute__(self, key)
                except AttributeError:
                    node = None
                if not isinstance(node, Config):
                    # a leaf is being deepened into a subtree: replace it
                    node = Config("%s.%s" % (self.__path__, key))
                    setattr(self, key, node)
                node.update(val)
            else:
                setattr(self, key, val)
        return self

    def protect(self, *names):
        """Make keys read-only (reference ``config.py`` protect())."""
        object.__getattribute__(self, _PROTECTED).update(names)

    def get(self, name, default=None):
        """Return the value of ``name`` without materializing it."""
        try:
            value = object.__getattribute__(self, name)
        except AttributeError:
            return default
        if isinstance(value, Config):
            return default
        return value

    def __contains__(self, name):
        try:
            return not isinstance(object.__getattribute__(self, name), Config)
        except AttributeError:
            return False

    def __content__(self):
        result = {}
        for key, value in vars(self).items():
            if key in (_NAME, _PROTECTED):
                continue
            if isinstance(value, Config):
                result[key] = value.__content__()
            else:
                result[key] = value
        return result

    def print_(self, stream=None):
        pprint.pprint({self.__path__: self.__content__()}, stream=stream)

    def __repr__(self):
        return "<Config %s: %s>" % (
            self.__path__, pprint.pformat(self.__content__()))


def validate_kwargs(caller, **kwargs):
    """Warn about Config nodes leaking in as kwargs values
    (reference ``config.py:164``): an unset config path materializes as a
    Config instance rather than a value, which is almost always a typo."""
    for name, value in kwargs.items():
        if isinstance(value, Config):
            raise ConfigError(
                "%s: keyword %r is an unset config node %s — probably a typo "
                "in your config file" % (caller, name, value.__path__))


#: The global configuration root, like reference ``config.py:151``.
root = Config("root")

#: All framework cache/state dirs live under this; VELES_TPU_HOME relocates
#: them (tests point it at a tmpdir).
_home = os.path.expanduser(os.environ.get("VELES_TPU_HOME", "~/.veles_tpu"))

# -- engine defaults (TPU edition of reference config.py:177-290) -----------
root.common.update({
    "dirs": {
        "cache": os.path.join(_home, "cache"),
        "snapshots": os.path.join(_home, "snapshots"),
        "datasets": os.path.join(_home, "datasets"),
        "events": os.path.join(_home, "events"),
        # runtime sockets (manhole) live here, one per pid
        "run": os.path.join(_home, "run"),
    },
    "engine": {
        # compute dtype policy: matmuls/convs run in bfloat16 on the MXU with
        # float32 accumulation; params kept in float32.
        "compute_dtype": "bfloat16",
        "param_dtype": "float32",
        # precision levels mirror reference config.py:244-247:
        # 0 - default MXU precision, 1 - float32 inputs ("Kahan" tier),
        # 2 - highest XLA precision (multi-partial tier).
        "precision_level": 0,
        "donate_params": True,
    },
    "mesh": {
        # logical mesh axes; sizes resolve against the actual device
        # count at Mesh build time (parallel/mesh.py). ALL ones = pod
        # mode off; any non-1 axis (e.g. --mesh data=-1 to absorb every
        # device) makes the launcher build the mesh into the workflow —
        # pod mode is explicit, not ambient (a data=-1 default would put
        # every standalone run on every visible device silently).
        "axes": {"data": 1, "model": 1, "seq": 1, "expert": 1, "pipe": 1},
    },
    "trace": {"run": False},
    "timings": False,
    "disable": {"plotting": False, "publishing": False, "snapshotting": False},
    "web": {"enabled": False, "host": "localhost", "port": 8090,
            "notification_interval": 1.0},
    "api": {"port": 8180, "path": "/api"},
    # serving survival layer (docs/serving_robustness.md): admission
    # bound (max_queue <= 0 disables load shedding), default
    # per-request deadline, breaker rebuild backoff, and the serving
    # chaos harness (serving_chaos.py, --chaos-serve-*)
    "serve": {
        "max_queue": 64,
        "deadline": 300.0,
        "rebuild_backoff": 0.5,
        "rebuild_backoff_max": 30.0,
    },
    "fleet": {
        "job_timeout": 120.0,
        "sync_interval": 1.0,
        "max_reconnect_attempts": 7,
        # wire serialization: "pickle" (default; arbitrary payloads) or
        # "safe" (pickle-free — a leaked fleet secret is then data
        # injection at worst, not code execution). Set IDENTICALLY on
        # every fleet host; see fleet/safecodec.py.
        "codec": "pickle",
    },
    "forge": {"service_name": "forge", "manifest": "manifest.json",
              "server": "http://127.0.0.1:8190"},
})


def site_config_paths():
    """Where site overrides are looked for: /etc, $HOME and the CWD."""
    return ("/etc/default/veles_tpu.json",
            os.path.expanduser("~/.veles_tpu/site_config.json"),
            os.path.join(os.getcwd(), "site_config.json"))


def _apply_site_overrides():
    """Layered site configuration (reference ``site_config.py`` and
    ``config.py:292-307``): JSON overrides merged from /etc, $HOME and CWD."""
    import sys
    for path in site_config_paths():
        try:
            with open(path, "r") as fin:
                overrides = json.load(fin)
        except (OSError, ValueError):
            continue
        try:
            root.update(overrides)
        except Exception as exc:
            # a malformed override must not break `import veles_tpu`
            print("veles_tpu: ignoring bad site config %s: %s"
                  % (path, exc), file=sys.stderr)


_apply_site_overrides()


#: The XLA persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: does not place it: one fixed directory inside the checkout, resolved
#: from this file. The path is part of every entry's key, so it must not
#: move with ``VELES_TPU_HOME``, ``$HOME``, the cwd, a pid or the time —
#: a cache that moves never hits (the TPU-era descendant of the
#: reference's kernel binary cache, accelerated_units.py:605-673).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _place_compilation_cache():
    """Point jax at the persistent compilation cache before the first
    compilation; importing veles_tpu does it. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax has already read it and
    nothing is set in code. A directory that cannot be created raises:
    on the chip a cold compile of every program is an error, not a
    mode."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


_place_compilation_cache()
