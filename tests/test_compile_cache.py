"""Where the XLA persistent compile cache lives (core/config.py).

``JAX_COMPILATION_CACHE_DIR`` places it from outside and nothing in
code overrides it; unset, it is ONE fixed directory inside the checkout
— the path is part of every entry's key, so a path that moves with
``VELES_TPU_HOME``, ``$HOME`` or the cwd never hits.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("import veles_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "VELES_TPU_HOME")}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
    proc = subprocess.run([sys.executable, "-c", PROBE], cwd=str(cwd),
                          env=base, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_environment_variable_survives_the_import(tmp_path):
    placed = str(tmp_path / "placed")
    assert _cache_dir(tmp_path, JAX_COMPILATION_CACHE_DIR=placed,
                      VELES_TPU_HOME=str(tmp_path / "home")) == placed


def test_unset_resolves_one_path_inside_the_checkout(tmp_path):
    from veles_tpu.core.config import COMPILE_CACHE_DIR

    assert COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    first = _cache_dir(tmp_path / "a", HOME=str(tmp_path / "a"),
                       VELES_TPU_HOME=str(tmp_path / "home_a"))
    second = _cache_dir(tmp_path / "b", HOME=str(tmp_path / "b"),
                        VELES_TPU_HOME=str(tmp_path / "home_b"))
    assert first == second == COMPILE_CACHE_DIR
