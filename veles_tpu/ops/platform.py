"""The one place that asks JAX which platform this process runs on.

Every kernel gate (``ops/gemm``, ``ops/quant``, ``ops/attention``,
``ops/paged_attention``) and the serving bucket policy decide from
these two functions, so "is this the chip?" has one answer and one
spelling. Two platforms exist for this code: ``tpu`` (Pallas kernels
compile through Mosaic) and ``cpu`` (tests and drives; Pallas kernels
run in interpret mode there and only there).
"""

import jax


def on_tpu():
    """True when the default JAX backend is the TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret():
    """Whether a Pallas kernel called on this platform must run in
    interpret mode: never on ``tpu``, always on ``cpu``. Any other
    platform raises — a kernel written for Mosaic has no meaning there
    and guessing would hide which device actually ran."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        "Pallas kernels here target the TPU (compiled) or the CPU "
        "(interpret mode); JAX platform %r is neither — set "
        "JAX_PLATFORMS to tpu or cpu" % backend)
