"""The one place that asks JAX which platform this process runs on.

The rule for choosing a kernel: the module that owns a kernel has one
function that returns the choice, read off ``on_tpu()``, static shapes
and the sharding or mesh — ``ops/moe.expert_path``,
``ops/quant.use_int8_kernel``, ``ops/attention.use_flash``,
``ops/paged_attention.use_paged_kernel``,
``ops/slab_attention.use_slab_kernel``. No config key, flag,
argument or file decides; a test that wants the other side patches
that module's ``on_tpu`` (and ``device_kind`` where the rule reads the
chip's generation). Two platforms exist for this code: ``tpu``
(Pallas kernels compile through Mosaic) and ``cpu`` (tests and drives;
Pallas kernels run in interpret mode there and only there).
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


def device_kind():
    """The default device's kind as JAX names it (``"TPU v5 lite"``,
    ``"cpu"``): what a rule reads where a kernel's fit depends on the
    chip's generation, not only on its being a TPU."""
    return jax.devices()[0].device_kind
