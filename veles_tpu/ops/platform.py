"""The one place that asks JAX which platform this process runs on.

The rule for choosing a kernel: the module that owns a kernel has one
function that returns the choice, read off ``on_tpu()``, static shapes
and the sharding or mesh — ``ops/moe.expert_plan``,
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


#: VMEM of a TensorCore by the device's kind, in MiB (Pallas's own
#: table, ``jax._src.pallas.mosaic.tpu_info``, which answers for the
#: default device only). A rule that sizes a kernel's claim reads it
#: through its own module's ``device_kind`` (the seam a test steers);
#: a kind that is not here has no kernel whose fit depends on VMEM:
#: no rule guesses a chip's.
VMEM_MIB = {"TPU v5 lite": 128, "TPU v5e": 128, "TPU v6 lite": 128,
            "TPU v6e": 128, "TPU v5": 64, "TPU v5p": 64, "TPU7x": 64}


def device_kind():
    """The default device's kind as JAX names it (``"TPU v5 lite"``,
    ``"cpu"``): what a rule reads where a kernel's fit depends on the
    chip's generation, not only on its being a TPU."""
    return jax.devices()[0].device_kind
