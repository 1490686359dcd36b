"""Opt-in jax.profiler integration: device traces aligned with spans.

The CLI's ``--profile`` wraps a run
window in ``jax.profiler.trace``; while a capture is active the tracer
also enters a ``jax.profiler.TraceAnnotation`` named after each span
(``Span.__enter__``), so the host-side span timeline and the XLA device
timeline share one clock in TensorBoard/Perfetto — "decode.dispatch"
on a host lane sits over the slot_step_many program on the device
lane. What the capture holds: the span names on the ``/host:CPU``
plane; on the device plane one ``XLA Modules`` event per program run
(``jit_<function>(<hash>)``) and one ``XLA Ops`` event per instruction,
named by the instruction's text. It holds NO ``op_name`` metadata: the
program's ``jax.named_scope``s reach a traced op only through the
scope table (``observe/xla_stats.scope_table``), not through the
capture.

By default everything here degrades to a no-op when jax is unavailable
or the profiler cannot start (a serving box must never crash because a
capture was requested) — the failure is logged, the run continues.
``strict=True`` re-raises instead: a caller that was ASKED for a
capture (the CLI's ``--profile``) must not end without one in silence.
"""

import contextlib
import logging


@contextlib.contextmanager
def profile_window(profile_dir, annotate=True, strict=False):
    """Capture a jax profiler trace of the enclosed window into
    ``profile_dir`` (viewable in TensorBoard or ui.perfetto.dev).
    ``annotate=True`` additionally turns on span-named
    TraceAnnotations for the duration so host spans align with the
    device trace — and ENABLES the tracer for the window if it was
    off (annotations are emitted by real spans; with the tracer
    disabled every instrumented site returns the null span and the
    capture would carry no host names at all). Span events go to
    whatever EventRecorder is configured; none configured means they
    are simply dropped while the annotations still fire.
    ``profile_dir`` of None/"" makes this a no-op — callers wrap
    unconditionally and the flag decides. ``strict`` re-raises a
    profiler that cannot start or cannot write its capture, where the
    default logs it and lets the run go on without one."""
    if not profile_dir:
        yield None
        return
    from veles_tpu.observe.tracing import get_tracer

    tracer = get_tracer()
    saved = tracer.annotate_device
    saved_enabled = tracer.enabled
    log = logging.getLogger("observe.profile")
    try:
        import jax
        profiler_cm = jax.profiler.trace(profile_dir)
        # start INSIDE the guard: jax.profiler.trace constructs lazily
        # and only start_trace (__enter__) touches the filesystem /
        # checks for a concurrent capture
        profiler_cm.__enter__()
    except Exception:
        if strict:
            raise
        log.exception(
            "jax profiler unavailable; continuing without a capture")
        yield None
        return
    if annotate:
        tracer.annotate_device = True
        tracer.enabled = True
    try:
        yield profile_dir
    finally:
        tracer.annotate_device = saved
        tracer.enabled = saved_enabled
        try:
            profiler_cm.__exit__(None, None, None)
        except Exception:
            if strict:
                raise
            log.exception("jax profiler capture failed to finalize; "
                          "the run itself is unaffected")
