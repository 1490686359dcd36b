"""Fused Pallas paged-attention kernel (docs/paged_kv.md "The fused
kernel"): the kernel tier must stream bit-identical tokens to the
page-table gather path it replaces — proven on CPU via Pallas
interpret mode (bf16/f32 and int8-KV, staggered mid-flight joins,
shared-prefix tail and hit admissions) — plus the fast CPU invariants:
the capability-probe fallback matrix, the kernel math vs the masked
reference attend, the ragged admission path's single-dispatch /
no-duplication / exact-page-allocation contract, tile_pad waste
accounting with span/page overshoot pinned 0, and the warmed-sweep
zero-retrace guard under the existing ``paged.*`` program names.
`make paged-kernel` runs this file standalone (the interpret-mode
composites ride the `slow` marker so tier-1 keeps its timeout
margin)."""

import math

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.observe.xla_stats import get_compile_tracker
from veles_tpu.ops import paged_attention as pgatt
from veles_tpu.parallel.kv_pool import pages_for
from veles_tpu.parallel.transformer_step import init_transformer_params
from veles_tpu.serving import ContinuousDecoder

pytestmark = pytest.mark.paged_kernel

PS = 8  # page size: tiny so short prompts span several pages


@pytest.fixture
def force_kernel():
    """Engage the kernel tier on CPU (Pallas interpret mode) and clear
    the jit caches both ways: the jitted paged step reads the probe at
    TRACE time, so a cached gather-path program would otherwise keep
    serving after the toggle."""
    prev = pgatt.FORCE_PAGED_KERNEL
    pgatt.FORCE_PAGED_KERNEL = True
    jax.clear_caches()
    yield
    pgatt.FORCE_PAGED_KERNEL = prev
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 16, 11
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(
        rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
    return params, table, heads, vocab


class TestCapabilityProbe:
    """The rule (``use_paged_kernel``): the platform and the mesh
    decide; the one seam stands in for the platform, never for the
    mesh (``tests/test_ops.py::TestKernelRules`` has the rule's own
    matrix)."""

    def test_force_toggle_wins(self):
        from veles_tpu.parallel.mesh import build_mesh
        mesh = build_mesh(devices=jax.devices()[:2], data=1, model=2)
        prev = pgatt.FORCE_PAGED_KERNEL
        try:
            pgatt.FORCE_PAGED_KERNEL = True
            assert pgatt.use_paged_kernel() is True
            # under a mesh the answer is the gather whatever the seam
            assert pgatt.use_paged_kernel(mesh) is False
            pgatt.FORCE_PAGED_KERNEL = False
            assert pgatt.use_paged_kernel() is False
        finally:
            pgatt.FORCE_PAGED_KERNEL = prev

    def test_backend_auto_gathers_off_tpu(self):
        # the CPU test env: the rule falls back to the gather path
        assert jax.default_backend() == "cpu"
        assert pgatt.use_paged_kernel() is False

    def test_decoder_resolves_probe(self, model, monkeypatch):
        """``decoder.paged_kernel`` is a fact derived from the rule,
        not an argument."""
        params, table, heads, _ = model
        kw = dict(slots=2, max_len=32, page_size=PS)
        auto = ContinuousDecoder(params, table, heads, paged=True, **kw)
        assert auto.paged_kernel is False  # the CPU
        with pytest.raises(TypeError):
            ContinuousDecoder(params, table, heads, paged=True,
                              paged_kernel=True, **kw)
        monkeypatch.setattr(pgatt, "on_tpu", lambda: True)
        chip = ContinuousDecoder(params, table, heads, paged=True, **kw)
        assert chip.paged_kernel is True
        dense = ContinuousDecoder(params, table, heads, slots=2,
                                  max_len=32)
        assert dense.paged_kernel is False  # meaningless without paged


class TestKernelMath:
    """paged_attend / paged_attend_int8 (interpret mode) vs the masked
    reference softmax over the gathered span — ragged lengths, scratch
    pages in the dead page-table tail."""

    def _problem(self, heads=4, head_dim=8, slots=3, pb=3,
                 pool_pages=10):
        rng = numpy.random.RandomState(7)
        q = rng.randn(slots, heads, head_dim).astype(numpy.float32)
        k = rng.randn(pool_pages, PS, heads, head_dim).astype(
            numpy.float32)
        v = rng.randn(pool_pages, PS, heads, head_dim).astype(
            numpy.float32)
        # live pages 1.. + SCRATCH_PAGE-padded dead tail, ragged
        # lengths crossing page boundaries (incl. length 0: position
        # 0 visible, the append-precedes-attend contract)
        page_table = numpy.zeros((slots, pb), numpy.int32)
        lengths = numpy.asarray([0, PS, 2 * PS + 3], numpy.int32)
        nxt = 1
        for s in range(slots):
            for p in range(int(lengths[s]) // PS + 1):
                page_table[s, p] = nxt
                nxt += 1
        return q, k, v, page_table, lengths

    @staticmethod
    def _reference(q, kg, vg, lengths):
        slots, span = kg.shape[0], kg.shape[1]
        mask = numpy.arange(span)[None, :] <= lengths[:, None]
        s = numpy.einsum("shd,skhd->shk", q, kg) \
            / math.sqrt(float(q.shape[-1]))
        s = numpy.where(mask[:, None, :], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = numpy.exp(s)
        p = p / p.sum(axis=-1, keepdims=True)
        return numpy.einsum("shk,skhd->shd", p, vg)

    def test_float_matches_reference(self):
        q, k, v, pt, lens = self._problem()
        out = pgatt.paged_attend(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(pt),
                                 jnp.asarray(lens), page_size=PS,
                                 interpret=True)
        kg = k[pt].reshape(3, -1, 4, 8)
        vg = v[pt].reshape(3, -1, 4, 8)
        ref = self._reference(q, kg, vg, lens)
        numpy.testing.assert_allclose(numpy.asarray(out), ref,
                                      rtol=1e-5, atol=1e-5)

    def test_int8_matches_dequant_reference(self):
        from veles_tpu.parallel.decode import _quantize_kv
        q, k, v, pt, lens = self._problem()
        # the pool's quantization: per-(page, position, head) over D,
        # then head-major (P, H, D, ps) quants + (P, H, ps) scales
        k8, ks = _quantize_kv(jnp.asarray(k))     # (P,ps,H,D), (P,ps,H)
        v8, vs = _quantize_kv(jnp.asarray(v))
        inv = 1.0 / math.sqrt(float(q.shape[-1]))
        out = pgatt.paged_attend_int8(
            jnp.asarray(q) * inv,
            jnp.transpose(k8, (0, 2, 3, 1)),
            jnp.transpose(ks, (0, 2, 1)),
            jnp.transpose(v8, (0, 2, 3, 1)),
            jnp.transpose(vs, (0, 2, 1)),
            jnp.asarray(pt), jnp.asarray(lens), page_size=PS,
            interpret=True)
        kd = numpy.asarray(k8, numpy.float32) \
            * numpy.asarray(ks)[..., None]
        vd = numpy.asarray(v8, numpy.float32) \
            * numpy.asarray(vs)[..., None]
        kg = kd[pt].reshape(3, -1, 4, 8)
        vg = vd[pt].reshape(3, -1, 4, 8)
        ref = self._reference(q, kg, vg, lens)
        numpy.testing.assert_allclose(numpy.asarray(out), ref,
                                      rtol=1e-4, atol=1e-4)


class TestTilePadAccounting:
    """The waste-plane satellite: the kernel's residual is the last
    partial page's dead lanes, never a silently-zeroed overshoot."""

    def test_tile_pad_tokens_matches_brute_force(self):
        from veles_tpu.parallel.decode import tile_pad_tokens
        rng = numpy.random.RandomState(0)
        for _ in range(25):
            lens = rng.randint(0, 40, size=3)
            ps = int(rng.choice([4, 8, 16]))
            chunk = int(rng.randint(1, 6))
            brute = 0
            for n in lens:
                for i in range(1, chunk + 1):
                    live = int(n) + i  # live to n+i-1, attends n+i pos
                    pages = (live - 1) // ps + 1
                    brute += pages * ps - live
            assert tile_pad_tokens(lens, ps, chunk) == brute

    def test_note_dispatch_books_tile_pad(self):
        from veles_tpu.observe.servescope import ServeScope
        scope = ServeScope()
        scope.note_dispatch(2, 4, 3, 11, 0.001, paged=True, pages=3,
                            kernel=True)
        assert scope.waste["tile_pad"] == 11
        assert scope.waste["page_overshoot"] == 0
        assert scope.waste["span_overshoot"] == 0
        # the accounting ring names the kernel mode
        assert scope.debug_snapshot()["dispatches"][-1][1] == "kernel"


class TestRaggedAdmission:
    """The pow2 ladder only exists to bound the gather path's jit
    cache: on the kernel path one mixed-length wave admits in ONE
    dispatch, no duplicate rows, each row owning exactly its pages."""

    def test_single_dispatch_exact_pages(self, model, force_kernel):
        params, table, heads, vocab = model
        rng = numpy.random.RandomState(2)
        dec = ContinuousDecoder(params, table, heads, slots=3,
                                max_len=32, n_tokens=4, paged=True,
                                page_size=PS)
        base = dict(dec.scope.waste)
        prompts = [rng.randint(0, vocab, n) for n in (3, 9, 17)]
        rids = [dec.submit(p, 2) for p in prompts]
        dec.step()
        # three bucket-distinct lengths, ONE ragged admission program
        assert dec.dispatch_counts["admit"] == 1
        assert dec.dispatch_counts["admit_requests"] == 3
        by_rid = {rid: prompt for rid, prompt in zip(rids, prompts)}
        for slot, rid in dec._slot_req.items():
            assert len(dec._slot_pages[slot]) == \
                pages_for(len(by_rid[rid]), PS)
        waste = {k: v - base.get(k, 0)
                 for k, v in dec.scope.waste.items()}
        assert waste["group_dup"] == 0
        # width = page-rounded max (17 -> 24): residual pad only
        assert waste["bucket_pad"] == (24 - 3) + (24 - 9) + (24 - 17)
        assert waste["span_overshoot"] == 0
        assert waste["page_overshoot"] == 0
        assert waste["tile_pad"] > 0

    def test_tail_allocates_exact_pages(self, model, force_kernel):
        params, table, heads, vocab = model
        rng = numpy.random.RandomState(3)
        system = rng.randint(0, vocab, 2 * PS)
        extended = numpy.concatenate(
            [system, rng.randint(0, vocab, 3)])
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=48, n_tokens=2, paged=True,
                                page_size=PS)
        dec.submit(system, 2)
        dec.run_until_drained()
        rid = dec.submit(extended, 2)
        dec.step()
        assert dec.dispatch_counts["admit_tail"] == 1
        slot = next(s for s, r in dec._slot_req.items() if r == rid)
        # 2 shared prefix pages + exactly ONE ragged tail page (the
        # gather ladder would round the 3-token tail to its bucket)
        assert len(dec._slot_pages[slot]) == 3


@pytest.mark.slow
class TestKernelBitIdentity:
    """The acceptance composite: the kernel tier must reproduce the
    gather path's streams exactly — and both must equal greedy
    generate() — through staggered mid-flight joins and shared-prefix
    tail/hit admissions, on both KV tiers (interpret mode: emulated
    but bit-faithful kernel semantics)."""

    def _drive(self, model, quantize, force):
        params, table, heads, vocab = model
        prev = pgatt.FORCE_PAGED_KERNEL
        pgatt.FORCE_PAGED_KERNEL = force
        jax.clear_caches()
        try:
            rng = numpy.random.RandomState(1)
            prompts = [rng.randint(0, vocab, n)
                       for n in (5, 3, 16, 4, 9)]
            dec = ContinuousDecoder(params, table, heads, slots=2,
                                    max_len=32, n_tokens=6,
                                    quantize=quantize, paged=True,
                                    page_size=PS)
            base = dict(dec.scope.waste)
            pending = list(prompts)
            for _ in range(2):
                dec.submit(pending.pop(0))
            dec.drain_pipelined(
                4, admit=lambda dec=dec, pending=pending:
                    pending and dec.submit(pending.pop(0)))
            # shared-prefix families: the page-aligned prompt 2 (len
            # 16) re-admits as a HIT, its 3-token extension as a TAIL
            # (bf16 only: the int8 pool takes exact hits only)
            extra = [numpy.asarray(prompts[2])]
            if quantize is None:
                extra.append(numpy.concatenate(
                    [prompts[2], rng.randint(0, vocab, 3)]))
            for p in extra:
                dec.submit(p, 4)
            dec.run_until_drained(chunk=4)
            waste = {k: v - base.get(k, 0)
                     for k, v in dec.scope.waste.items()}
            return dec, prompts + extra, waste
        finally:
            pgatt.FORCE_PAGED_KERNEL = prev
            jax.clear_caches()

    @pytest.mark.parametrize("quantize", [None, "int8-kv"])
    def test_composite_matches_gather_and_generate(self, model,
                                                   quantize):
        from veles_tpu.parallel.decode import generate

        params, table, heads, vocab = model
        gather, prompts, w_gather = self._drive(model, quantize, False)
        kernel, _, w_kernel = self._drive(model, quantize, True)
        assert gather.results == kernel.results
        assert kernel.dispatch_counts["admit_hit"] >= 1
        if quantize is None:
            assert kernel.dispatch_counts["admit_tail"] >= 1
        for rid, prompt in enumerate(prompts):
            n = 6 if rid < 5 else 4
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=n, max_len=32,
                               quantize=quantize)
            assert kernel.results[rid] == \
                numpy.asarray(want)[0][:len(kernel.results[rid])] \
                .tolist()
        # the acceptance counters: overshoot structurally deleted,
        # the residual booked honestly as tile_pad
        assert w_kernel["span_overshoot"] == 0
        assert w_kernel["page_overshoot"] == 0
        assert w_kernel["tile_pad"] > 0
        assert w_gather["page_overshoot"] > 0
        assert w_kernel["bucket_pad"] < w_gather["bucket_pad"]
        assert w_kernel["group_dup"] == 0


@pytest.mark.slow
class TestKernelDispatchEconomy:
    """The kernel tier rides the SAME paged.* program names: six
    same-shape waves through the ragged admission + kernel step must
    compile each program at most twice with zero recompile storms —
    veles_xla_compiles_total{paged.*} stays flat across a warmed
    sweep."""

    def test_warmed_sweep_zero_storms(self, model, force_kernel):
        params, table, heads, vocab = model
        waves = 6
        tracker = get_compile_tracker()
        was_enabled = tracker.enabled
        tracker.reset()
        tracker.enabled = True
        try:
            rng = numpy.random.RandomState(6)
            dec = ContinuousDecoder(params, table, heads, slots=2,
                                    max_len=32, n_tokens=4,
                                    paged=True, page_size=PS)
            for _ in range(waves):
                for _ in range(2):
                    dec.submit(rng.randint(0, vocab, 6))
                dec.run_until_drained(chunk=4)
            snap = tracker.snapshot()
        finally:
            tracker.reset()
            tracker.enabled = was_enabled
        assert sum(snap["storms"].values()) == 0
        assert dec.dispatch_counts["admit"] <= waves
        assert dec.dispatch_counts["admit_requests"] == 2 * waves
        for program in ("paged.admit", "paged.dispatch"):
            compiles = snap["compiles"].get(program, 0)
            hits = snap["hits"].get(program, 0)
            assert compiles <= 2, \
                "%s retraced %d times over %d same-shape waves" % (
                    program, compiles, waves)
            assert hits >= waves - 2, \
                "%s only hit %d times" % (program, hits)
