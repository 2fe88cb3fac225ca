"""Ring-attention tests on the 8-device CPU mesh: the sequence-parallel
result must match single-device attention exactly (same math, different
schedule)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from caffe_mpi_tpu.ops.attention import (
    attention,
    ring_attention,
    sequence_parallel_attention,
)
from caffe_mpi_tpu.parallel import MeshPlan


def qkv(rng, b=2, s=32, h=4, d=8):
    def mk():
        return jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return mk(), mk(), mk()


class TestAttention:
    def test_matches_naive_softmax(self, rng):
        q, k, v = qkv(rng, s=16)
        out = attention(q, k, v)
        # naive reference
        s_ = np.einsum("bqhd,bkhd->bhqk", np.array(q), np.array(k)) / np.sqrt(8)
        p = np.exp(s_ - s_.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        expect = np.einsum("bhqk,bkhd->bqhd", p, np.array(v))
        np.testing.assert_allclose(np.array(out), expect, rtol=2e-5, atol=1e-6)

    def test_causal_masks_future(self, rng):
        q, k, v = qkv(rng, s=8)
        out = attention(q, k, v, causal=True)
        # first position attends only to itself
        expect0 = np.array(v)[:, 0]
        np.testing.assert_allclose(np.array(out)[:, 0], expect0, rtol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, rng, causal):
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, k, v = qkv(rng, b=2, s=256, h=2, d=32)
        ref = attention(q, k, v, causal=causal)
        # interpret mode on CPU; the same kernel compiles via Mosaic on TPU
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-5,
                                   atol=1e-6)

    @pytest.mark.parametrize("sq,sk", [(130, 130), (300, 160), (100, 333),
                                       (257, 257), (600, 600)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_uneven_lengths_match_reference(self, rng, sq, sk, causal):
        """Lengths that don't tile evenly are padded+masked in-kernel:
        padded key columns must not leak into the softmax denominator and
        padded query rows must not leak into dK/dV."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, _, _ = qkv(rng, b=1, s=sq, h=2, d=32)
        _, k, v = qkv(rng, b=1, s=sk, h=2, d=32)
        ref = attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-5,
                                   atol=1e-6)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=True)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(attention(q, k, v, causal=causal)))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.array(a), np.array(b), rtol=2e-4,
                                       atol=2e-5, err_msg=f"d{name}")

    @pytest.mark.parametrize("s,window,valid,want", [
        (8192, 0, None, (136, 120)), (8192, 4096, None, (108, 84)),
        (640, 0, None, (15, 10)), (640, 200, None, None),
        (640, 256, None, None), (640, 128, None, None),
        (640, 1000, None, (15, 10)), (640, 0, 600, None),
        (640, 200, 600, None), (1024, 512, None, (3, 0)),
        (1536, 512, None, (5, 0)), (1536, 600, None, (6, 0)),
        (2048, 1024, None, (9, 3)), (96, 0, None, (1, 0)),
        (96, 40, None, (1, 0))])
    def test_tile_counts_match_the_element_mask(self, s, window, valid,
                                                want):
        """(visited, inside) from the range code the kernels run, against
        a count over the mask itself, element by element: a tile is
        visited iff it holds a valid score and inside iff it holds no
        other. 372 of the 460 tiles a head visits over the benchmark's one
        full and three window layers run without the mask."""
        from caffe_mpi_tpu.ops.flash_attention import _tile, tile_counts
        rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
        mask = rows >= cols
        if window:
            mask &= rows - cols < window
        t = _tile(s)
        tiles = mask.reshape(s // t, t, s // t, t)
        brute = (int(tiles.any((1, 3)).sum()), int(tiles.all((1, 3)).sum()))
        assert tile_counts(s, s, True, window, dkv=True) == brute
        if valid is not None:   # padded keys: forward and dQ alone
            tiles = (mask & (cols < valid)).reshape(s // t, t, s // t, t)
            brute = (int(tiles.any((1, 3)).sum()),
                     int(tiles.all((1, 3)).sum()))
        assert tile_counts(s, s, True, window, valid) == brute
        assert want in (None, brute)

    @pytest.mark.parametrize("valid", [None, 600])
    def test_tile_counts_without_a_mask(self, valid):
        """Not causal: every tile with a live key column, all of them
        inside but the padded tail's."""
        from caffe_mpi_tpu.ops.flash_attention import tile_counts
        assert tile_counts(640, 640, False, 0, valid) == (
            (25, 25) if valid is None else (25, 20))
        assert tile_counts(640, 640, False, dkv=True) == (25, 25)

    def test_uneven_lengths_extreme_logits_no_nan(self, rng):
        """With padded keys and all-strongly-negative valid scores
        (row lse < -88), the recomputed p at padded columns is
        exp(0 - lse) -> inf; unmasked it would NaN dQ via inf*0."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, _, _ = qkv(rng, b=1, s=160, h=1, d=32)
        _, k, v = qkv(rng, b=1, s=160, h=1, d=32)
        # drive every valid score strongly negative (row lse ~ -100,
        # past the exp(-lse) f32 overflow threshold of ~88.7) while
        # keeping softmax comparisons meaningful
        q = jnp.abs(q) * 6.0
        k = -jnp.abs(k) * 6.0
        g = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, interpret=True)))(q)
        assert np.isfinite(np.array(g)).all()
        gr = jax.grad(lambda q: jnp.sum(attention(q, k, v)))(q)
        np.testing.assert_allclose(np.array(g), np.array(gr), rtol=2e-4,
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_reference(self, rng, causal):
        """jax.grad through the Pallas kernels (custom_vjp: dQ kernel +
        dK/dV kernel, probabilities recomputed from the saved logsumexp)
        must match jax.grad through the jnp reference attention."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, k, v = qkv(rng, b=2, s=256, h=2, d=32)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, interpret=True)
            return jnp.sum(jnp.sin(o))  # non-trivial cotangent

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(attention(q, k, v, causal=causal)))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.array(a), np.array(b), rtol=2e-4,
                                       atol=2e-5, err_msg=f"d{name}")

    @pytest.mark.skipif(jax.default_backend() != "tpu",
                        reason="real Mosaic compile path needs a TPU")
    @pytest.mark.parametrize("causal", [False, True])
    def test_tpu_mosaic_compile_fwd_bwd(self, rng, causal):
        """On real TPU: the kernels must COMPILE via Mosaic (not
        interpret) and match the jnp reference forward and backward —
        interpret-mode tests cannot prove the TPU lowering."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, k, v = qkv(rng, b=1, s=256, h=2, d=32)
        ref = attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=False)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-3,
                                   atol=1e-4)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=causal, interpret=False) ** 2))(q)
        gr = jax.grad(lambda q: jnp.sum(
            attention(q, k, v, causal=causal) ** 2))(q)
        np.testing.assert_allclose(np.array(g), np.array(gr), rtol=5e-3,
                                   atol=1e-4)

    def test_bf16_inputs(self, rng):
        """bf16 activations (the FLOAT16 policy) through the kernels:
        compute is f32 internally, output returns bf16, and fwd/bwd track
        the f32 reference at bf16 resolution."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, k, v = qkv(rng, b=1, s=128, h=2, d=16)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        out = flash_attention(qb, kb, vb, causal=True, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.array(out, np.float32), np.array(ref),
                                   rtol=2e-2, atol=2e-2)
        g = jax.grad(lambda qb: jnp.sum(flash_attention(
            qb, kb, vb, causal=True, interpret=True).astype(jnp.float32)))(qb)
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.array(g, np.float32)).all()

    def test_use_flash_entry_gradcheck(self, rng):
        """Finite-difference gradient check through the public
        attention(use_flash=True) entry (the framework's gradcheck bar,
        reference test_gradient_check_util.hpp)."""
        q, k, v = qkv(rng, b=1, s=128, h=1, d=8)

        def f(q):
            return jnp.sum(attention(q, k, v, use_flash=True) ** 2)

        g = jax.grad(f)(q)
        eps = 1e-3
        r = np.random.RandomState(0)
        for _ in range(5):
            idx = tuple(r.randint(0, s) for s in q.shape)
            dq = np.zeros(q.shape, np.float32)
            dq[idx] = eps
            fd = (float(f(q + dq)) - float(f(q - dq))) / (2 * eps)
            np.testing.assert_allclose(float(g[idx]), fd, rtol=2e-2,
                                       atol=1e-4)

    def test_backward_multi_tile(self, rng):
        """Sequences spanning several 128-wide tiles exercise the
        fori_loop accumulation and the causal tile-skip in both backward
        kernels."""
        from caffe_mpi_tpu.ops.flash_attention import flash_attention
        q, k, v = qkv(rng, b=1, s=384, h=1, d=16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.array(a), np.array(b), rtol=5e-4,
                                       atol=2e-5)


class TestRingFlash:
    """Ring schedule with Pallas flash blocks (interpret mode on CPU):
    must match single-device attention exactly, forward and backward,
    including uneven lengths (global pad masked via the kernels' key
    bias) and causal block skipping.

    Tracing + interpret-mode execution of an 8-device ring program costs
    10-30 s per case on the one host core, so the heaviest variants are
    marked slow to keep tier-1 inside its wall-clock budget: where a
    causal/non-causal pair exists the causal variant (strictly more
    masking + block-skipping coverage) stays in tier-1 and the
    non-causal one goes slow; the two extreme edge-case tests
    (fully-padded shards, 1030-long multi-tile) are slow outright."""

    @pytest.mark.parametrize("causal", [
        pytest.param(False, marks=pytest.mark.slow), True])
    def test_matches_single_device(self, rng, causal):
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=64, h=2, d=16)
        ref = attention(q, k, v, causal=causal)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=causal,
                                          use_flash=True,
                                          flash_interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-5,
                                   atol=1e-6)

    @pytest.mark.parametrize("s", [100,
                                   pytest.param(200,
                                                marks=pytest.mark.slow)])
    @pytest.mark.parametrize("causal", [
        pytest.param(False, marks=pytest.mark.slow), True])
    def test_uneven_lengths(self, rng, s, causal):
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=s, h=2, d=16)
        ref = attention(q, k, v, causal=causal)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=causal,
                                          use_flash=True,
                                          flash_interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-5,
                                   atol=1e-6)

    @pytest.mark.parametrize("causal", [
        pytest.param(False, marks=pytest.mark.slow), True])
    def test_gradients_match_single_device(self, rng, causal):
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=72, h=1, d=8)  # uneven: 72 = 8*9

        def loss_ring(q, k, v):
            o = sequence_parallel_attention(q, k, v, plan.mesh,
                                            seq_axis="data", causal=causal,
                                            use_flash=True,
                                            flash_interpret=True)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(attention(q, k, v, causal=causal)))

        gf = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.array(a), np.array(b), rtol=5e-4,
                                       atol=2e-5, err_msg=f"d{name}")

    @pytest.mark.slow
    def test_fully_padded_shards_with_saturated_scores(self, rng):
        """s=9 over an 8-way ring leaves shards 5-7 entirely padding; a
        fully-masked flash block's clamped lse (~ -69) must NOT enter the
        merge — with all genuine scores ~ -100 a phantom exp(-69) term
        would dominate the denominator and collapse the output to ~0."""
        plan = MeshPlan.data_parallel()
        q, _, _ = qkv(rng, b=1, s=9, h=1, d=32)
        _, k, v = qkv(rng, b=1, s=9, h=1, d=32)
        q = jnp.abs(q) * 6.0
        k = -jnp.abs(k) * 6.0
        ref = attention(q, k, v)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", use_flash=True,
                                          flash_interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-5,
                                   atol=1e-6)
        gf = jax.grad(lambda q: jnp.sum(jnp.sin(sequence_parallel_attention(
            q, k, v, plan.mesh, seq_axis="data", use_flash=True,
            flash_interpret=True))))(q)
        gr = jax.grad(lambda q: jnp.sum(jnp.sin(attention(q, k, v))))(q)
        assert np.isfinite(np.array(gf)).all()
        np.testing.assert_allclose(np.array(gf), np.array(gr), rtol=5e-4,
                                   atol=2e-5)

    @pytest.mark.slow
    def test_long_local_shards_multi_tile(self, rng):
        """ceil(s/n) > 128 exercises the paths short tests can't: padding
        to n*128 multiples (s=1030 -> 2048, local shards of 256 = two
        flash tiles), the multi-tile bias dslice in every kernel, shards
        5-7 being ENTIRELY padding (their blocks merge a clamped lse),
        and the causal cross-block schedule at scale."""
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=1030, h=1, d=8)
        ref = attention(q, k, v, causal=True)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=True,
                                          use_flash=True,
                                          flash_interpret=True)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=5e-5,
                                   atol=1e-5)
        gf = jax.grad(lambda q: jnp.sum(jnp.sin(sequence_parallel_attention(
            q, k, v, plan.mesh, seq_axis="data", causal=True,
            use_flash=True, flash_interpret=True))))(q)
        gr = jax.grad(lambda q: jnp.sum(jnp.sin(
            attention(q, k, v, causal=True))))(q)
        np.testing.assert_allclose(np.array(gf), np.array(gr), rtol=5e-4,
                                   atol=2e-5)

    def test_matches_jnp_ring(self, rng):
        # same schedule, two block implementations — cross-check
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=2, s=64, h=2, d=16)
        a = sequence_parallel_attention(q, k, v, plan.mesh, seq_axis="data",
                                        causal=True)
        b = sequence_parallel_attention(q, k, v, plan.mesh, seq_axis="data",
                                        causal=True, use_flash=True,
                                        flash_interpret=True)
        np.testing.assert_allclose(np.array(b), np.array(a), rtol=2e-5,
                                   atol=1e-6)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, rng, causal):
        plan = MeshPlan.data_parallel()  # 8 devices on 'data'
        q, k, v = qkv(rng, b=2, s=32, h=4, d=8)  # 4 seq positions per device
        ref = attention(q, k, v, causal=causal)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=causal)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-4,
                                   atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_bigger_shapes(self, rng, causal):
        """Non-toy sizes: S=256 over 8 devices (32/shard), 8 heads, d=32."""
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=2, s=256, h=8, d=32)
        ref = attention(q, k, v, causal=causal)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=causal)
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-3,
                                   atol=1e-5)

    @pytest.mark.parametrize("s", [13, 27, 63])
    @pytest.mark.parametrize("causal", [False, True])
    def test_uneven_sequence_shards(self, rng, s, causal):
        """S not divisible by the ring size: padded up, pad keys masked in
        every block, output sliced back — results identical to the
        single-device reference."""
        plan = MeshPlan.data_parallel()  # 8 devices; 13/27/63 all uneven
        q, k, v = qkv(rng, b=2, s=s, h=2, d=8)
        ref = attention(q, k, v, causal=causal)
        out = sequence_parallel_attention(q, k, v, plan.mesh,
                                          seq_axis="data", causal=causal)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.array(out), np.array(ref), rtol=2e-4,
                                   atol=1e-5)

    def test_mixed_causal_and_not_same_program(self, rng):
        """Both mask modes through the same jitted caller (mode is a
        static argument; both variants must trace and agree)."""
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=24, h=2, d=8)

        @jax.jit
        def both(q, k, v):
            a = sequence_parallel_attention(q, k, v, plan.mesh,
                                            seq_axis="data", causal=False)
            b = sequence_parallel_attention(q, k, v, plan.mesh,
                                            seq_axis="data", causal=True)
            return a, b
        a, b = both(q, k, v)
        np.testing.assert_allclose(np.array(a),
                                   np.array(attention(q, k, v)),
                                   rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(np.array(b),
                                   np.array(attention(q, k, v, causal=True)),
                                   rtol=2e-4, atol=1e-5)

    def test_gradients_flow(self, rng):
        plan = MeshPlan.data_parallel()
        q, k, v = qkv(rng, b=1, s=16, h=2, d=4)

        def loss_ring(q, k, v):
            return jnp.sum(sequence_parallel_attention(
                q, k, v, plan.mesh, seq_axis="data"))

        def loss_ref(q, k, v):
            return jnp.sum(attention(q, k, v))

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.array(a), np.array(b), rtol=5e-4,
                                       atol=1e-5)
