"""Op-level tests: forward numerics (torch CPU as independent oracle where
available, naive numpy otherwise) + finite-difference gradient checks.

Mirrors the reference's per-layer test files (src/caffe/test/test_*_layer.cpp)
and their GradientChecker usage.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
import torch
import torch.nn.functional as F

from gradcheck import check_gradients, make_layer


def rand(shape, rng, scale=1.0):
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)


class TestConvolution:
    def test_forward_matches_torch(self, rng):
        layer, params, state = make_layer(
            'name: "c" type: "Convolution" bottom: "x" top: "y"\n'
            'convolution_param { num_output: 6 kernel_size: 3 stride: 2 pad: 1\n'
            '  weight_filler { type: "gaussian" std: 0.1 } }',
            [(2, 4, 9, 9)],
        )
        x = rand((2, 4, 9, 9), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = F.conv2d(torch.tensor(np.array(x)),
                       torch.tensor(np.array(params["weight"])),
                       torch.tensor(np.array(params["bias"])),
                       stride=2, padding=1)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-4, atol=1e-5)
        assert y.shape == (2, 6, 5, 5)

    def test_grouped_dilated(self, rng):
        layer, params, state = make_layer(
            'name: "c" type: "Convolution"  top: "y" bottom: "x"\n'
            'convolution_param { num_output: 4 kernel_size: 3 group: 2\n'
            '  dilation: 2 weight_filler { type: "xavier" } }',
            [(1, 4, 10, 10)],
        )
        x = rand((1, 4, 10, 10), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = F.conv2d(torch.tensor(np.array(x)),
                       torch.tensor(np.array(params["weight"])),
                       torch.tensor(np.array(params["bias"])),
                       dilation=2, groups=2)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-4, atol=1e-5)

    def test_asymmetric_kernel_matches_torch(self, rng):
        # 1x7 kernel with asymmetric padding (inception_v3's factorized conv)
        layer, params, state = make_layer(
            'name: "c" type: "Convolution" bottom: "x" top: "y"\n'
            'convolution_param { num_output: 4 kernel_h: 1 kernel_w: 7\n'
            '  pad_h: 0 pad_w: 3 weight_filler { type: "gaussian" std: 0.1 } }',
            [(2, 3, 9, 9)],
        )
        x = rand((2, 3, 9, 9), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = F.conv2d(torch.tensor(np.array(x)),
                       torch.tensor(np.array(params["weight"])),
                       torch.tensor(np.array(params["bias"])),
                       padding=(0, 3))
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-4,
                                   atol=1e-5)
        assert y.shape == (2, 4, 9, 9)

    def test_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "c" type: "Convolution" bottom: "x" top: "y"\n'
            'convolution_param { num_output: 3 kernel_size: 3 pad: 1\n'
            '  weight_filler { type: "gaussian" std: 0.3 } }',
            [(2, 2, 5, 5)],
        )
        check_gradients(layer, params, state, [rand((2, 2, 5, 5), rng)])


class TestDeconvolution:
    def test_forward_matches_torch(self, rng):
        layer, params, state = make_layer(
            'name: "d" type: "Deconvolution" bottom: "x" top: "y"\n'
            'convolution_param { num_output: 3 kernel_size: 4 stride: 2 pad: 1\n'
            '  weight_filler { type: "gaussian" std: 0.1 } }',
            [(2, 5, 6, 6)],
        )
        x = rand((2, 5, 6, 6), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = F.conv_transpose2d(torch.tensor(np.array(x)),
                                 torch.tensor(np.array(params["weight"])),
                                 torch.tensor(np.array(params["bias"])),
                                 stride=2, padding=1)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-4, atol=1e-5)
        assert y.shape == (2, 3, 12, 12)

    def test_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "d" type: "Deconvolution" bottom: "x" top: "y"\n'
            'convolution_param { num_output: 2 kernel_size: 3 stride: 2\n'
            '  weight_filler { type: "gaussian" std: 0.3 } }',
            [(1, 2, 4, 4)],
        )
        check_gradients(layer, params, state, [rand((1, 2, 4, 4), rng)])


def naive_caffe_avg_pool(x, k, s, p):
    """Direct transcription of the reference AVE arithmetic
    (pooling_layer.cpp:196-215) as an oracle."""
    import math
    n, c, h, w = x.shape
    oh = int(math.ceil((h + 2 * p - k) / s)) + 1
    ow = int(math.ceil((w + 2 * p - k) / s)) + 1
    if p > 0:
        if (oh - 1) * s >= h + p:
            oh -= 1
        if (ow - 1) * s >= w + p:
            ow -= 1
    out = np.zeros((n, c, oh, ow), np.float32)
    for ph in range(oh):
        for pw in range(ow):
            hs, ws = ph * s - p, pw * s - p
            he, we = min(hs + k, h + p), min(ws + k, w + p)
            pool_size = (he - hs) * (we - ws)
            hs_, ws_ = max(hs, 0), max(ws, 0)
            he_, we_ = min(he, h), min(we, w)
            region = x[:, :, hs_:he_, ws_:we_]
            out[:, :, ph, pw] = region.sum(axis=(2, 3)) / pool_size
    return out


class TestPooling:
    def test_max_ceil_mode_matches_torch(self, rng):
        # 6x6 input, k=3 s=2: ceil -> 3x3 output (floor would give 2x2)
        layer, params, state = make_layer(
            'name: "p" type: "Pooling" bottom: "x" top: "y"\n'
            'pooling_param { pool: MAX kernel_size: 3 stride: 2 }',
            [(2, 3, 6, 6)],
        )
        x = rand((2, 3, 6, 6), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        assert y.shape == (2, 3, 3, 3)
        ref = F.max_pool2d(torch.tensor(np.array(x)), 3, 2, 0, ceil_mode=True)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-6)

    def test_max_with_pad(self, rng):
        layer, params, state = make_layer(
            'name: "p" type: "Pooling" bottom: "x" top: "y"\n'
            'pooling_param { pool: MAX kernel_size: 3 stride: 2 pad: 1 }',
            [(1, 2, 6, 6)],
        )
        x = rand((1, 2, 6, 6), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = F.max_pool2d(torch.tensor(np.array(x)), 3, 2, 1, ceil_mode=True)
        assert y.shape == tuple(ref.shape)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-6)

    def test_avg_caffe_divisor(self, rng):
        layer, params, state = make_layer(
            'name: "p" type: "Pooling" bottom: "x" top: "y"\n'
            'pooling_param { pool: AVE kernel_size: 3 stride: 2 pad: 1 }',
            [(2, 2, 5, 5)],
        )
        x = rand((2, 2, 5, 5), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = naive_caffe_avg_pool(np.array(x), 3, 2, 1)
        assert y.shape == ref.shape
        np.testing.assert_allclose(np.array(y), ref, rtol=1e-5, atol=1e-6)

    def test_global_pooling(self, rng):
        layer, params, state = make_layer(
            'name: "p" type: "Pooling" bottom: "x" top: "y"\n'
            'pooling_param { pool: AVE global_pooling: true }',
            [(2, 4, 6, 6)],
        )
        x = rand((2, 4, 6, 6), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        assert y.shape == (2, 4, 1, 1)
        np.testing.assert_allclose(np.array(y)[:, :, 0, 0],
                                   np.array(x).mean(axis=(2, 3)), rtol=1e-5)

    def test_output_dim_clip_guard_matches_reference(self):
        """The last-window clip applies to BOTH dims whenever EITHER pad is
        nonzero — the reference's `if (pad_h_ || pad_w_)` guard
        (pooling_layer.cpp:96-108), not a per-dim pad check."""
        import math
        from caffe_mpi_tpu.ops.pool import pool_output_dim

        def ref_dims(h, w, k, s, ph, pw):
            oh = int(math.ceil((h + 2 * ph - k) / s)) + 1
            ow = int(math.ceil((w + 2 * pw - k) / s)) + 1
            if ph or pw:
                if (oh - 1) * s >= h + ph:
                    oh -= 1
                if (ow - 1) * s >= w + pw:
                    ow -= 1
            return oh, ow

        for h in (3, 4, 6, 7):
            for w in (3, 5, 6):
                for k in (1, 2, 3):
                    for s in (1, 2, 3):
                        for ph in (0, 1):
                            for pw in (0, 1):
                                if ph >= k or pw >= k:
                                    continue  # Caffe CHECKs pad < kernel
                                any_pad = ph > 0 or pw > 0
                                got = (pool_output_dim(h, k, ph, s, any_pad),
                                       pool_output_dim(w, k, pw, s, any_pad))
                                assert got == ref_dims(h, w, k, s, ph, pw), \
                                    (h, w, k, s, ph, pw)

    def test_gradients(self, rng):
        for pool in ("MAX", "AVE"):
            layer, params, state = make_layer(
                f'name: "p" type: "Pooling" bottom: "x" top: "y"\n'
                f'pooling_param {{ pool: {pool} kernel_size: 2 stride: 2 }}',
                [(1, 2, 4, 4)],
            )
            check_gradients(layer, params, state, [rand((1, 2, 4, 4), rng)])


class TestLRN:
    def test_across_channels_formula(self, rng):
        layer, params, state = make_layer(
            'name: "n" type: "LRN" bottom: "x" top: "y"\n'
            'lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 }',
            [(1, 8, 3, 3)],
        )
        x = rand((1, 8, 3, 3), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        # naive: scale_c = k + alpha/n * sum_{c'} x^2 over window
        xn = np.array(x)
        out = np.zeros_like(xn)
        for c in range(8):
            lo, hi = max(0, c - 2), min(8, c + 3)
            s = 1.0 + (1e-4 / 5) * (xn[:, lo:hi] ** 2).sum(axis=1)
            out[:, c] = xn[:, c] * s ** -0.75
        np.testing.assert_allclose(np.array(y), out, rtol=1e-5)
        # torch cross-check: torch LRN uses the same alpha/n convention
        ref = F.local_response_norm(torch.tensor(xn), 5, alpha=1e-4, beta=0.75, k=1.0)
        np.testing.assert_allclose(np.array(y), ref.numpy(), rtol=1e-5)

    def test_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "n" type: "LRN" bottom: "x" top: "y"\n'
            'lrn_param { local_size: 3 alpha: 0.1 beta: 0.75 }',
            [(1, 4, 3, 3)],
        )
        check_gradients(layer, params, state, [rand((1, 4, 3, 3), rng)])

    @staticmethod
    def _oracle(x, size, alpha, beta, k=1.0):
        """The expression LRNLayer.apply held before PR 39: pad, `size`
        shifted adds, jnp.power; its gradient is reverse-mode AD's."""
        half = (size - 1) // 2
        padded = jnp.pad(jnp.square(x), ((0, 0), (half, half), (0, 0), (0, 0)))
        c = x.shape[1]
        window_sum = padded[:, 0:c]
        for off in range(1, size):
            window_sum = window_sum + padded[:, off:off + c]
        return x * jnp.power(k + window_sum * (alpha / size), -beta)

    # C of 3 is smaller than the window, 100 no sublane multiple; alpha
    # 0.1 puts the scale far from k. Relative 1e-5; the absolute 1e-6 is
    # for gradient elements whose two terms cancel (values are of order 1)
    @pytest.mark.parametrize("beta", [0.75, 0.5])
    @pytest.mark.parametrize("alpha", [1e-4, 0.1])
    @pytest.mark.parametrize("batch", [1, 4, 10, 128])
    @pytest.mark.parametrize("channels", [3, 6, 96, 100])
    @pytest.mark.parametrize("size", [3, 5])
    def test_lax_path_against_the_shifted_adds(self, size, channels, batch,
                                               alpha, beta):
        from caffe_mpi_tpu.ops.lrn_lax import lrn_across_channels
        rng = np.random.RandomState(size * 1000 + channels * 10 + batch)
        x = rand((batch, channels, 2, 3), rng, scale=3.0)
        dy = rand((batch, channels, 2, 3), rng)
        y, vjp = jax.vjp(
            lambda x: lrn_across_channels(x, size, alpha, beta, 1.0), x)
        ref, ref_vjp = jax.vjp(
            lambda x: self._oracle(x, size, alpha, beta), x)
        assert y.dtype == jnp.float32
        np.testing.assert_allclose(np.array(y), np.array(ref), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.array(vjp(dy)[0]),
                                   np.array(ref_vjp(dy)[0]), rtol=1e-5,
                                   atol=1e-6)

    @pytest.mark.parametrize("size,channels", [(3, 3), (5, 8)])
    def test_lax_path_custom_vjp_against_finite_differences(self, size,
                                                            channels, rng):
        from jax.test_util import check_grads
        from caffe_mpi_tpu.ops.lrn_lax import lrn_across_channels
        x = rand((2, channels, 2, 2), rng)
        check_grads(lambda x: lrn_across_channels(x, size, 0.1, 0.75, 1.0),
                    (x,), order=1, modes=("rev",), atol=2e-2, rtol=2e-2)


class TestInnerProduct:
    def test_forward_and_transpose(self, rng):
        x = rand((3, 4, 2, 2), rng)
        layer, params, state = make_layer(
            'name: "ip" type: "InnerProduct" bottom: "x" top: "y"\n'
            'inner_product_param { num_output: 5 weight_filler { type: "xavier" } }',
            [(3, 4, 2, 2)],
        )
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        ref = np.array(x).reshape(3, -1) @ np.array(params["weight"]).T + \
            np.array(params["bias"])
        np.testing.assert_allclose(np.array(y), ref, rtol=1e-4, atol=1e-5)

        layer_t, params_t, _ = make_layer(
            'name: "ip" type: "InnerProduct" bottom: "x" top: "y"\n'
            'inner_product_param { num_output: 5 transpose: true\n'
            '  weight_filler { type: "xavier" } }',
            [(3, 4, 2, 2)],
        )
        assert params_t["weight"].shape == (16, 5)
        (yt,), _ = layer_t.apply(params_t, state, [x], train=False, rng=None)
        assert yt.shape == (3, 5)

    def test_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "ip" type: "InnerProduct" bottom: "x" top: "y"\n'
            'inner_product_param { num_output: 4 weight_filler { type: "xavier" } }',
            [(2, 6)],
        )
        check_gradients(layer, params, state, [rand((2, 6), rng)])


class TestActivations:
    CASES = [
        ('type: "ReLU"', lambda x: np.maximum(x, 0)),
        ('type: "ReLU" relu_param { negative_slope: 0.1 }',
         lambda x: np.where(x > 0, x, 0.1 * x)),
        ('type: "Sigmoid"', lambda x: 1 / (1 + np.exp(-x))),
        ('type: "TanH"', np.tanh),
        ('type: "AbsVal"', np.abs),
        ('type: "BNLL"', lambda x: np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0)),
        ('type: "ELU"', lambda x: np.where(x > 0, x, np.exp(x) - 1)),
        ('type: "Power" power_param { power: 2 scale: 0.5 shift: 1 }',
         lambda x: (1 + 0.5 * x) ** 2),
        ('type: "Exp"', np.exp),
    ]

    @pytest.mark.parametrize("proto,ref", CASES, ids=[c[0][7:20] for c in CASES])
    def test_forward(self, proto, ref, rng):
        layer, params, state = make_layer(
            f'name: "a" {proto} bottom: "x" top: "y"', [(2, 3, 4)])
        x = rand((2, 3, 4), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        np.testing.assert_allclose(np.array(y), ref(np.array(x)), rtol=1e-5,
                                   atol=1e-6)

    def test_smooth_gradients(self, rng):
        for proto in ['type: "Sigmoid"', 'type: "TanH"', 'type: "ELU"',
                      'type: "BNLL"']:
            layer, params, state = make_layer(
                f'name: "a" {proto} bottom: "x" top: "y"', [(2, 5)])
            check_gradients(layer, params, state, [rand((2, 5), rng)])

    def test_prelu_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "a" type: "PReLU" bottom: "x" top: "y"', [(2, 3, 4)])
        assert params["slope"].shape == (3,)
        x = rand((2, 3, 4), rng) + 0.3  # keep away from the kink
        check_gradients(layer, params, state, [x])

    def test_dropout(self, rng):
        layer, params, state = make_layer(
            'name: "d" type: "Dropout" bottom: "x" top: "y"\n'
            'dropout_param { dropout_ratio: 0.4 }', [(100, 100)])
        x = jnp.ones((100, 100))
        (y_test,), _ = layer.apply(params, state, [x], train=False, rng=None)
        np.testing.assert_array_equal(np.array(y_test), np.ones((100, 100)))
        (y_train,), _ = layer.apply(params, state, [x], train=True,
                                    rng=jax.random.PRNGKey(3))
        yn = np.array(y_train)
        kept = yn != 0
        assert 0.55 < kept.mean() < 0.65
        np.testing.assert_allclose(yn[kept], 1 / 0.6, rtol=1e-5)


class TestBatchNorm:
    def test_train_normalizes_and_updates_ema(self, rng):
        layer, params, state = make_layer(
            'name: "bn" type: "BatchNorm" bottom: "x" top: "y"\n'
            'batch_norm_param { moving_average_fraction: 0.9 }',
            [(4, 3, 5, 5)],
        )
        x = rand((4, 3, 5, 5), rng, scale=2.0) + 1.0
        (y,), new_state = layer.apply(params, state, [x], train=True, rng=None)
        yn = np.array(y)
        assert abs(yn.mean(axis=(0, 2, 3))).max() < 1e-4
        np.testing.assert_allclose(yn.std(axis=(0, 2, 3)), 1.0, atol=1e-3)
        xn = np.array(x, np.float64)
        batch_mean = xn.mean(axis=(0, 2, 3))
        np.testing.assert_allclose(np.array(new_state["mean"]),
                                   0.1 * batch_mean, rtol=1e-4)

    def test_test_phase_uses_global_stats(self, rng):
        layer, params, state = make_layer(
            'name: "bn" type: "BatchNorm" bottom: "x" top: "y"',
            [(2, 3, 4, 4)], phase="TEST",
        )
        state = {"mean": jnp.array([1.0, 2.0, 3.0]),
                 "var": jnp.array([4.0, 4.0, 4.0])}
        x = rand((2, 3, 4, 4), rng)
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        expect = (np.array(x) - np.array([1, 2, 3])[None, :, None, None]) / \
            np.sqrt(4.0 + 1e-5)
        np.testing.assert_allclose(np.array(y), expect, rtol=1e-4, atol=1e-5)

    def test_scale_bias_params(self, rng):
        layer, params, state = make_layer(
            'name: "bn" type: "BatchNorm" bottom: "x" top: "y"\n'
            'batch_norm_param { scale_bias: true }',
            [(2, 3, 4, 4)],
        )
        assert set(params) == {"scale", "bias"}
        check_gradients(layer, params, state, [rand((2, 3, 4, 4), rng)],
                        bottoms_to_check=[])


class TestLosses:
    def test_softmax_loss_matches_torch(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"',
            [(5, 7), (5,)],
        )
        x = rand((5, 7), rng)
        t = jnp.asarray(rng.randint(0, 7, 5))
        (loss,), _ = layer.apply(params, state, [x, t], train=True, rng=None)
        ref = F.cross_entropy(torch.tensor(np.array(x)),
                              torch.tensor(np.array(t), dtype=torch.long))
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    def test_softmax_loss_spatial_ignore(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"\n'
            'loss_param { ignore_label: 255 }',
            [(2, 4, 3, 3), (2, 3, 3)],
        )
        x = rand((2, 4, 3, 3), rng)
        t = rng.randint(0, 4, (2, 3, 3))
        t[0, 0, :] = 255
        tj = jnp.asarray(t)
        (loss,), _ = layer.apply(params, state, [x, tj], train=True, rng=None)
        ref = F.cross_entropy(torch.tensor(np.array(x)),
                              torch.tensor(t, dtype=torch.long),
                              ignore_index=255)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    def test_legacy_normalize_false_is_batch_size(self, rng):
        """loss_param { normalize: false } maps to BATCH_SIZE for every
        loss (softmax_loss_layer.cpp:35-38), i.e. divide by N even when the
        target is spatial — NOT by the full count, NOT by 1."""
        x = rand((3, 4, 2, 2), rng)
        t = jnp.asarray(rng.randint(0, 4, (3, 2, 2)))
        legacy, params, state = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"\n'
            'loss_param { normalize: false }',
            [(3, 4, 2, 2), (3,)],
        )
        (loss,), _ = legacy.apply(params, state, [x, t], train=True, rng=None)
        modern, p2, s2 = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"\n'
            'loss_param { normalization: BATCH_SIZE }',
            [(3, 4, 2, 2), (3,)],
        )
        (ref,), _ = modern.apply(p2, s2, [x, t], train=True, rng=None)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
        # sanity: BATCH_SIZE (sum/3) differs from VALID (sum/12) here
        valid, p3, s3 = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"',
            [(3, 4, 2, 2), (3,)],
        )
        (lv,), _ = valid.apply(p3, s3, [x, t], train=True, rng=None)
        np.testing.assert_allclose(float(loss), 4 * float(lv), rtol=1e-5)

    def test_softmax_loss_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "t" top: "loss"',
            [(4, 5), (4,)],
        )
        x = rand((4, 5), rng)
        t = jnp.asarray(rng.randint(0, 5, 4))
        check_gradients(layer, params, state, [x, t], bottoms_to_check=[0])

    def test_euclidean(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "EuclideanLoss" bottom: "a" bottom: "b" top: "loss"',
            [(4, 3), (4, 3)],
        )
        a, b = rand((4, 3), rng), rand((4, 3), rng)
        (loss,), _ = layer.apply(params, state, [a, b], train=True, rng=None)
        expect = ((np.array(a) - np.array(b)) ** 2).sum() / 8
        np.testing.assert_allclose(float(loss), expect, rtol=1e-5)
        check_gradients(layer, params, state, [a, b])

    def test_sigmoid_ce_matches_torch(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "SigmoidCrossEntropyLoss" bottom: "x" bottom: "t" top: "loss"',
            [(4, 6), (4, 6)],
        )
        x = rand((4, 6), rng)
        t = jnp.asarray(rng.rand(4, 6).astype(np.float32))
        (loss,), _ = layer.apply(params, state, [x, t], train=True, rng=None)
        ref = F.binary_cross_entropy_with_logits(
            torch.tensor(np.array(x)), torch.tensor(np.array(t)),
            reduction="sum") / 4
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        check_gradients(layer, params, state, [x, t], bottoms_to_check=[0])

    def test_hinge(self, rng):
        layer, params, state = make_layer(
            'name: "l" type: "HingeLoss" bottom: "x" bottom: "t" top: "loss"',
            [(3, 4), (3,)],
        )
        x = rand((3, 4), rng)
        t = jnp.asarray(rng.randint(0, 4, 3))
        (loss,), _ = layer.apply(params, state, [x, t], train=True, rng=None)
        xn, tn = np.array(x), np.array(t)
        margins = np.maximum(0, 1 + xn)
        for i, lab in enumerate(tn):
            margins[i, lab] = max(0, 1 - xn[i, lab])
        np.testing.assert_allclose(float(loss), margins.sum() / 3, rtol=1e-5)

    def test_accuracy_topk(self, rng):
        layer, params, state = make_layer(
            'name: "a" type: "Accuracy" bottom: "x" bottom: "t" top: "acc"\n'
            'accuracy_param { top_k: 2 }',
            [(6, 5), (6,)],
        )
        x = rand((6, 5), rng)
        t = jnp.asarray(rng.randint(0, 5, 6))
        (acc,), _ = layer.apply(params, state, [x, t], train=False, rng=None)
        order = np.argsort(-np.array(x), axis=1)
        expect = np.mean([t[i] in order[i, :2] for i in range(6)])
        np.testing.assert_allclose(float(acc), expect, rtol=1e-6)


class TestShapeOps:
    def test_concat_slice_roundtrip(self, rng):
        x = rand((2, 6, 3), rng)
        sl, _, _ = make_layer(
            'name: "s" type: "Slice" bottom: "x" top: "a" top: "b" top: "c"\n'
            'slice_param { axis: 1 slice_point: 1 slice_point: 3 }',
            [(2, 6, 3)],
        )
        tops, _ = sl.apply({}, {}, [x], train=False, rng=None)
        assert [t.shape for t in tops] == [(2, 1, 3), (2, 2, 3), (2, 3, 3)]
        cat, _, _ = make_layer(
            'name: "c" type: "Concat" bottom: "a" bottom: "b" bottom: "c" top: "y"',
            [t.shape for t in tops],
        )
        (y,), _ = cat.apply({}, {}, tops, train=False, rng=None)
        np.testing.assert_array_equal(np.array(y), np.array(x))

    def test_eltwise(self, rng):
        a, b = rand((2, 3), rng), rand((2, 3), rng)
        for op, ref in [("SUM", np.array(a) + np.array(b)),
                        ("PROD", np.array(a) * np.array(b)),
                        ("MAX", np.maximum(np.array(a), np.array(b)))]:
            el, _, _ = make_layer(
                f'name: "e" type: "Eltwise" bottom: "a" bottom: "b" top: "y"\n'
                f'eltwise_param {{ operation: {op} }}',
                [(2, 3), (2, 3)],
            )
            (y,), _ = el.apply({}, {}, [a, b], train=False, rng=None)
            np.testing.assert_allclose(np.array(y), ref, rtol=1e-6)

    def test_eltwise_coeff(self, rng):
        a, b = rand((2, 3), rng), rand((2, 3), rng)
        el, _, _ = make_layer(
            'name: "e" type: "Eltwise" bottom: "a" bottom: "b" top: "y"\n'
            'eltwise_param { operation: SUM coeff: 1 coeff: -1 }',
            [(2, 3), (2, 3)],
        )
        (y,), _ = el.apply({}, {}, [a, b], train=False, rng=None)
        np.testing.assert_allclose(np.array(y), np.array(a) - np.array(b),
                                   rtol=1e-5)

    def test_flatten_reshape(self, rng):
        x = rand((2, 3, 4, 5), rng)
        fl, _, _ = make_layer(
            'name: "f" type: "Flatten" bottom: "x" top: "y"', [(2, 3, 4, 5)])
        (y,), _ = fl.apply({}, {}, [x], train=False, rng=None)
        assert y.shape == (2, 60)
        rs, _, _ = make_layer(
            'name: "r" type: "Reshape" bottom: "x" top: "y"\n'
            'reshape_param { shape { dim: 0 dim: -1 dim: 5 } }',
            [(2, 3, 4, 5)],
        )
        (z,), _ = rs.apply({}, {}, [x], train=False, rng=None)
        assert z.shape == (2, 12, 5)

    def test_argmax(self, rng):
        x = rand((3, 7), rng)
        am, _, _ = make_layer(
            'name: "a" type: "ArgMax" bottom: "x" top: "y"', [(3, 7)])
        (y,), _ = am.apply({}, {}, [x], train=False, rng=None)
        np.testing.assert_array_equal(
            np.array(y)[:, 0, 0], np.argmax(np.array(x), axis=1))

    def test_scale_bias_layers(self, rng):
        x = rand((2, 3, 4), rng)
        sc, params, _ = make_layer(
            'name: "s" type: "Scale" bottom: "x" top: "y"\n'
            'scale_param { bias_term: true }',
            [(2, 3, 4)],
        )
        params = {"operand": jnp.array([1.0, 2.0, 3.0]),
                  "bias": jnp.array([0.5, 0.0, -0.5])}
        (y,), _ = sc.apply(params, {}, [x], train=False, rng=None)
        expect = np.array(x) * np.array([1, 2, 3])[None, :, None] + \
            np.array([0.5, 0, -0.5])[None, :, None]
        np.testing.assert_allclose(np.array(y), expect, rtol=1e-5)


class TestMoreGradients:
    """Gradient checks for structural ops (reference runs GradientChecker
    on every layer; these cover the pure-movement ones)."""

    @pytest.mark.parametrize("proto,shapes", [
        ('type: "Concat" bottom: "a" bottom: "b" top: "y"',
         [(2, 3, 4), (2, 2, 4)]),
        ('type: "Slice" bottom: "x" top: "a" top: "b"\n'
         'slice_param { axis: 1 slice_point: 2 }', [(2, 5)]),
        ('type: "Flatten" bottom: "x" top: "y"', [(2, 3, 4)]),
        ('type: "Tile" bottom: "x" top: "y" tile_param { tiles: 3 }',
         [(2, 4)]),
        ('type: "Reduction" bottom: "x" top: "y"\n'
         'reduction_param { operation: SUMSQ axis: 1 }', [(3, 4)]),
        ('type: "Eltwise" bottom: "a" bottom: "b" top: "y"\n'
         'eltwise_param { operation: PROD }', [(2, 3), (2, 3)]),
        ('type: "Scale" bottom: "x" top: "y" scale_param { bias_term: true }',
         [(2, 3, 4)]),
        ('type: "Bias" bottom: "x" top: "y"', [(2, 3, 4)]),
        ('type: "MVN" bottom: "x" top: "y"', [(2, 3, 4, 4)]),
        ('type: "LRN" bottom: "x" top: "y"\n'
         'lrn_param { local_size: 3 norm_region: WITHIN_CHANNEL }',
         [(1, 2, 5, 5)]),
        ('type: "SPP" bottom: "x" top: "y" spp_param { pyramid_height: 2 }',
         [(1, 2, 6, 6)]),
    ], ids=lambda v: v[7:25] if isinstance(v, str) else "")
    def test_gradients(self, proto, shapes, rng):
        layer, params, state = make_layer(f'name: "l" {proto}', shapes)
        bottoms = [rand(s, rng) for s in shapes]
        check_gradients(layer, params, state, bottoms)

    def test_crop_gradients(self, rng):
        layer, params, state = make_layer(
            'name: "c" type: "Crop" bottom: "x" bottom: "ref" top: "y"\n'
            'crop_param { axis: 2 offset: 1 }',
            [(1, 2, 5, 5), (1, 2, 3, 3)],
        )
        check_gradients(layer, params, state,
                        [rand((1, 2, 5, 5), rng), rand((1, 2, 3, 3), rng)],
                        bottoms_to_check=[0])


class TestEmbed:
    def test_forward_and_grad(self, rng):
        layer, params, state = make_layer(
            'name: "e" type: "Embed" bottom: "i" top: "y"\n'
            'embed_param { num_output: 4 input_dim: 10\n'
            '  weight_filler { type: "gaussian" std: 1 } }',
            [(5,)],
        )
        idx = jnp.asarray(rng.randint(0, 10, 5))
        (y,), _ = layer.apply(params, state, [idx], train=False, rng=None)
        np.testing.assert_allclose(
            np.array(y), np.array(params["weight"])[np.array(idx)] +
            np.array(params["bias"]), rtol=1e-5)
        check_gradients(layer, params, state, [idx], bottoms_to_check=[])


class TestMVN:
    def test_normalizes(self, rng):
        layer, params, state = make_layer(
            'name: "m" type: "MVN" bottom: "x" top: "y"', [(3, 2, 4, 4)])
        x = rand((3, 2, 4, 4), rng, scale=3.0) + 2.0
        (y,), _ = layer.apply(params, state, [x], train=False, rng=None)
        yn = np.array(y)
        np.testing.assert_allclose(yn.mean(axis=(2, 3)), 0, atol=1e-5)
        np.testing.assert_allclose(yn.std(axis=(2, 3)), 1, atol=1e-2)
