"""Gradcheck-style tests for the fused kernels — the TPU analog of the
reference's fp64 ``torch.autograd.gradcheck`` self-test (resnet.py:316-319)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faster_distributed_training_tpu.ops import (
    conv_bn_reference, fused_conv_bn, fused_mlp, mlp_reference)


def _rand(key, *shape):
    return jax.random.normal(key, shape, dtype=jnp.float64)


class TestFusedConvBN:
    @pytest.mark.parametrize("stride,padding,hw,cin,cout,k", [
        (1, 1, 8, 3, 5, 3),
        (1, 0, 6, 4, 4, 1),
        (2, 1, 8, 3, 6, 3),   # reference only supports stride 1; we support any
    ])
    def test_forward_matches_unfused(self, stride, padding, hw, cin, cout, k):
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = _rand(kx, 2, hw, hw, cin)
        w = _rand(kw, k, k, cin, cout)
        out, mean, var = fused_conv_bn(x, w, stride, padding)
        ref = conv_bn_reference(x, w, stride, padding)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-10)
        # stats are the conv output's batch stats
        from faster_distributed_training_tpu.ops.conv_bn import conv2d
        y = conv2d(x, w, stride, padding)
        np.testing.assert_allclose(np.asarray(mean), np.asarray(y.mean((0, 1, 2))),
                                   rtol=1e-10)
        assert np.all(np.asarray(var) > 0)

    @pytest.mark.parametrize("stride", [1, 2])
    def test_backward_matches_autodiff(self, stride):
        kx, kw, kg = jax.random.split(jax.random.PRNGKey(1), 3)
        x = _rand(kx, 2, 8, 8, 3)
        w = _rand(kw, 3, 3, 3, 5)

        def loss_fused(x, w):
            out, _, _ = fused_conv_bn(x, w, stride, 1)
            return jnp.sum(out * cot)

        def loss_ref(x, w):
            return jnp.sum(conv_bn_reference(x, w, stride, 1) * cot)

        out_shape = conv_bn_reference(x, w, stride, 1).shape
        cot = _rand(kg, *out_shape)
        gx_f, gw_f = jax.grad(loss_fused, argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r), rtol=1e-8,
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r), rtol=1e-8,
                                   atol=1e-10)

    def test_jit_and_remat_compile(self):
        # the fused op must be jittable and differentiable under jit
        kx, kw = jax.random.split(jax.random.PRNGKey(2))
        x = jax.random.normal(kx, (4, 8, 8, 3), dtype=jnp.float32)
        w = jax.random.normal(kw, (3, 3, 3, 8), dtype=jnp.float32) * 0.1

        @jax.jit
        def step(x, w):
            return jax.grad(lambda w: fused_conv_bn(x, w, 1, 1)[0].sum())(w)

        g = step(x, w)
        assert g.shape == w.shape and np.isfinite(np.asarray(g)).all()


class TestFusedMLP:
    def test_forward_and_backward_match(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        x = _rand(ks[0], 4, 7, 20)      # leading batch dims like the reference's 3-D input
        w1 = _rand(ks[1], 30, 20) * 0.3
        b1 = _rand(ks[2], 1, 30) * 0.1
        w2 = _rand(ks[3], 10, 30) * 0.3
        b2 = _rand(ks[4], 1, 10) * 0.1
        cot = _rand(ks[5], 4, 7, 10)

        out = fused_mlp(x, w1, b1, w2, b2)
        ref = mlp_reference(x, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-12)

        gf = jax.grad(lambda *a: jnp.sum(fused_mlp(*a) * cot), argnums=(0, 1, 2, 3, 4))(
            x, w1, b1, w2, b2)
        gr = jax.grad(lambda *a: jnp.sum(mlp_reference(*a) * cot), argnums=(0, 1, 2, 3, 4))(
            x, w1, b1, w2, b2)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9,
                                       atol=1e-12)

    def test_no_bias(self):
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        x = _rand(ks[0], 5, 8)
        w1 = _rand(ks[1], 16, 8)
        w2 = _rand(ks[2], 3, 16)
        out = fused_mlp(x, w1, None, w2, None)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mlp_reference(x, w1, None, w2, None)),
                                   rtol=1e-12)
        g = jax.grad(lambda w: fused_mlp(x, w, None, w2, None).sum())(w1)
        assert g.shape == w1.shape

    def test_pallas_kernel_matches_reference(self):
        # interpret-mode run of the Pallas forward (non-aligned shapes
        # exercise the row-padding path); backward shares _mlp_bwd.
        from faster_distributed_training_tpu.ops import fused_mlp_pallas
        ks = jax.random.split(jax.random.PRNGKey(6), 6)
        x = _rand(ks[0], 3, 11, 20)
        w1 = _rand(ks[1], 30, 20) * 0.3
        b1 = _rand(ks[2], 1, 30) * 0.1
        w2 = _rand(ks[3], 10, 30) * 0.3
        b2 = _rand(ks[4], 1, 10) * 0.1
        cot = _rand(ks[5], 3, 11, 10)
        np.testing.assert_allclose(
            np.asarray(fused_mlp_pallas(x, w1, b1, w2, b2)),
            np.asarray(mlp_reference(x, w1, b1, w2, b2)), rtol=1e-5, atol=1e-6)
        gp = jax.grad(lambda *a: jnp.sum(fused_mlp_pallas(*a) * cot),
                      argnums=(0, 1, 2, 3, 4))(x, w1, b1, w2, b2)
        gr = jax.grad(lambda *a: jnp.sum(mlp_reference(*a) * cot),
                      argnums=(0, 1, 2, 3, 4))(x, w1, b1, w2, b2)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_pallas_in_transformer_model(self):
        # the mlp_impl='pallas' classifier path compiles and runs
        from faster_distributed_training_tpu.models import Transformer
        model = Transformer(n_class=4, vocab=50, n_layers=1, h=2, d_model=16,
                            d_ff=32, d_hidden=32, maxlen=12, alpha=0.0,
                            mlp_impl="pallas")
        tokens = jnp.ones((2, 10), jnp.int32)
        variables = model.init({"params": jax.random.PRNGKey(0)}, tokens,
                               train=False)
        logits = model.apply(variables, tokens, train=False)
        assert logits.shape == (2, 4)
        assert np.isfinite(np.asarray(logits)).all()

    def test_mean_bias_grad_parity_mode(self):
        # reference reduces bias grads with mean (transformer.py:311,327)
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        x = _rand(ks[0], 6, 20)
        w1, b1 = _rand(ks[1], 30, 20), _rand(ks[2], 1, 30)
        w2, b2 = _rand(ks[3], 10, 30), _rand(ks[4], 1, 10)
        g_sum = jax.grad(lambda b: fused_mlp(x, w1, b, w2, b2, False).sum())(b1)
        g_mean = jax.grad(lambda b: fused_mlp(x, w1, b, w2, b2, True).sum())(b1)
        np.testing.assert_allclose(np.asarray(g_mean) * x.shape[0],
                                   np.asarray(g_sum), rtol=1e-9)


class TestConvBNTrain:
    """conv_bn_train: remat and autodiff paths agree with the oracle."""

    def _xw(self, key, dtype=jnp.float64):
        x = jax.random.normal(jax.random.fold_in(key, 0), (4, 8, 8, 3), dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 3, 16),
                              dtype)
        return x, w

    @pytest.mark.parametrize("remat", [True, False])
    def test_forward_matches_reference(self, remat):
        from faster_distributed_training_tpu.ops.conv_bn import (
            conv_bn_reference, conv_bn_train)
        x, w = self._xw(jax.random.PRNGKey(5))
        out, mean, var = conv_bn_train(x, w, 1, 1, 1e-3, remat=remat)
        ref = conv_bn_reference(x, w, 1, 1, 1e-3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-10)
        assert mean.shape == (16,) and var.shape == (16,)

    @pytest.mark.parametrize("remat", [True, False])
    def test_gradients_match_reference(self, remat):
        from faster_distributed_training_tpu.ops.conv_bn import (
            conv_bn_reference, conv_bn_train)
        x, w = self._xw(jax.random.PRNGKey(6))

        def loss_train(x_, w_):
            return jnp.sum(conv_bn_train(x_, w_, 1, 1, 1e-3,
                                         remat=remat)[0] ** 2)

        def loss_ref(x_, w_):
            return jnp.sum(conv_bn_reference(x_, w_, 1, 1, 1e-3) ** 2)

        g1 = jax.grad(loss_train, argnums=(0, 1))(x, w)
        g2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-8, atol=1e-10)

    def test_degenerate_constant_channel_finite(self):
        """var==0 (constant conv output) must not produce NaN/inf grads in
        the hand-written backward (the clamp-edge guard)."""
        from faster_distributed_training_tpu.ops.conv_bn import fused_conv_bn
        x = jnp.ones((2, 4, 4, 1), jnp.float32)      # constant input
        w = jnp.ones((1, 1, 1, 4), jnp.float32)      # 1x1 conv -> constant y

        g = jax.grad(lambda x_: jnp.sum(
            fused_conv_bn(x_, w, 1, 0, 1e-3)[0] ** 2))(x)
        assert np.isfinite(np.asarray(g)).all()


class TestExpanding1x1ConvBN:
    """conv_bn_train's second custom_vjp: the expanding 1x1 convolution
    (1x1, stride 1, unpadded, cout > cin) whose backward reads the
    convolution's INPUT where fused_conv_bn's reads its output (ISSUE 28).
    The oracle is autodiff of conv_bn_reference."""

    @staticmethod
    def _xwc(cin, cout, dtype=jnp.float64, shift=0.0, hw=8, n=4):
        k = jax.random.PRNGKey(100 * cin + cout)
        x = jax.random.normal(jax.random.fold_in(k, 0), (n, hw, hw, cin),
                              jnp.float64) * 2.0 + shift
        w = jax.random.normal(jax.random.fold_in(k, 1), (1, 1, cin, cout),
                              jnp.float64)
        cot = jax.random.normal(jax.random.fold_in(k, 2), (n, hw, hw, cout),
                                jnp.float64)
        return x.astype(dtype), w.astype(dtype), cot.astype(dtype)

    @staticmethod
    def _grads(fn, x, w, cot):
        return jax.grad(lambda x_, w_: jnp.sum(fn(x_, w_) * cot),
                        argnums=(0, 1))(x, w)

    # shift != 0: sum g (y - mean) is a difference of two large sums on
    # this path (sum_k W G - mean g_sum): the cancellation must be exact
    @pytest.mark.parametrize("shift", [0.0, 7.5])
    @pytest.mark.parametrize("cin,cout", [(4, 16), (64, 256), (3, 5)])
    def test_gradients_match_reference(self, cin, cout, shift):
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, cot = self._xwc(cin, cout, shift=shift)
        got = self._grads(lambda x_, w_: conv_bn_train(x_, w_, 1, 0)[0],
                          x, w, cot)
        ref = self._grads(lambda x_, w_: conv_bn_reference(x_, w_, 1, 0),
                          x, w, cot)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-8, atol=1e-10)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("cin,cout", [(4, 16), (3, 5)])
    def test_forward_matches_shared_forward(self, cin, cout, dtype):
        """The forward takes mean and variance from the INPUT's column sums
        and Gram matrix (ISSUE 30), so it equals ``_conv_bn_forward`` to
        rounding, not to the bit: the statistics within 1e-5 (float32) /
        1e-3 (bfloat16: the shared forward's are taken from ``y`` after its
        rounding) - the mean against the channel's standard deviation, the
        variance relative - and ``out`` within 1e-5 / one bfloat16 ulp at
        the larger of the value's binade and 1."""
        from faster_distributed_training_tpu.ops.conv_bn import (
            _conv_bn_forward, conv_bn_train)
        x, w, _ = self._xwc(cin, cout, dtype, shift=1.0)
        out, _, mean, var = (np.asarray(a, np.float32) for a in
                             _conv_bn_forward(x, w, 1, 0, 1e-3))
        got = conv_bn_train(x, w, 1, 0, 1e-3)
        assert [a.dtype for a in got] == [dtype, jnp.float32, jnp.float32]
        g_out, g_mean, g_var = (np.asarray(a, np.float32) for a in got)
        tol = 1e-5 if dtype == jnp.float32 else 1e-3
        assert np.max(np.abs(g_mean - mean) / np.sqrt(var)) < tol
        np.testing.assert_allclose(g_var, var, rtol=tol)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g_out, out, rtol=1e-5, atol=1e-5)
        else:
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(out), 1.0))) - 7)
            assert np.all(np.abs(g_out - out) <= ulp)

    def test_statistics_no_further_from_float64_than_shared_forward(self):
        """bf16 post-ReLU input with a large mean (the cancellation in
        ``E[y^2] - mean^2``), 64 -> 256, against float64 on the same bf16
        operands: the Gram route accumulates in float32 from the operands,
        the shared forward reads ``y`` after its rounding to bf16."""
        from faster_distributed_training_tpu.ops.conv_bn import (
            _conv_bn_forward, conv_bn_train)
        x, w, _ = self._xwc(64, 256, jnp.float32, shift=7.5, hw=16)
        x, w = jax.nn.relu(x).astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        y = np.einsum("nhwk,kc->nhwc", np.asarray(x, np.float64),
                      np.asarray(w, np.float64)[0, 0])
        mean = y.mean((0, 1, 2))
        var = y.var((0, 1, 2), ddof=1)
        assert np.median(mean ** 2 / var) > 5.0  # the cancellation is there

        def gaps(m, v):
            return (np.max(np.abs(np.asarray(m, np.float64) - mean)
                           / np.sqrt(var)),
                    np.max(np.abs(np.asarray(v, np.float64) / var - 1.0)))

        _, _, s_mean, s_var = _conv_bn_forward(x, w, 1, 0, 1e-3)
        _, g_mean, g_var = conv_bn_train(x, w, 1, 0, 1e-3)
        for got, shared in zip(gaps(g_mean, g_var), gaps(s_mean, s_var)):
            assert got <= shared, (got, shared)
            assert got < 1e-4, got

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_clamps_a_constant_channel(self, dtype):
        """A constant input: ``E[y^2] - mean^2`` is round-off of either sign
        and the forward's clamp holds it at zero or above, as ``_bn_stats``'
        does; ``out`` is finite and (nearly) centred."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x = jnp.full((4, 8, 8, 3), 3.3, dtype)
        w = jax.random.normal(jax.random.PRNGKey(5), (1, 1, 3, 7)).astype(dtype)
        out, mean, var = (np.asarray(a, np.float32)
                          for a in conv_bn_train(x, w, 1, 0, 1e-3))
        assert np.all(var >= 0.0) and np.all(var < 1e-4 * (1 + mean ** 2))
        assert np.isfinite(out).all() and np.max(np.abs(out)) < 20.0

    def test_stats_cotangents_ignored_as_in_fused(self):
        """mean/var are stats-only outputs on both custom_vjps: a loss that
        reads them gets the gradient of its `out` term alone."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, cot = self._xwc(4, 16, shift=1.0)

        def loss(x_, w_):
            out, mean, var = conv_bn_train(x_, w_, 1, 0)
            return jnp.sum(out * cot) + 3.0 * jnp.sum(mean) + jnp.sum(var ** 2)

        got = jax.grad(loss, argnums=(0, 1))(x, w)
        ref = self._grads(lambda x_, w_: conv_bn_reference(x_, w_, 1, 0),
                          x, w, cot)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-8, atol=1e-10)

    # what the shape dispatch leaves alone must read what it read: the
    # reducing and the square 1x1, a strided 1x1, a padded 1x1, a 3x3
    @pytest.mark.parametrize("k,cin,cout,stride,padding", [
        (1, 4, 4, 1, 0), (1, 16, 4, 1, 0), (1, 4, 16, 2, 0),
        (1, 4, 16, 1, 1), (3, 4, 16, 1, 1)])
    def test_other_shapes_bitwise_equal_fused_conv_bn(self, k, cin, cout,
                                                      stride, padding):
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        key = jax.random.PRNGKey(7)
        x = jax.random.normal(jax.random.fold_in(key, 0), (4, 8, 8, cin),
                              jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (k, k, cin, cout),
                              jnp.float32)

        def grads(fn):
            return jax.grad(lambda x_, w_: jnp.sum(fn(x_, w_)[0] ** 2),
                            argnums=(0, 1))(x, w)

        got = grads(lambda x_, w_: conv_bn_train(x_, w_, stride, padding))
        ref = grads(lambda x_, w_: fused_conv_bn(x_, w_, stride, padding))
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("scope", ["fdt/conv1x1_bn_stats",
                                       "fdt/conv1x1_bn_bwd"])
    @pytest.mark.parametrize("k,cin,cout,remat,scoped", [
        (1, 4, 16, True, True), (1, 4, 16, False, False),
        (1, 16, 4, True, False), (3, 4, 16, True, False)])
    def test_scope_names_the_layers_that_take_the_path(self, k, cin, cout,
                                                       remat, scoped, scope):
        """`fdt/conv1x1_bn_stats` (forward) and `fdt/conv1x1_bn_bwd` are the
        path's static counters: each is in the lowered program exactly
        where the path is taken."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x = jnp.ones((2, 4, 4, cin), jnp.float32)
        w = jnp.ones((k, k, cin, cout), jnp.float32)
        text = jax.jit(jax.grad(lambda x_: jnp.sum(conv_bn_train(
            x_, w, 1, k // 2, remat=remat)[0] ** 2))).lower(x).as_text(
                debug_info=True)
        assert (scope in text) == scoped

    def test_bf16_within_2e2_of_float32_oracle(self):
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, cot = self._xwc(64, 256, jnp.float32, shift=0.5, hw=16)
        ref = self._grads(lambda x_, w_: conv_bn_reference(x_, w_, 1, 0),
                          x, w, cot)
        got = self._grads(lambda x_, w_: conv_bn_train(x_, w_, 1, 0)[0],
                          *(a.astype(jnp.bfloat16) for a in (x, w, cot)))
        for a, b in zip(got, ref):
            assert a.dtype == jnp.bfloat16
            gap = (np.linalg.norm(np.asarray(a, np.float32) - np.asarray(b))
                   / np.linalg.norm(np.asarray(b)))
            assert gap < 2e-2, gap

    def test_degenerate_constant_channel_finite(self):
        """TestConvBNTrain's clamp-edge case on THIS path: var == 0."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x = jnp.ones((2, 4, 4, 1), jnp.float32)
        w = jnp.ones((1, 1, 1, 4), jnp.float32)
        gx, gw = jax.grad(lambda x_, w_: jnp.sum(
            (conv_bn_train(x_, w_, 1, 0, 1e-3)[0] + 1.0) ** 2),
            argnums=(0, 1))(x, w)
        assert np.isfinite(np.asarray(gx)).all()
        assert np.isfinite(np.asarray(gw)).all()

    def test_no_conv_output_shaped_backward_residual(self):
        """The point of the path: nothing of the output's [N,H,W,cout] size
        lives from forward to backward (fused_conv_bn's residuals are the
        same four, but its backward asks for y again and XLA keeps it)."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, _ = self._xwc(4, 16, jnp.float32)
        out, vjp = jax.vjp(lambda x_, w_: conv_bn_train(x_, w_, 1, 0)[0],
                           x, w)
        leaves = jax.tree.leaves(vjp)
        assert any(np.shape(leaf) == x.shape for leaf in leaves)
        for leaf in leaves:
            assert np.size(leaf) < out.size, np.shape(leaf)

    def test_backward_takes_gram_from_forward(self):
        """``S = x^T x`` and ``xsum`` are the forward's (its statistics need
        them) and reach the backward as residuals: the lowered backward
        holds no ``nhwk,nhwl->kl`` contraction of its own and the residuals
        hold the ``[K, K]`` leaf (none of the output's size: the next test)."""
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, cot = self._xwc(4, 16, jnp.float32)
        gram = "nhwk,nhwl->kl"
        whole = jax.jit(jax.grad(lambda x_: jnp.sum(
            conv_bn_train(x_, w, 1, 0)[0] * cot))).lower(x).as_text(
                debug_info=True)
        assert gram in whole
        _, vjp = jax.vjp(lambda x_, w_: conv_bn_train(x_, w_, 1, 0)[0], x, w)
        assert gram not in jax.jit(vjp).lower(cot).as_text(debug_info=True)
        assert (4, 4) in [np.shape(leaf) for leaf in jax.tree.leaves(vjp)]

    def test_batch_sharded_gives_global_sums(self, devices8):
        """The contractions over rows are plain sums over the batch axis:
        under a batch-sharded mesh they stay GLOBAL, as the statistics."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from faster_distributed_training_tpu.ops.conv_bn import conv_bn_train
        x, w, cot = self._xwc(4, 16, n=8)
        fn = jax.jit(lambda x_, w_, c_: self._grads(
            lambda a, b: conv_bn_train(a, b, 1, 0)[0], x_, w_, c_))
        ref = fn(x, w, cot)
        mesh = Mesh(np.asarray(devices8), ("dp",))
        rows = NamedSharding(mesh, P("dp"))
        got = fn(jax.device_put(x, rows),
                 jax.device_put(w, NamedSharding(mesh, P())),
                 jax.device_put(cot, rows))
        for a, b in zip(got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-10, atol=1e-12)


class TestFusedFFNSublayer:
    """ops/fused_ffn.py — the whole pre-LN FFN sublayer (LN -> Dense ->
    GELU -> dropout -> Dense -> dropout -> +residual) as ONE Pallas
    kernel with a vjp-of-reference recompute backward.  Measured role
    (PARITY): an intermediate capacity rung (-11% peak memory for +8%
    step time at bs256/seq512), NOT a throughput win — XLA's
    saved-intermediate autodiff beats recompute on time."""

    def _inputs(self, dtype=jnp.float32, B=4, L=8, d=32, dff=64):
        rr = np.random.default_rng(0)
        h = jnp.asarray(rr.normal(size=(B, L, d)), dtype)
        lns = jnp.asarray(rr.normal(size=(d,)) * 0.1 + 1.0, jnp.float32)
        lnb = jnp.asarray(rr.normal(size=(d,)) * 0.1, jnp.float32)
        w1 = jnp.asarray(rr.normal(size=(d, dff)) * 0.1, dtype)
        b1 = jnp.asarray(rr.normal(size=(dff,)) * 0.1, dtype)
        w2 = jnp.asarray(rr.normal(size=(dff, d)) * 0.1, dtype)
        b2 = jnp.asarray(rr.normal(size=(d,)) * 0.1, dtype)
        return h, lns, lnb, w1, b1, w2, b2

    @pytest.mark.parametrize("rates", [(0.0, 0.0), (0.1, 0.1)])
    def test_kernel_matches_reference_fwd_and_grads(self, rates):
        from faster_distributed_training_tpu.ops.fused_ffn import (
            ffn_sublayer_reference, fused_ffn_sublayer)

        args = self._inputs()
        s1, s2 = jnp.uint32(11), jnp.uint32(22)
        rh, rc = rates
        out = fused_ffn_sublayer(*args, s1, s2, rh, rc)
        ref = ffn_sublayer_reference(*args, s1, s2, rh, rc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        gk = jax.grad(lambda *a: jnp.sum(
            fused_ffn_sublayer(*a, s1, s2, rh, rc) ** 2),
            argnums=tuple(range(7)))(*args)
        gr = jax.grad(lambda *a: jnp.sum(
            ffn_sublayer_reference(*a, s1, s2, rh, rc) ** 2),
            argnums=tuple(range(7)))(*args)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_no_ffn_shaped_backward_residuals(self):
        """The custom_vjp must save INPUTS only: no residual leaf may
        carry the (rows, d_ff) hidden shape — that is the whole point
        of the fusion (capacity)."""
        from faster_distributed_training_tpu.ops.fused_ffn import (
            fused_ffn_sublayer)

        h, lns, lnb, w1, b1, w2, b2 = self._inputs(B=16)
        n_hidden = h.shape[0] * h.shape[1] * w1.shape[1]
        _, vjp = jax.vjp(
            lambda h_: fused_ffn_sublayer(h_, lns, lnb, w1, b1, w2, b2,
                                          jnp.uint32(1), jnp.uint32(2),
                                          0.1, 0.1), h)
        for leaf in jax.tree.leaves(vjp):
            assert np.size(leaf) < n_hidden, np.shape(leaf)

    def test_dropout_stream_matches_hash_dropout(self):
        """The in-kernel masks must equal ops.dropout.hash_dropout on the
        full tensor (same (seed, global-index) stream), so backward
        regeneration and the module-level engine agree — including at a
        NONZERO row offset and through the sharded _global_rows mapping."""
        from faster_distributed_training_tpu.ops.dropout import (
            hash_dropout, keep_factor_rows, keep_factor_tile)
        from faster_distributed_training_tpu.ops.fused_ffn import (
            _global_rows)

        seed = jnp.uint32(77)
        rows, cols = 16, 32
        ones = jnp.ones((rows, cols), jnp.float32)
        via_tile = np.asarray(
            ones * keep_factor_tile(seed, jnp.uint32(0), rows, cols, 0.3))
        via_module = np.asarray(hash_dropout(ones, seed, 0.3))
        np.testing.assert_array_equal(via_tile, via_module)
        # row0=6: the tile must reproduce rows 6.. of the full stream
        tail = np.asarray(jnp.ones((rows - 6, cols), jnp.float32)
                          * keep_factor_tile(seed, jnp.uint32(6), rows - 6,
                                             cols, 0.3))
        np.testing.assert_array_equal(tail, via_module[6:])
        # the sharded global-rows mapping: a (B=4, L=4) shard at batch
        # offset 2, seq offset 0 of an L_glob=8 tensor addresses rows
        # {(2+b)*8 + s} of the global stream
        g = _global_rows(jnp.arange(8, dtype=jnp.uint32), b0=2, s0=0,
                         l_loc=4, l_glob=8)
        expect = [(2 + r // 4) * 8 + r % 4 for r in range(8)]
        np.testing.assert_array_equal(np.asarray(g), expect)
        shard = np.asarray(keep_factor_rows(seed, g, cols, 0.3))
        full = np.asarray(keep_factor_tile(seed, jnp.uint32(0), 40, cols,
                                           0.3))
        np.testing.assert_array_equal(shard, full[np.asarray(expect)])
        # rate ~1 drops everything instead of dividing by zero
        assert float(np.abs(keep_factor_tile(
            seed, jnp.uint32(0), 4, 8, 1.0 - 1e-9)).max()) == 0.0

    def test_multi_block_grid_and_padding(self):
        """Rows > block_rows exercise the grid>1 path (per-block row0
        dropout offsets) and a non-multiple row count exercises the
        pad-and-slice path — both must still match the reference."""
        from faster_distributed_training_tpu.ops.fused_ffn import (
            ffn_sublayer_reference, fused_ffn_sublayer)

        # 300 rows with block_rows=256 -> 2 blocks, 212 rows of padding
        args = self._inputs(B=30, L=10)
        s1, s2 = jnp.uint32(5), jnp.uint32(6)
        out = fused_ffn_sublayer(*args, s1, s2, 0.3, 0.2)
        ref = ffn_sublayer_reference(*args, s1, s2, 0.3, 0.2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        gk = jax.grad(lambda h: jnp.sum(
            fused_ffn_sublayer(h, *args[1:], s1, s2, 0.3, 0.2) ** 2))(args[0])
        gr = jax.grad(lambda h: jnp.sum(
            ffn_sublayer_reference(h, *args[1:], s1, s2, 0.3, 0.2) ** 2))(
            args[0])
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)

    def test_erf_polynomial_accuracy(self):
        """Mosaic has no erf; the A&S 7.1.26 polynomial must stay within
        ~5e-7 of lax.erf in fp32 (measured 4.2e-7 — far below bf16's
        ~8e-3 resolution)."""
        from faster_distributed_training_tpu.ops.fused_ffn import _erf_f32

        x = jnp.linspace(-6.0, 6.0, 4001, dtype=jnp.float32)
        err = np.abs(np.asarray(_erf_f32(x))
                     - np.asarray(jax.lax.erf(x)))
        assert float(err.max()) < 1e-6

    @pytest.mark.slow  # r20 budget diet: 28 s — sharded-vs-unsharded
    # kernel parity incl. dropout placement-invariance is tier-1 in
    # tests/test_kernel_shard.py (the r19 layer this wrapper predates)
    def test_sharded_wrapper_matches_unsharded(self, devices8):
        """fused_ffn_sublayer_sharded is PLACEMENT-INVARIANT (the
        codebase's sharded-dropout convention, ops/attention.py
        dropout_keep): per-shard kernels address the GLOBAL dropout
        index space through their (batch, seq) offsets, so the same
        global batch reproduces the unsharded output and gradients
        exactly — WITH dropout active, on batch-sharded and
        sequence-sharded meshes alike."""
        from faster_distributed_training_tpu.ops.fused_ffn import (
            fused_ffn_sublayer, fused_ffn_sublayer_sharded)
        from faster_distributed_training_tpu.parallel import make_mesh

        args = self._inputs(B=16)
        s1, s2 = jnp.uint32(3), jnp.uint32(4)
        plain = fused_ffn_sublayer(*args, s1, s2, 0.0, 0.0)
        plain_d = np.asarray(fused_ffn_sublayer(*args, s1, s2, 0.4, 0.3))
        gp = jax.grad(lambda h: jnp.sum(
            fused_ffn_sublayer(h, *args[1:], s1, s2, 0.4, 0.3) ** 2))(args[0])

        for axes, shape in ((("dp",), (8,)), (("dp", "sp"), (2, 4))):
            mesh = make_mesh(axes, shape, devices8)
            with mesh:
                sh = fused_ffn_sublayer_sharded(*args, s1, s2, mesh=mesh)
                sh_d = np.asarray(fused_ffn_sublayer_sharded(
                    *args, s1, s2, mesh=mesh, rate_hidden=0.4,
                    rate_conn=0.3))
                gs = jax.grad(lambda h: jnp.sum(
                    fused_ffn_sublayer_sharded(h, *args[1:], s1, s2,
                                               mesh=mesh, rate_hidden=0.4,
                                               rate_conn=0.3) ** 2))(args[0])
            np.testing.assert_allclose(np.asarray(sh), np.asarray(plain),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=str(axes))
            np.testing.assert_array_equal(
                sh_d == 0.0, plain_d == 0.0)   # identical drop pattern
            np.testing.assert_allclose(sh_d, plain_d, rtol=1e-5, atol=1e-6,
                                       err_msg=str(axes))
            np.testing.assert_allclose(np.asarray(gs), np.asarray(gp),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=str(axes))

    def test_model_param_tree_identical_and_eval_equal(self):
        """ffn_impl='pallas' must keep the EXACT param tree of the flax
        path (checkpoints interchange) and agree at eval."""
        from faster_distributed_training_tpu.models import Transformer

        x = jnp.asarray(np.random.default_rng(0).integers(0, 64, size=(4, 8)),
                        jnp.int32)
        rng = jax.random.PRNGKey(0)
        models, trees = {}, {}
        for impl in ("flax", "pallas"):
            m = Transformer(n_class=4, vocab=64, n_layers=2, h=2, d_model=16,
                            d_ff=32, d_hidden=16, maxlen=8, ffn_impl=impl)
            v = m.init({"params": rng, "dropout": rng, "mixup": rng},
                       x, train=True)
            models[impl] = m
            trees[impl] = (jax.tree_util.tree_structure(v["params"]), v)
        assert trees["flax"][0] == trees["pallas"][0]
        params = trees["flax"][1]["params"]
        ef = models["flax"].apply({"params": params}, x, train=False)
        ep = models["pallas"].apply({"params": params}, x, train=False)
        np.testing.assert_allclose(np.asarray(ef), np.asarray(ep),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.slow  # r21 budget diet: 12 s — kernel fwd/grad parity
    # vs the reference stays tier-1 above; full-model training through
    # the pallas FFN stays tier-1 in test_train (8dev-mesh fused FFN)
    def test_model_trains_through_kernel(self):
        from faster_distributed_training_tpu.models import Transformer

        m = Transformer(n_class=4, vocab=64, n_layers=2, h=2, d_model=16,
                        d_ff=32, d_hidden=16, maxlen=8, ffn_impl="pallas")
        x = jnp.asarray(np.random.default_rng(1).integers(0, 64, size=(4, 8)),
                        jnp.int32)
        rng = jax.random.PRNGKey(0)
        v = m.init({"params": rng, "dropout": rng, "mixup": rng},
                   x, train=True)

        def loss(p):
            lg, idx, lam = m.apply({"params": p}, x, train=True,
                                   rngs={"dropout": jax.random.PRNGKey(1),
                                         "mixup": jax.random.PRNGKey(2)})
            return jnp.mean(lg ** 2)

        l, g = jax.value_and_grad(loss)(v["params"])
        assert np.isfinite(float(l))
        assert all(np.all(np.isfinite(np.asarray(t)))
                   for t in jax.tree.leaves(g))
        # FFN weights actually receive gradient through the kernel path
        gffn = g["layer_0"]["ffn"]["Dense_0"]["kernel"]
        assert float(jnp.max(jnp.abs(gffn))) > 0.0


class TestSavedStatsLayerNorm:
    """ops/layernorm.py torch_layernorm (VERDICT r5 #4): the saved-
    (mean, rstd) custom_vjp must be forward-BIT-IDENTICAL to the pure
    fp32 math at the reference's NONSTANDARD semantics (UNBIASED n-1
    variance, eps added to the STD, not the variance) and gradient-equal
    to XLA autodiff of that math — the 13 LN sites all route through it,
    so a backward-math slip would corrupt every transformer gradient."""

    def _xsb(self, key, shape=(3, 5, 16), dtype=jnp.float32):
        ks = jax.random.split(key, 3)
        return (jax.random.normal(ks[0], shape, dtype),
                jax.random.normal(ks[1], shape[-1:], dtype),
                jax.random.normal(ks[2], shape[-1:], dtype))

    def test_forward_bit_identical_and_unbiased_semantics(self):
        from faster_distributed_training_tpu.ops.layernorm import (
            _ln_saved_stats, torch_layernorm, torch_layernorm_f32)
        x, s, b = self._xsb(jax.random.PRNGKey(0))
        eps = 1e-6
        got = torch_layernorm(x, s, b, eps)
        pure = torch_layernorm_f32(x, s, b, eps)
        assert np.array_equal(np.asarray(got), np.asarray(pure))
        assert np.array_equal(np.asarray(_ln_saved_stats(x, s, b, eps)),
                              np.asarray(pure))
        # explicit reference of the nonstandard semantics
        xn = np.asarray(x, np.float64)
        mean = xn.mean(-1, keepdims=True)
        var = ((xn - mean) ** 2).sum(-1, keepdims=True) / (xn.shape[-1] - 1)
        ref = (np.asarray(s, np.float64) * (xn - mean)
               / (np.sqrt(var) + eps) + np.asarray(b, np.float64))
        np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                   atol=2e-6)

    @pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                            (jnp.float64, 1e-10)])
    def test_backward_matches_autodiff(self, dtype, rtol):
        from faster_distributed_training_tpu.ops.layernorm import (
            _ln_saved_stats, torch_layernorm_f32)
        x, s, b = self._xsb(jax.random.PRNGKey(1), dtype=dtype)
        eps = 1e-6

        def loss_vjp(x_, s_, b_):
            return jnp.sum(jnp.sin(_ln_saved_stats(x_, s_, b_, eps)))

        def loss_ref(x_, s_, b_):
            return jnp.sum(jnp.sin(torch_layernorm_f32(x_, s_, b_, eps)))

        g_vjp = jax.grad(loss_vjp, argnums=(0, 1, 2))(x, s, b)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, s, b)
        for name, a, c in zip(("x", "scale", "bias"), g_vjp, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=rtol, atol=rtol,
                                       err_msg=f"d{name} mismatch")

    def test_residuals_are_input_plus_two_scalars_per_row(self):
        # the point of the VJP: residual tensors are x, scale, and ONE
        # (mean, rstd) scalar pair per row — nothing normalized-shaped
        from faster_distributed_training_tpu.ops.layernorm import _ln_fwd
        x, s, b = self._xsb(jax.random.PRNGKey(2))
        out, res = _ln_fwd(x, s, b, 1e-6)
        x_r, s_r, mean, rstd = res
        assert x_r.shape == x.shape and s_r.shape == s.shape
        assert mean.shape == x.shape[:-1] + (1,)
        assert rstd.shape == x.shape[:-1] + (1,)

    def test_kill_switch_restores_default_autodiff(self, monkeypatch):
        from faster_distributed_training_tpu.ops import layernorm as ln
        x, s, b = self._xsb(jax.random.PRNGKey(3))
        monkeypatch.setenv("FDT_LN_SAVED_STATS", "0")
        off = ln.torch_layernorm(x, s, b, 1e-6)
        monkeypatch.delenv("FDT_LN_SAVED_STATS")
        on = ln.torch_layernorm(x, s, b, 1e-6)
        assert np.array_equal(np.asarray(off), np.asarray(on))

    def test_transformer_layernorm_module_routes_through_vjp(self):
        # TorchLayerNorm (models/transformer.py) delegates here; its
        # grads must equal the pure-math autodiff at model shapes
        from faster_distributed_training_tpu.models.transformer import (
            TorchLayerNorm)
        from faster_distributed_training_tpu.ops.layernorm import (
            torch_layernorm_f32)
        m = TorchLayerNorm()
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 32),
                              jnp.float32)
        v = m.init(jax.random.PRNGKey(5), x)

        def loss(p, x_):
            return jnp.sum(m.apply(p, x_) ** 2)

        gx = jax.grad(loss, argnums=1)(v, x)

        def loss_ref(x_):
            return jnp.sum(torch_layernorm_f32(
                x_, v["params"]["scale"], v["params"]["bias"], m.eps) ** 2)

        gx_ref = jax.grad(loss_ref)(x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                                   rtol=2e-5, atol=2e-6)


class TestFfnVmemDtypeBytes:
    """r13 satellite: ffn_kernel_fits_vmem's weight-byte parameter must
    follow the ACTUAL compute dtype at the build_model call site — an
    fp32 run must not falsely pass the budget sized for bf16, and
    1-byte (quantized) weights must not be falsely rejected.  The
    (1280, 1280) cell is chosen to straddle the 12 MiB budget: weights
    alone are 6.25 MiB at bf16, 12.5 MiB at fp32, 3.13 MiB at int8."""

    def test_w_bytes_drive_the_verdict(self):
        from faster_distributed_training_tpu.ops.fused_ffn import (
            ffn_kernel_fits_vmem)
        assert ffn_kernel_fits_vmem(1280, 1280, w_bytes=2)       # bf16
        assert not ffn_kernel_fits_vmem(1280, 1280, w_bytes=4)   # fp32
        assert ffn_kernel_fits_vmem(1280, 1280, w_bytes=1)       # int8

    def test_build_model_passes_compute_dtype_itemsize(self):
        import warnings as _w
        from faster_distributed_training_tpu.cli import build_model
        from faster_distributed_training_tpu.config import TrainConfig

        def mk(precision):
            return TrainConfig(model="transformer", dataset="synthetic",
                               num_classes=4, batch_size=4, seq_len=16,
                               n_layers=1, d_model=1280, d_ff=1280,
                               n_heads=4, precision=precision,
                               attention="dense", ffn_impl="pallas")

        with pytest.warns(UserWarning, match="VMEM budget"):
            m32 = build_model(mk("fp32"), vocab_size=100)
        assert m32.ffn_impl == "flax"      # fp32 weights bust the budget
        with _w.catch_warnings(record=True) as caught:
            _w.simplefilter("always")
            m16 = build_model(mk("bf16"), vocab_size=100)
        assert m16.ffn_impl == "pallas"    # bf16 weights fit
        assert not any("VMEM budget" in str(c.message) for c in caught)
