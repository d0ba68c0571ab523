"""Per-kernel allclose sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.conv2d import conv2d_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestConv2d:
    @pytest.mark.parametrize("B,H,W,Cin,Cout,k", [
        (1, 8, 8, 1, 4, 3),
        (2, 16, 16, 3, 8, 3),
        (2, 12, 12, 4, 16, 5),
        (1, 7, 9, 2, 4, 3),          # odd spatial
        (4, 16, 16, 8, 16, 3),       # the fwd+bwd gate's shape
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, H, W, Cin, Cout, k, dtype):
        key = jax.random.PRNGKey(hash((B, H, W, Cin, Cout, k)) % 2**31)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (B, H, W, Cin), dtype)
        w = rand(k2, (k, k, Cin, Cout), dtype)
        got = conv2d_pallas(x, w)
        want = ref.conv2d_ref(x, w)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32),
            atol=TOL[dtype] * k * k * Cin, rtol=1e-2)

    def test_oc_tiling(self):
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (1, 8, 8, 3), jnp.float32)
        w = rand(k2, (3, 3, 3, 8), jnp.float32)
        a = conv2d_pallas(x, w, oc_tile=4)
        b = conv2d_pallas(x, w, oc_tile=8)
        np.testing.assert_allclose(a, b, atol=1e-5)

    @pytest.mark.parametrize("activation", ["none", "relu"])
    def test_fused_bias_activation_epilogue(self, activation):
        """Eq. (1)+(2) in one pallas_call matches conv -> +b -> act."""
        key = jax.random.PRNGKey(11)
        k1, k2, k3 = jax.random.split(key, 3)
        x = rand(k1, (2, 8, 8, 3), jnp.float32)
        w = rand(k2, (3, 3, 3, 8), jnp.float32)
        b = rand(k3, (8,), jnp.float32)
        got = conv2d_pallas(x, w, b, activation=activation)
        want = ref.conv2d_ref(x, w) + b
        if activation == "relu":
            want = jax.nn.relu(want)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def _lax_conv(x, w, padding):
    """The lax.conv_general_dilated oracle the gradient checks gate on."""
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))


class TestConv2dGrad:
    """jax.grad through the Pallas custom_vjp vs the lax.conv reference."""

    GRID = [
        # seed, padding, oc_tile, k, shape (B, H, W, Cin, Cout)
        (0, "SAME", 0, 3, (2, 8, 8, 3, 8)),
        (1, "SAME", 4, 3, (2, 8, 8, 3, 8)),
        (2, "VALID", 0, 3, (2, 8, 8, 3, 8)),
        (3, "VALID", 4, 3, (2, 8, 8, 3, 8)),
        (4, "SAME", 0, 5, (1, 9, 7, 2, 4)),     # odd kernel, odd spatial
        (5, "VALID", 2, 5, (1, 9, 7, 2, 4)),
        (6, "SAME", 0, 1, (2, 6, 6, 4, 4)),     # 1x1 conv
        (7, "SAME", 0, 2, (2, 8, 8, 3, 8)),     # even k: asymmetric pads
        (8, "SAME", 4, 4, (1, 8, 8, 2, 8)),     # even k, tiled
        (9, "SAME", 0, 3, (4, 16, 16, 8, 16)),  # 4-image batch, 8 -> 16
    ]

    @pytest.mark.parametrize("seed,padding,oc_tile,k,shape", GRID)
    @pytest.mark.parametrize("activation", ["none", "relu"])
    def test_grads_match_lax(self, seed, padding, oc_tile, k, shape,
                             activation):
        B, H, W, Cin, Cout = shape
        key = jax.random.PRNGKey(seed)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        x = rand(k1, (B, H, W, Cin), jnp.float32)
        w = rand(k2, (k, k, Cin, Cout), jnp.float32)
        b = rand(k3, (Cout,), jnp.float32)

        def loss_ref(x_, w_, b_):
            out = _lax_conv(x_, w_, padding) + b_
            if activation == "relu":
                out = jax.nn.relu(out)
            return jnp.sum(out * cot)

        def loss_pallas(x_, w_, b_):
            out = conv2d_pallas(x_, w_, b_, padding=padding,
                                activation=activation, oc_tile=oc_tile)
            return jnp.sum(out * cot)

        out_shape = jax.eval_shape(lambda a, c: _lax_conv(a, c, padding),
                                   x, w).shape
        cot = rand(k4, out_shape, jnp.float32)   # non-uniform cotangent
        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, w, b)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        for g, r, name in zip(got, want, ("dx", "dw", "db"), strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"{name} mismatch")

    def test_dw_batch_tiled_accumulation(self):
        """B=16 runs the dw kernel's sequential batch-tile grid (bt=8)."""
        key = jax.random.PRNGKey(12)
        k1, k2, k3 = jax.random.split(key, 3)
        x = rand(k1, (16, 8, 8, 3), jnp.float32)
        w = rand(k2, (3, 3, 3, 8), jnp.float32)
        cot = rand(k3, (16, 8, 8, 8), jnp.float32)
        got = jax.grad(lambda w_: jnp.sum(
            conv2d_pallas(x, w_, oc_tile=4) * cot))(w)
        want = jax.grad(lambda w_: jnp.sum(
            _lax_conv(x, w_, "SAME") * cot))(w)
        np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)

    def test_db_keeps_bias_dtype_mixed_precision(self):
        """bf16 activations with a float32 master bias -> float32 db."""
        key = jax.random.PRNGKey(14)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (2, 8, 8, 2), jnp.bfloat16)
        w = rand(k2, (3, 3, 2, 4), jnp.bfloat16)
        b = jnp.zeros((4,), jnp.float32)
        db = jax.grad(lambda b_: jnp.sum(
            conv2d_pallas(x, w, b_).astype(jnp.float32)))(b)
        assert db.dtype == jnp.float32

    def test_dw_odd_batch(self):
        """Odd B exercises the gcd batch-tile fallback (bt=1)."""
        key = jax.random.PRNGKey(15)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (5, 8, 8, 2), jnp.float32)
        w = rand(k2, (3, 3, 2, 4), jnp.float32)
        got = jax.grad(lambda w_: jnp.sum(conv2d_pallas(x, w_) ** 2))(w)
        want = jax.grad(lambda w_: jnp.sum(_lax_conv(x, w_, "SAME") ** 2))(w)
        np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)

    def test_non_divisor_oc_tile_raises(self):
        key = jax.random.PRNGKey(13)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (1, 8, 8, 2), jnp.float32)
        w = rand(k2, (3, 3, 2, 8), jnp.float32)
        with pytest.raises(ValueError):
            conv2d_pallas(x, w, oc_tile=3)

    def test_forward_matches_lax(self):
        key = jax.random.PRNGKey(5)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (2, 10, 10, 3), jnp.float32)
        w = rand(k2, (3, 3, 3, 8), jnp.float32)
        for padding in ("SAME", "VALID"):
            got = conv2d_pallas(x, w, padding=padding)
            np.testing.assert_allclose(got, _lax_conv(x, w, padding),
                                       atol=1e-5, rtol=1e-5)

    def test_no_bias_grad(self):
        """b=None still differentiates wrt x and w."""
        key = jax.random.PRNGKey(6)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (1, 8, 8, 2), jnp.float32)
        w = rand(k2, (3, 3, 2, 4), jnp.float32)
        got = jax.grad(lambda w_: jnp.sum(conv2d_pallas(x, w_) ** 2))(w)
        want = jax.grad(lambda w_: jnp.sum(_lax_conv(x, w_, "SAME") ** 2))(w)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

    def test_grad_under_jit_and_vmap(self):
        """The fused trainer wraps the conv in jit(vmap(grad(...)))."""
        key = jax.random.PRNGKey(7)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (3, 2, 8, 8, 2), jnp.float32)       # (m, B, H, W, C)
        w = rand(k2, (3, 3, 2, 4), jnp.float32)

        def loss(x_):
            return jnp.sum(conv2d_pallas(x_, w, activation="relu"))

        got = jax.jit(jax.vmap(jax.grad(loss)))(x)
        want = jax.vmap(jax.grad(
            lambda x_: jnp.sum(jax.nn.relu(_lax_conv(x_, w, "SAME")))))(x)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KH,D", [
        (1, 64, 4, 4, 16),           # MHA
        (2, 100, 8, 2, 32),          # GQA, ragged seq
        (1, 128, 4, 1, 64),          # MQA
    ])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, S, H, KH, D, causal, dtype):
        key = jax.random.PRNGKey(hash((B, S, H, KH, D, causal)) % 2**31)
        ks = jax.random.split(key, 3)
        q = rand(ks[0], (B, H, S, D), dtype)
        k = rand(ks[1], (B, KH, S, D), dtype)
        v = rand(ks[2], (B, KH, S, D), dtype)
        got = flash_attention_pallas(q, k, v, causal=causal,
                                     q_tile=32, k_tile=32)
        want = ref.attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   atol=TOL[dtype] * 4, rtol=2e-2)

    @pytest.mark.parametrize("window,softcap", [(16, 0.0), (0, 30.0),
                                                (8, 50.0)])
    def test_window_and_softcap(self, window, softcap):
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 3)
        q = rand(ks[0], (1, 4, 96, 32), jnp.float32)
        k = rand(ks[1], (1, 2, 96, 32), jnp.float32)
        v = rand(ks[2], (1, 2, 96, 32), jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     softcap=softcap, q_tile=32, k_tile=32)
        want = ref.attention_ref(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=window or None,
            softcap=softcap).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)

    def test_ops_wrapper_layouts(self):
        """ops.flash_attention takes BSHD like the models."""
        key = jax.random.PRNGKey(3)
        ks = jax.random.split(key, 3)
        q = rand(ks[0], (2, 64, 4, 16), jnp.float32)
        k = rand(ks[1], (2, 64, 2, 16), jnp.float32)
        v = rand(ks[2], (2, 64, 2, 16), jnp.float32)
        got = ops.flash_attention(q, k, v, impl="pallas")
        want = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)


class TestRMSNorm:
    @settings(max_examples=20, deadline=None)
    @given(rows=st.integers(1, 300), d=st.sampled_from([8, 64, 128, 512]),
           seed=st.integers(0, 99))
    def test_matches_ref_hypothesis(self, rows, d, seed):
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (rows, d))
        s = jax.random.normal(k2, (d,)) * 0.1 + 1.0
        got = rmsnorm_pallas(x, s, row_tile=64)
        want = ref.rmsnorm_ref(x, s)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)

    @pytest.mark.parametrize("shape", [(5, 7, 64), (2, 3, 4, 32), (128,)])
    def test_nd_shapes(self, shape):
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, shape)
        s = jnp.ones((shape[-1],))
        got = rmsnorm_pallas(x, s)
        want = ref.rmsnorm_ref(x, s)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


class TestOpsSelection:
    def test_default_on_cpu_is_ref(self):
        assert ops.default_impl() in ("ref", "pallas")

    def test_conv_grad_via_ref(self):
        """The ref conv path is differentiable (used by CNN training)."""
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (1, 8, 8, 2))
        w = jax.random.normal(k2, (3, 3, 2, 4))
        g = jax.grad(lambda w_: ops.conv2d(x, w_, impl="ref").sum())(w)
        assert g.shape == w.shape and float(jnp.abs(g).sum()) > 0

    def test_conv_grad_pallas_matches_ref_dispatch(self):
        """Both dispatch impls agree on value AND gradient (fused epilogue)."""
        key = jax.random.PRNGKey(9)
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (2, 8, 8, 2))
        w = jax.random.normal(k2, (3, 3, 2, 4))
        b = jax.random.normal(k3, (4,))

        def loss(impl):
            def f(w_, b_):
                out = ops.conv2d(x, w_, b_, activation="relu", impl=impl)
                return jnp.sum(out ** 2)
            return f

        vp, (gwp, gbp) = jax.value_and_grad(loss("pallas"), (0, 1))(w, b)
        vr, (gwr, gbr) = jax.value_and_grad(loss("ref"), (0, 1))(w, b)
        np.testing.assert_allclose(float(vp), float(vr), rtol=1e-5)
        np.testing.assert_allclose(gwp, gwr, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gbp, gbr, atol=1e-4, rtol=1e-4)

    def test_mixed_precision_output_dtype_agrees(self):
        """bf16 x/w with an f32 master bias: both impls emit bf16."""
        key = jax.random.PRNGKey(21)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (1, 8, 8, 2), jnp.bfloat16)
        w = rand(k2, (3, 3, 2, 4), jnp.bfloat16)
        b = jnp.zeros((4,), jnp.float32)
        out_p = ops.conv2d(x, w, b, activation="relu", impl="pallas")
        out_r = ops.conv2d(x, w, b, activation="relu", impl="ref")
        assert out_p.dtype == out_r.dtype == jnp.bfloat16

    def test_conv_oc_tile_auto_uses_dag_cost_model(self):
        """oc_tile=None resolves through core.dag.choose_oc_tile."""
        from repro.core.dag import choose_oc_tile
        key = jax.random.PRNGKey(10)
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (2, 8, 8, 3))
        w = jax.random.normal(k2, (3, 3, 3, 16))
        tile = choose_oc_tile(2, 16)
        assert 16 % tile == 0
        auto = ops.conv2d(x, w, impl="pallas")
        explicit = ops.conv2d(x, w, impl="pallas", oc_tile=tile)
        np.testing.assert_allclose(auto, explicit, atol=1e-6)
