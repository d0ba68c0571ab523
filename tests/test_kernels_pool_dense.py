"""Pool + dense Pallas kernels: allclose sweeps vs the ref.py oracles,
the grad-check matrix (window/block x activation x jit+vmap+grad), the
Alg. 4.2 block auto-selection, and the explicit-fallback contract."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dag import choose_fc_block
from repro.kernels import ops, ref
from repro.kernels.dense import dense_pallas
from repro.kernels.pool2d import max_pool2d_pallas


def rand(key, shape, dtype=jnp.float32):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.fixture(autouse=True)
def _clean_fallback_log():
    ops.clear_fallback_log()
    yield
    ops.clear_fallback_log()


# ----------------------------------------------------------------------
# max_pool2d
# ----------------------------------------------------------------------
class TestMaxPool2d:
    SHAPES = [
        (1, 8, 8, 1, 2),
        (2, 16, 16, 3, 2),
        (2, 12, 12, 4, 4),
        (1, 9, 7, 2, 2),           # odd spatial: remainder dropped
        (2, 8, 8, 3, 8),           # window == whole map
    ]

    @pytest.mark.parametrize("B,H,W,C,window", SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_matches_ref(self, B, H, W, C, window, dtype):
        key = jax.random.PRNGKey(hash((B, H, W, C, window)) % 2**31)
        x = rand(key, (B, H, W, C), dtype)
        got = max_pool2d_pallas(x, window=window, stride=window)
        want = ref.max_pool2d_ref(x, window=window, stride=window)
        assert got.dtype == x.dtype
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=0)

    @pytest.mark.parametrize("B,H,W,C,window", SHAPES)
    def test_grads_match_ref(self, B, H, W, C, window):
        key = jax.random.PRNGKey(hash(("g", B, H, W, C, window)) % 2**31)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (B, H, W, C))
        cot = rand(k2, (B, H // window, W // window, C))
        got = jax.grad(lambda x_: jnp.sum(
            max_pool2d_pallas(x_, window=window, stride=window) * cot))(x)
        want = jax.grad(lambda x_: jnp.sum(
            ref.max_pool2d_ref(x_, window=window, stride=window) * cot))(x)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_tie_routing_splits_evenly(self):
        """Relu feature maps tie constantly (exact zeros); the Eq. 18
        routing must split tied maxima evenly like jax.grad of the ref,
        or the pallas ≡ ref trajectory equivalence breaks."""
        key = jax.random.PRNGKey(3)
        k1, k2 = jax.random.split(key)
        # quantize hard so nearly every window has tied maxima
        x = jnp.round(jax.nn.relu(rand(k1, (2, 8, 8, 3))) * 2) / 2
        cot = rand(k2, (2, 4, 4, 3))
        got = jax.grad(lambda x_: jnp.sum(max_pool2d_pallas(x_) * cot))(x)
        want = jax.grad(lambda x_: jnp.sum(ref.max_pool2d_ref(x_) * cot))(x)
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_grad_under_jit_and_vmap(self):
        """The fused trainer wraps pooling in jit(vmap(grad(...)))."""
        key = jax.random.PRNGKey(5)
        x = rand(key, (3, 2, 8, 8, 2))                   # (m, B, H, W, C)
        got = jax.jit(jax.vmap(jax.grad(
            lambda x_: jnp.sum(max_pool2d_pallas(x_) ** 2))))(x)
        want = jax.vmap(jax.grad(
            lambda x_: jnp.sum(ref.max_pool2d_ref(x_) ** 2)))(x)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_overlapping_window_raises(self):
        x = rand(jax.random.PRNGKey(0), (1, 8, 8, 1))
        with pytest.raises(ValueError, match="non-overlapping"):
            max_pool2d_pallas(x, window=3, stride=2)

    def test_window_larger_than_input_raises(self):
        x = rand(jax.random.PRNGKey(0), (1, 4, 4, 1))
        with pytest.raises(ValueError, match="smaller than"):
            max_pool2d_pallas(x, window=8, stride=8)

    def test_dispatch_impls_agree(self):
        x = rand(jax.random.PRNGKey(7), (2, 10, 10, 3))
        got = ops.max_pool2d(x, impl="pallas")
        want = ops.max_pool2d(x, impl="ref")
        np.testing.assert_allclose(got, want, atol=0)
        assert ops.fallback_events() == {}


# ----------------------------------------------------------------------
# dense
# ----------------------------------------------------------------------
def _dense_grid():
    # seed, block, activation, bias, shape (B, Din, Dout)
    return [
        (0, 0, "none", True, (4, 12, 8)),
        (1, 0, "relu", True, (4, 12, 8)),
        (2, 4, "none", True, (4, 12, 8)),
        (3, 4, "relu", True, (4, 12, 8)),
        (4, 8, "relu", True, (2, 16, 8)),    # block == Dout
        (5, 2, "none", False, (1, 6, 10)),   # no bias, odd dims
        (6, 5, "relu", False, (3, 7, 10)),   # Din not divisible by block
        (7, 128, "relu", True, (5, 40, 200)),    # lane block: Dout ragged
        (8, 128, "none", True, (4, 300, 130)),   # dx reuses it: Din ragged
        # widths past MAX_TILE that are no lane multiples: whole-K blocks,
        # ragged row/column edges in all three passes
        (9, 256, "relu", True, (8, 600, 520)),
        (10, 128, "none", False, (3, 700, 530)),
        # K too wide for one whole-K block: the masked ragged K tail, in
        # the forward and dx (K = 1400 features) ...
        (11, 512, "relu", True, (256, 1400, 1400)),
        # ... and in dw (K = 1400 samples, the rows of x^T)
        (12, 512, "none", False, (1400, 256, 768)),
    ]


class TestDensePallas:
    @pytest.mark.parametrize("seed,block,activation,bias,shape",
                             _dense_grid())
    def test_forward_matches_ref(self, seed, block, activation, bias, shape):
        B, Din, Dout = shape
        key = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        x = rand(k1, (B, Din))
        w = rand(k2, (Din, Dout))
        b = rand(k3, (Dout,)) if bias else None
        got = dense_pallas(x, w, b, activation=activation, block=block)
        want = ref.dense_ref(x, w, b, activation=activation)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("seed,block,activation,bias,shape",
                             _dense_grid())
    def test_grads_match_ref(self, seed, block, activation, bias, shape):
        """The §4.1.2 G_FC gradient tasks: dx/dw/db vs the jnp oracle."""
        B, Din, Dout = shape
        key = jax.random.PRNGKey(100 + seed)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        x = rand(k1, (B, Din))
        w = rand(k2, (Din, Dout))
        b = rand(k3, (Dout,)) if bias else jnp.zeros((Dout,))
        cot = rand(k4, (B, Dout))              # non-uniform cotangent

        def loss_pallas(x_, w_, b_):
            return jnp.sum(dense_pallas(x_, w_, b_, activation=activation,
                                        block=block) * cot)

        def loss_ref(x_, w_, b_):
            return jnp.sum(ref.dense_ref(x_, w_, b_,
                                         activation=activation) * cot)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, w, b)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        for g, r, name in zip(got, want, ("dx", "dw", "db"), strict=True):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"{name} mismatch")

    def test_grad_under_jit_and_vmap(self):
        """The fused trainer wraps the FC stack in jit(vmap(grad(...)))."""
        key = jax.random.PRNGKey(9)
        k1, k2, k3 = jax.random.split(key, 3)
        x = rand(k1, (3, 4, 12))                          # (m, B, Din)
        w = rand(k2, (12, 8))
        b = rand(k3, (8,))

        def loss(x_):
            return jnp.sum(dense_pallas(x_, w, b, activation="relu",
                                        block=4))

        got = jax.jit(jax.vmap(jax.grad(loss)))(x)
        want = jax.vmap(jax.grad(lambda x_: jnp.sum(
            ref.dense_ref(x_, w, b, activation="relu"))))(x)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

    def test_mixed_precision_dtypes(self):
        """bf16 x/w with an f32 master bias: bf16 out, f32 db."""
        key = jax.random.PRNGKey(11)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (2, 8), jnp.bfloat16)
        w = rand(k2, (8, 4), jnp.bfloat16)
        b = jnp.zeros((4,), jnp.float32)
        out = dense_pallas(x, w, b)
        assert out.dtype == jnp.bfloat16
        db = jax.grad(lambda b_: jnp.sum(
            dense_pallas(x, w, b_).astype(jnp.float32)))(b)
        assert db.dtype == jnp.float32

    def test_non_divisor_block_raises(self):
        x = rand(jax.random.PRNGKey(0), (1, 8))
        w = rand(jax.random.PRNGKey(1), (8, 8))
        with pytest.raises(ValueError, match="block"):
            dense_pallas(x, w, block=3)

    def test_nd_input_rejected_at_kernel_level(self):
        x = rand(jax.random.PRNGKey(0), (2, 3, 8))
        w = rand(jax.random.PRNGKey(1), (8, 4))
        with pytest.raises(ValueError, match="2-D"):
            dense_pallas(x, w)

    def test_ops_dense_flattens_leading_dims(self):
        """ops.dense takes (B, S, D) like the LM matmul sites."""
        key = jax.random.PRNGKey(13)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (2, 5, 12))
        w = rand(k2, (12, 8))
        got = ops.dense(x, w, impl="pallas")
        want = ops.dense(x, w, impl="ref")
        assert got.shape == (2, 5, 8)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_block_auto_uses_dag_cost_model(self):
        """block=None resolves through core.dag.choose_fc_block."""
        key = jax.random.PRNGKey(15)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (2, 8))
        w = rand(k2, (8, 32))
        block = choose_fc_block(32)
        assert 32 % block == 0
        auto = ops.dense(x, w, impl="pallas")
        explicit = ops.dense(x, w, impl="pallas", block=block)
        np.testing.assert_allclose(auto, explicit, atol=1e-6)

    def test_dispatch_grads_agree(self):
        """Both dispatch impls agree on value AND gradient (fused epilogue)."""
        key = jax.random.PRNGKey(17)
        k1, k2, k3 = jax.random.split(key, 3)
        x = rand(k1, (4, 12))
        w = rand(k2, (12, 8))
        b = rand(k3, (8,))

        def loss(impl):
            def f(w_, b_):
                return jnp.sum(
                    ops.dense(x, w_, b_, activation="relu", impl=impl) ** 2)
            return f

        vp, (gwp, gbp) = jax.value_and_grad(loss("pallas"), (0, 1))(w, b)
        vr, (gwr, gbr) = jax.value_and_grad(loss("ref"), (0, 1))(w, b)
        np.testing.assert_allclose(float(vp), float(vr), rtol=1e-5)
        np.testing.assert_allclose(gwp, gwr, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gbp, gbr, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------
# the explicit-fallback contract
# ----------------------------------------------------------------------
class TestFallbackContract:
    def _conv_args(self):
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        return rand(k1, (1, 8, 8, 2)), rand(k2, (3, 3, 2, 4))

    def test_explicit_pallas_strided_conv_raises(self):
        x, w = self._conv_args()
        with pytest.raises(NotImplementedError, match="stride"):
            ops.conv2d(x, w, stride=2, impl="pallas")

    def test_env_pallas_strided_conv_warns_once_and_records(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
        x, w = self._conv_args()
        with pytest.warns(ops.KernelFallbackWarning, match="stride"):
            got = ops.conv2d(x, w, stride=2)
        np.testing.assert_allclose(
            got, ops.conv2d(x, w, stride=2, impl="ref"), atol=1e-6)
        events = ops.fallback_events()
        assert len(events) == 1 and next(iter(events))[0] == "conv2d"
        # second identical call: recorded, but NOT warned again
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ops.conv2d(x, w, stride=2)
        assert next(iter(ops.fallback_events().values())) == 2

    def test_explicit_pallas_overlapping_pool_raises(self):
        x = rand(jax.random.PRNGKey(1), (1, 8, 8, 2))
        with pytest.raises(NotImplementedError, match="window"):
            ops.max_pool2d(x, window=3, stride=1, impl="pallas")

    def test_env_pallas_overlapping_pool_warns_and_records(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
        x = rand(jax.random.PRNGKey(1), (1, 8, 8, 2))
        # the jnp ref is non-overlapping-only too: the fallback is
        # recorded+warned first, then the ref raises loudly — never a
        # silently wrong pooling result
        with pytest.warns(ops.KernelFallbackWarning, match="window"):
            with pytest.raises(ValueError):
                ops.max_pool2d(x, window=3, stride=1)
        assert any(op == "max_pool2d" for op, _ in ops.fallback_events())

    def test_explicit_pallas_wide_dense_is_tiled(self):
        """A matmul far wider than one VMEM cell is served by the kernel
        itself — rows and the contraction tile, with ragged edges — never
        by the ref: no raise, no recorded fallback, the ref's answer."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(29), 3)
        x = rand(k1, (300, 600))          # rows > 256, K > 512: both tile
        w = rand(k2, (600, 200))          # Dout: a ragged 128 block
        b = rand(k3, (200,))
        got = ops.dense(x, w, b, activation="relu", impl="pallas")
        want = ops.dense(x, w, b, activation="relu", impl="ref")
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        assert ops.fallback_events() == {}

    def test_env_pallas_wide_dense_grads_match_ref(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(31), 3)
        x = rand(k1, (264, 520))
        w = rand(k2, (520, 130))
        cot = rand(k3, (264, 130))

        def loss(impl):
            return lambda x_, w_: jnp.sum(ops.dense(x_, w_, impl=impl) * cot)

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = jax.grad(loss(""), argnums=(0, 1))(x, w)
        want = jax.grad(loss("ref"), argnums=(0, 1))(x, w)
        for g, r in zip(got, want, strict=True):
            np.testing.assert_allclose(g, r, atol=1e-3, rtol=1e-4)
        assert ops.fallback_events() == {}

    def test_dense_mixed_precision_matches_ref_dtype_path(self):
        """bf16 activations with f32 master weights: the pallas dispatch
        casts w to x.dtype like the ref, keeping parity (and halving the
        weight-panel traffic on real hardware)."""
        key = jax.random.PRNGKey(23)
        k1, k2 = jax.random.split(key)
        x = rand(k1, (4, 16), jnp.bfloat16)
        w = rand(k2, (16, 8), jnp.float32)
        got = ops.dense(x, w, impl="pallas")
        want = ops.dense(x, w, impl="ref")
        assert got.dtype == want.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   atol=0.1, rtol=0.05)

    def test_pallas_paths_log_nothing(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
        x, w = self._conv_args()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ops.conv2d(x, w)                       # stride-1: pallas serves
            ops.max_pool2d(x)                      # non-overlapping: serves
            ops.dense(x.reshape(1, -1), rand(jax.random.PRNGKey(3),
                                             (128, 8)))
        assert ops.fallback_events() == {}
