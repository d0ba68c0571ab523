"""Regression tests for the fused (vmapped) outer layer.

The fused SGWU round — node-stacked params/opt-states, one jitted
vmap-over-nodes × scan-over-local-steps dispatch, donated merge — must be
numerically equivalent to the legacy sequential per-node loop it replaced,
and the AGWU bookkeeping helpers must match their pre-refactor behaviour.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bpt_trainer import BPTTrainer
from repro.core.gwu import agwu_gamma, broadcast_tree
from repro.core.param_server import ParameterServer
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.models.cnn import CNNConfig, cnn_loss, init_cnn


def _run_sgwu(m: int, fused: bool, rounds: int = 3):
    """One SGWU training run on a fixed seed; batches=1 freezes the IDPA
    allocation so both paths see identical data regardless of wall time."""
    cfg = CNNConfig(name="equiv", image_size=8, conv_layers=1, filters=4,
                    fc_layers=1, fc_neurons=32)
    xs, ys = image_dataset(64 * m * 2, size=8, seed=0)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=m, batches=1)
    tc = TrainConfig(outer_strategy="sgwu", outer_nodes=m,
                     optimizer="adamw", learning_rate=2e-3,
                     total_steps=100, warmup_steps=5, local_steps=2,
                     seed=0, fused_outer=fused)
    tr = BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}), params, ds, tc,
                    batch_size=32)
    return tr.train(rounds=rounds)


class TestFusedSequentialEquivalence:
    @pytest.mark.parametrize("m", [1, 4])
    def test_same_losses_and_weights(self, m):
        fused = _run_sgwu(m, fused=True)
        seq = _run_sgwu(m, fused=False)
        np.testing.assert_allclose(fused.losses, seq.losses,
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(fused.final_params),
                        jax.tree_util.tree_leaves(seq.final_params),
                        strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_same_comm_accounting(self):
        fused = _run_sgwu(4, fused=True)
        seq = _run_sgwu(4, fused=False)
        assert fused.comm_bytes == seq.comm_bytes


class TestStackedBatches:
    def test_matches_sequential_draw_order(self):
        """(m, local_steps, B, ...) stacking consumes the RNG exactly like
        the per-node loop, so fixed seeds stay comparable."""
        xs, ys = image_dataset(240, size=8, seed=1)
        m, h, bsz = 3, 2, 16
        ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=m, batches=1)
        stacked = ds.stacked_round_batches(
            bsz, h, np.random.default_rng(7))
        rng = np.random.default_rng(7)
        for j in range(m):
            for s in range(h):
                want = ds.node_batch(j, bsz, rng)
                np.testing.assert_array_equal(stacked["images"][j, s],
                                              want["images"])
                np.testing.assert_array_equal(stacked["labels"][j, s],
                                              want["labels"])


def _tree(val):
    return {"a": jnp.full((3, 2), val, jnp.float32),
            "b": jnp.full((4,), 2 * val, jnp.float32)}


class TestStackedParameterServer:
    def test_stacked_push_matches_list_push(self):
        locals_ = [_tree(1.0), _tree(3.0), _tree(5.0)]
        qs = [0.2, 0.3, 0.5]
        ps_list = ParameterServer(_tree(0.0), num_workers=3)
        for j in range(3):
            ps_list.pull(j)
        ps_list.push_sgwu(list(zip(range(3), locals_, qs, strict=True)))

        ps_stacked = ParameterServer(_tree(0.0), num_workers=3)
        ps_stacked.pull_all_stacked()
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *locals_)
        ps_stacked.push_sgwu_stacked(stacked, qs)

        for a, b in zip(jax.tree_util.tree_leaves(ps_list.global_weights),
                        jax.tree_util.tree_leaves(ps_stacked.global_weights),
                        strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        assert ps_list.comm_bytes == ps_stacked.comm_bytes
        assert ps_list.version == ps_stacked.version

    def test_pull_all_returns_replicas(self):
        ps = ParameterServer(_tree(2.0), num_workers=4)
        stacked, version = ps.pull_all_stacked()
        assert version == 0
        for leaf, ref in zip(jax.tree_util.tree_leaves(stacked),
                             jax.tree_util.tree_leaves(ps.global_weights),
                             strict=True):
            assert leaf.shape == (4,) + ref.shape
            np.testing.assert_allclose(np.asarray(leaf),
                                       np.broadcast_to(np.asarray(ref),
                                                       leaf.shape))
        assert ps.comm_bytes == 4 * ps.weight_bytes

    def test_rebroadcast_cache_survives_round_trip(self):
        """pull → push → pull must hand out the *merged* weights."""
        ps = ParameterServer(_tree(0.0), num_workers=2)
        ps.pull_all_stacked()
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), _tree(1.0), _tree(3.0))
        ps.push_sgwu_stacked(stacked, [0.5, 0.5])
        again, version = ps.pull_all_stacked()
        assert version == 1
        np.testing.assert_allclose(np.asarray(again["a"][0]), 2.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(again["a"][1]), 2.0, rtol=1e-6)


def _agwu_gamma_seed_impl(base_version, latest_version, outstanding_versions):
    """The pre-refactor implementation, verbatim: Eq. (9) evaluated through
    ``jnp.exp`` (a device round-trip per push)."""
    denom_versions = list(outstanding_versions) + [base_version]
    i_minus_1 = max(latest_version, 1)
    num = float(jnp.exp(base_version / i_minus_1))
    den = float(sum(jnp.exp(v / i_minus_1) for v in denom_versions))
    return num / den


_VERSION_GRID = [(k, latest, out)
                 for k in (0, 1, 2, 5, 9, 13, 20)
                 for latest in (1, 2, 3, 8, 15, 21)
                 for out in ([], [0], [1, 4], [2, 5, 9], [0, 7, 13, 20])
                 if k <= latest]


class TestAgwuGammaRegression:
    def test_matches_seed_impl_f64(self):
        """Pure-python agwu_gamma == the old jnp implementation to 1e-12.

        The old path's *math* is compared under x64 — its default-config
        output was additionally rounded through float32 by the device
        round-trip, which is the very noise (and cost) the rewrite
        removes; the f32 agreement is checked separately below.
        """
        with jax.enable_x64():
            for k, latest, out in _VERSION_GRID:
                old = _agwu_gamma_seed_impl(k, latest, out)
                new = agwu_gamma(k, latest, out)
                assert abs(old - new) < 1e-12, (k, latest, out)

    def test_matches_seed_impl_f32_tolerance(self):
        for k, latest, out in _VERSION_GRID:
            old = _agwu_gamma_seed_impl(k, latest, out)
            new = agwu_gamma(k, latest, out)
            assert abs(old - new) < 1e-6, (k, latest, out)


class TestBroadcastTree:
    def test_shapes_and_values(self):
        t = _tree(3.0)
        s = broadcast_tree(t, 5)
        assert s["a"].shape == (5, 3, 2)
        np.testing.assert_allclose(np.asarray(s["b"]),
                                   np.broadcast_to(np.asarray(t["b"]), (5, 4)))
