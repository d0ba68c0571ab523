"""Runtime-sanitizer suite (repro.sanitize): transfer-guard semantics,
the sanctioned escape hatch and its audit log, compile budgets, and the
engines running end-to-end under ``REPRO_SANITIZE=1``.

The transfer tests exercise the implicit HOST-TO-DEVICE class (numpy
leaves reaching a jit dispatch), which is the class the CPU backend can
enforce — device arrays are host-resident on CPU, so the d2h half of
the guard only arms on real accelerators (see the harness docstring).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sanitize import (CompileBudgetExceeded, clear_sync_log,
                            compile_budget, compile_counts,
                            install_compile_listener, sanctioned_scope,
                            sanctioned_sync, sanitize_enabled, sanitized,
                            sync_log)


@pytest.fixture
def sanitize_on(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    clear_sync_log()
    yield
    clear_sync_log()


# ----------------------------------------------------------------------
# gating
# ----------------------------------------------------------------------
class TestGating:
    @pytest.mark.parametrize("val,on", [
        ("1", True), ("on", True), ("yes", True),
        ("", False), ("0", False), ("off", False), ("OFF", False),
    ])
    def test_env_values(self, monkeypatch, val, on):
        monkeypatch.setenv("REPRO_SANITIZE", val)
        assert sanitize_enabled() is on

    def test_unset_is_off(self, monkeypatch):
        monkeypatch.delenv("REPRO_SANITIZE", raising=False)
        assert not sanitize_enabled()

    def test_sanitized_is_noop_when_off(self, monkeypatch):
        monkeypatch.setenv("REPRO_SANITIZE", "0")
        with sanitized("noop"):
            # implicit h2d: numpy leaves straight into a jitted add —
            # legal because the guard never armed
            out = jax.jit(lambda a, b: a + b)(np.ones(3), np.ones(3))
        assert float(out.sum()) == 6.0


# ----------------------------------------------------------------------
# transfer guards
# ----------------------------------------------------------------------
class TestTransferGuard:
    def test_implicit_h2d_raises_inside_sanitized(self, sanitize_on):
        host = np.ones((4,), np.float32)
        with pytest.raises(Exception, match="[Dd]isallow"):
            with sanitized("test"):
                jnp.stack([host, host])

    def test_jit_dispatch_of_numpy_raises(self, sanitize_on):
        host = np.ones((5,), np.float32)
        f = jax.jit(lambda a: a * 2)
        with pytest.raises(Exception, match="[Dd]isallow"):
            with sanitized("test"):
                f(host)

    def test_explicit_device_put_is_legal(self, sanitize_on):
        host = {"w": np.ones((6,), np.float32)}
        with sanitized("test"):
            dev = jax.device_put(host)
            out = jax.jit(lambda t: t["w"] + 1)(dev)
        assert out.shape == (6,)

    def test_sanctioned_scope_allows_and_logs(self, sanitize_on):
        host = np.ones((7,), np.float32)
        with sanitized("test"):
            with sanctioned_scope("deliberate-upload"):
                dev = jnp.stack([host, host])
        assert dev.shape == (2, 7)
        assert sync_log() == ["deliberate-upload"]

    def test_sanctioned_sync_pulls_and_logs(self, sanitize_on):
        x = {"a": jnp.arange(3.0), "b": jnp.ones((2, 2))}
        with sanitized("test"):
            out = sanctioned_sync(x, "round.losses")
        assert isinstance(out["a"], np.ndarray)
        assert isinstance(out["b"], np.ndarray)
        np.testing.assert_array_equal(out["a"], [0.0, 1.0, 2.0])
        assert sync_log() == ["round.losses"]

    def test_sanctioned_sync_works_with_gate_off(self, monkeypatch):
        monkeypatch.setenv("REPRO_SANITIZE", "0")
        out = sanctioned_sync(jnp.ones(3), "plain")
        assert isinstance(out, np.ndarray)

    def test_gate_off_keeps_the_audit_log_empty(self, monkeypatch):
        monkeypatch.setenv("REPRO_SANITIZE", "0")
        clear_sync_log()
        sanctioned_sync(jnp.ones(3), "round.losses")
        with sanctioned_scope("eval"):
            pass
        assert sync_log() == []


# ----------------------------------------------------------------------
# compile budgets
# ----------------------------------------------------------------------
class TestCompileBudget:
    def test_fresh_compile_busts_zero_budget(self):
        # unique shape so no earlier test can have warmed this dispatch
        x = jnp.ones((3, 131))
        f = jax.jit(lambda a: (a * 2).sum(axis=1))
        with pytest.raises(CompileBudgetExceeded, match="budget"):
            with compile_budget(0, label="cold path"):
                f(x)

    def test_warmed_path_meets_zero_budget(self):
        x = jnp.ones((3, 137))
        f = jax.jit(lambda a: (a * 3).sum(axis=1))
        f(x)                                   # warmup compile
        with compile_budget(0, label="steady state"):
            for _ in range(4):
                f(x)

    def test_shape_drift_is_caught(self):
        f = jax.jit(lambda a: a + 1)
        f(jnp.ones((2, 139)))
        with pytest.raises(CompileBudgetExceeded):
            with compile_budget(0, what="traces", label="drift"):
                f(jnp.ones((4, 139)))          # new shape -> retrace

    def test_nonzero_budget_allows_bounded_compiles(self):
        f = jax.jit(lambda a: a - 1)
        # one fresh compilation emits a handful of trace/compile events;
        # a generous upper bound documents "at most one compilation"
        with compile_budget(8, what="traces", label="one warmup"):
            f(jnp.ones((2, 149)))

    def test_counters_are_monotonic_and_listener_idempotent(self):
        install_compile_listener()
        install_compile_listener()             # second install: no-op
        before = compile_counts()
        jax.jit(lambda a: a * 5)(jnp.ones((2, 151)))
        after = compile_counts()
        assert after["traces"] > before["traces"]
        assert after["compiles"] >= before["compiles"]


# ----------------------------------------------------------------------
# engines under the sanitizer: the CI REPRO_SANITIZE=1 leg in miniature
# ----------------------------------------------------------------------
class TestEngineUnderSanitizer:
    def _trainer(self, strategy, m=2, eval_fn=False, **tc_kwargs):
        from repro.core.bpt_trainer import BPTTrainer
        from repro.core.types import TrainConfig
        from repro.data.pipeline import IDPADataset
        from repro.data.synthetic import image_dataset
        from repro.models.cnn import (CNNConfig, cnn_accuracy, cnn_loss,
                                      init_cnn)
        cfg = CNNConfig(name="san", image_size=8, conv_layers=1, filters=4,
                        fc_layers=1, fc_neurons=32)
        xs, ys = image_dataset(64 * m * 2, size=8, seed=0)
        params = init_cnn(jax.random.PRNGKey(0), cfg)
        ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=m,
                         batches=1)
        tc = TrainConfig(outer_strategy=strategy, outer_nodes=m,
                         optimizer="adamw", learning_rate=2e-3,
                         total_steps=100, warmup_steps=5, local_steps=2,
                         seed=0, **tc_kwargs)
        ef = None
        if eval_fn:
            xe, ye = image_dataset(32, size=8, seed=9)
            eb = {"images": jnp.asarray(xe), "labels": jnp.asarray(ye)}
            ef = jax.jit(lambda p: cnn_accuracy(p, eb, cfg))
        return BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}),
                          params, ds, tc, batch_size=16, eval_fn=ef)

    @pytest.mark.parametrize("strategy", ["sgwu", "agwu"])
    def test_round_bodies_run_clean_under_guard(self, sanitize_on,
                                                strategy):
        """Zero unsanctioned transfers in the engine round bodies: the
        whole train loop completes with the guard armed, and the only
        host pulls are the logged sanctioned ones."""
        rep = self._trainer(strategy, eval_fn=True).train(rounds=2)
        assert len(rep.losses) >= 2
        assert all(np.isfinite(loss) for loss in rep.losses)
        labels = set(sync_log())
        # the Eq. 8 measurement boundary must be among the sanctioned
        # syncs — it is a *sanctioned* host sync, not an eliminated one
        assert any("loss" in lbl for lbl in labels), labels

    def test_sequential_engine_under_guard(self, sanitize_on):
        rep = self._trainer("sgwu", fused_outer=False).train(rounds=2)
        assert len(rep.losses) >= 2

    def test_scan_engine_under_guard(self, sanitize_on):
        rep = self._trainer("sync").train(rounds=2)
        assert len(rep.losses) >= 2
