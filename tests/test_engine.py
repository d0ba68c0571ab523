"""Engine-layer suite: the resolve_engine matrix, the single-decision-point
guarantee, TrainConfig construction-time validation, and the streaming
RoundEvent API (early-stop, TrainHooks cadences, mid-run checkpointing).

Device-count-dependent expectations are keyed on the live device count —
the CI ``multidevice`` job re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every branch of
the matrix (device engines AND their fallbacks) executes on every PR.
"""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import repro.core.engine as engine_module
from repro.checkpointing import checkpoint
from repro.core.bpt_trainer import BPTTrainer, TrainHooks
from repro.core.engine import (ENGINES, HeapDeviceEngine, HeapEngine,
                               ScanEngine, SequentialEngine, ShardMapEngine,
                               VmapEngine, engine_config, resolve_engine)
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss, init_cnn

NDEV = len(jax.devices())


def need_devices(m):
    return pytest.mark.skipif(
        NDEV < m, reason=f"needs {m} devices (have {NDEV}); run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _cfg(**kw):
    kw.setdefault("outer_nodes", 2)
    return TrainConfig(**kw)


def _make_trainer(m=2, eval_fn=False, batches=1, **tc_kwargs):
    cfg = CNNConfig(name="eng", image_size=8, conv_layers=1, filters=4,
                    fc_layers=1, fc_neurons=32)
    xs, ys = image_dataset(64 * m * 2, size=8, seed=0)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=m,
                     batches=batches)
    tc_kwargs.setdefault("outer_strategy", "sgwu")
    tc = TrainConfig(outer_nodes=m, optimizer="adamw", learning_rate=2e-3,
                     total_steps=100, warmup_steps=5, local_steps=2,
                     seed=0, **tc_kwargs)
    ef = None
    if eval_fn:
        import jax.numpy as jnp
        xe, ye = image_dataset(64, size=8, seed=9)
        eb = {"images": jnp.asarray(xe), "labels": jnp.asarray(ye)}
        ef = jax.jit(lambda p: cnn_accuracy(p, eb, cfg))
    return BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}), params, ds,
                      tc, batch_size=16, eval_fn=ef)


# ----------------------------------------------------------------------
# resolve_engine: the full flag matrix
# ----------------------------------------------------------------------
def _expected(strategy, fused, device, uneven, m, ndev):
    """Expected (backend, requested) or the ValueError the config earns."""
    if strategy == "sgwu":
        if device:
            return ("device", "device") if ndev >= m else ("vmap", "device")
        if fused:
            return ("vmap", "vmap")
        if uneven:
            return ValueError
        return ("sequential", "sequential")
    if uneven:
        return ValueError
    if strategy == "agwu":
        if device:
            return ("heap-device", "heap-device") if ndev >= m \
                else ("heap", "heap-device")
        return ("heap", "heap")
    return ("scan", "scan")


MATRIX = [(s, f, d, u)
          for s in ("sgwu", "agwu", "sync")
          for f in (True, False)
          for d in (True, False)
          for u in (True, False)]


class TestResolveMatrix:
    @pytest.mark.parametrize("strategy,fused,device,uneven", MATRIX)
    @pytest.mark.parametrize("m", [2, 8])
    def test_every_combination(self, strategy, fused, device, uneven, m):
        cfg = _cfg(outer_strategy=strategy, fused_outer=fused,
                   device_outer=device, uneven_batches=uneven,
                   outer_nodes=m)
        want = _expected(strategy, fused, device, uneven, m, NDEV)
        if want is ValueError:
            with pytest.raises(ValueError, match="uneven"):
                resolve_engine(cfg)
            return
        backend, requested = want
        plan = resolve_engine(cfg)
        assert plan.backend == backend
        assert plan.requested == requested
        assert plan.engine_cls is ENGINES[backend]
        assert plan.strategy == strategy
        # the fallback is RECORDED exactly when the request was downgraded
        assert bool(plan.fallback) == (backend != requested)
        if plan.backend == "device":
            assert plan.mesh is not None \
                and plan.mesh.shape["nodes"] == m
        else:
            assert plan.mesh is None

    def test_forced_fallback_always(self):
        """m > device count: both device requests downgrade, with the
        reason recorded in the plan (runs identically on any host)."""
        m = 2 * NDEV
        plan = resolve_engine(_cfg(outer_strategy="sgwu", device_outer=True,
                                   outer_nodes=m))
        assert (plan.backend, plan.requested) == ("vmap", "device")
        assert str(m) in plan.fallback and "vmap" in plan.fallback
        plan = resolve_engine(_cfg(outer_strategy="agwu", device_outer=True,
                                   outer_nodes=m))
        assert (plan.backend, plan.requested) == ("heap", "heap-device")
        assert plan.fallback

    def test_explicit_device_injection(self):
        """resolve_engine decides against the devices it is HANDED."""
        one = jax.devices()[:1]
        plan = resolve_engine(_cfg(outer_strategy="agwu", device_outer=True),
                              devices=one)
        assert (plan.backend, plan.requested) == ("heap", "heap-device")
        plan = resolve_engine(_cfg(outer_strategy="sgwu", device_outer=True),
                              devices=one)
        assert (plan.backend, plan.requested) == ("vmap", "device")

    def test_single_node_device_resolves_anywhere(self):
        """m=1 fits any backend: the device engine runs even on 1 device."""
        plan = resolve_engine(_cfg(outer_strategy="sgwu", device_outer=True,
                                   outer_nodes=1))
        assert plan.backend == "device" and plan.engine_cls is ShardMapEngine

    @need_devices(2)
    def test_named_nodes_mesh(self):
        plan = resolve_engine(_cfg(outer_strategy="sgwu", device_outer=True,
                                   mesh_name="nodes2"))
        assert plan.backend == "device"
        assert plan.mesh.shape == {"nodes": 2}

    def test_mesh_without_nodes_axis(self):
        """A mesh_name with no `nodes` axis is a config BUG (raise), unless
        the mesh cannot even be built (capacity -> transparent fallback)."""
        cfg = _cfg(outer_strategy="sgwu", device_outer=True,
                   mesh_name="tiny")
        if NDEV >= 4:           # tiny = (2,2)(data,model): builds, no nodes
            with pytest.raises(ValueError, match="nodes"):
                resolve_engine(cfg)
        else:
            assert resolve_engine(cfg).backend == "vmap"

    @need_devices(4)
    def test_mesh_nodes_axis_size_mismatch(self):
        with pytest.raises(ValueError, match="nodes"):
            resolve_engine(_cfg(outer_strategy="sgwu", device_outer=True,
                                mesh_name="nodes4", outer_nodes=2))

    @pytest.mark.parametrize("name", sorted(ENGINES))
    def test_engine_config_roundtrip(self, name):
        """TrainConfig(**engine_config(name)) resolves to the named engine
        (modulo the documented device-count fallback)."""
        plan = resolve_engine(TrainConfig(**engine_config(
            name, outer_nodes=2)))
        assert plan.requested == name
        if NDEV >= 2 or name not in ("device", "heap-device"):
            assert plan.backend == name and plan.engine_cls is ENGINES[name]
        else:
            assert plan.fallback

    def test_engine_config_unknown_name(self):
        with pytest.raises(ValueError, match="unknown engine"):
            engine_config("warp")


class TestSingleDecisionPoint:
    def test_only_resolve_engine_reads_the_flags(self):
        """Linter-verifiable acceptance bar: no module under src/repro
        other than core/engine.py reads the fused_outer / device_outer /
        mesh_name substrate flags off a config object.  Asserted through
        reprolint's AST pass (rule RPL101), which the old raw-source
        regex grew into — attribute reads are matched on the tree (no
        hits inside strings/comments, multi-line receivers still match)
        and ``getattr(cfg, "fused_outer")`` is caught too."""
        from tools.reprolint import lint_paths
        root = Path(engine_module.__file__).parents[1]   # src/repro
        offenders = [
            f"{Path(f.path).relative_to(root)}:{f.line}"
            for f in lint_paths([str(root)], only=["RPL101"])
        ]
        assert not offenders, (
            "substrate flags must only be inspected by "
            f"engine.resolve_engine, found: {offenders}")


class TestTrainConfigValidation:
    def test_bad_strategy(self):
        with pytest.raises(ValueError, match="outer_strategy"):
            TrainConfig(outer_strategy="ring")

    def test_bad_partitioning(self):
        with pytest.raises(ValueError, match="partitioning"):
            TrainConfig(partitioning="static")

    def test_bad_optimizer(self):
        with pytest.raises(ValueError, match="optimizer"):
            TrainConfig(optimizer="lion")

    def test_bad_counts(self):
        with pytest.raises(ValueError, match="outer_nodes"):
            TrainConfig(outer_nodes=0)
        with pytest.raises(ValueError, match="local_steps"):
            TrainConfig(local_steps=0)

    def test_valid_choices_construct(self):
        for s in ("sgwu", "agwu", "sync"):
            assert TrainConfig(outer_strategy=s).outer_strategy == s


# ----------------------------------------------------------------------
# backend + fallback surfaced on TrainReport
# ----------------------------------------------------------------------
class TestReportSurface:
    @pytest.mark.parametrize("name", ["scan", "sequential", "vmap", "heap"])
    def test_backend_recorded(self, name):
        tr = _make_trainer(m=2, **engine_config(name))
        rep = tr.train(rounds=2)
        assert rep.backend == name
        assert rep.fallback is None
        assert "fallback" not in rep.summary()

    @pytest.mark.parametrize("name", ["device", "heap-device"])
    @need_devices(2)
    def test_device_backends_recorded(self, name):
        tr = _make_trainer(m=2, **engine_config(name))
        rep = tr.train(rounds=2)
        assert rep.backend == name and rep.fallback is None

    def test_fallback_surfaced(self):
        m = 2 * NDEV
        tr = _make_trainer(m=m, **engine_config("device"))
        rep = tr.train(rounds=1)
        assert rep.backend == "vmap"
        assert str(m) in rep.fallback
        assert rep.summary()["fallback"] == rep.fallback


# ----------------------------------------------------------------------
# streaming API: RoundEvent, TrainHooks, early-stop, checkpoint resume
# ----------------------------------------------------------------------
class TestStreaming:
    def test_sgwu_event_stream(self):
        tr = _make_trainer(m=2)
        events = list(tr.run(3))
        assert [ev.round for ev in events] == [0, 1, 2]
        for ev in events:
            assert ev.node_losses.shape == (2,)
            assert np.isfinite(ev.loss)
            assert ev.params is not None
        # virtual clock and comm volume are cumulative and monotone
        clocks = [ev.virtual_clock for ev in events]
        comms = [ev.comm_bytes for ev in events]
        assert clocks == sorted(clocks) and comms == sorted(comms)
        assert comms[0] > 0

    def test_agwu_event_stream_is_per_push(self):
        tr = _make_trainer(m=2, **engine_config("heap"))
        events = list(tr.run(2))
        assert len(events) == 4                      # m x rounds pushes
        assert sorted({ev.node for ev in events}) == [0, 1]
        assert [ev.round for ev in events] == [0, 1, 2, 3]

    def test_stream_matches_train_report(self):
        """run() and train() are the same computation on a fixed seed."""
        streamed = [ev.loss for ev in _make_trainer(m=2).run(3)]
        report = _make_trainer(m=2).train(rounds=3)
        np.testing.assert_allclose(streamed, report.losses, rtol=1e-6)

    def test_on_round_hook_and_eval_cadence(self):
        tr = _make_trainer(m=2, eval_fn=True)
        seen = []
        hooks = TrainHooks(on_round=seen.append, eval_every=2)
        events = list(tr.run(4, hooks))
        assert seen == events
        assert [ev.accuracy is not None for ev in events] == \
            [False, True, False, True]

    def test_default_eval_cadences(self):
        # SGWU: every round; sync scan: every 5 rounds; AGWU: every m pushes
        sg = _make_trainer(m=2, eval_fn=True).train(rounds=2)
        assert len(sg.accuracies) == 2
        sc = _make_trainer(m=1, eval_fn=True, **engine_config("scan"))\
            .train(rounds=5)
        assert len(sc.accuracies) == 1
        ag = _make_trainer(m=2, eval_fn=True, **engine_config("heap"))\
            .train(rounds=2)
        assert len(ag.accuracies) == 2               # 4 pushes / m=2

    @pytest.mark.parametrize("device", [
        False, pytest.param(True, marks=need_devices(2))])
    def test_early_stop_and_midrun_checkpoint_resume(self, tmp_path, device):
        """The acceptance bar, end to end under VmapEngine AND
        ShardMapEngine: stream rounds, checkpoint mid-run via TrainHooks,
        early-stop on a loss threshold, restore the checkpoint into a new
        trainer and keep training."""
        name = "device" if device else "vmap"
        tr = _make_trainer(m=2, **engine_config(name))
        ckpt = str(tmp_path / "ck")
        hooks = TrainHooks(checkpoint_every=2, checkpoint_dir=ckpt)
        max_rounds, threshold, events = 12, None, []
        for ev in tr.run(max_rounds, hooks):
            events.append(ev)
            if threshold is None:
                threshold = ev.loss          # first-round loss
            elif ev.loss < 0.995 * threshold:
                break                        # early-stop on the threshold
        assert tr.last_plan.engine_cls is \
            (ShardMapEngine if device else VmapEngine)
        assert 1 < len(events) < max_rounds          # genuinely stopped early
        # a mid-run checkpoint exists (every 2nd event, BEFORE the stop)
        step = checkpoint.latest_step(ckpt)
        assert step is not None and step <= len(events)
        restored, got = checkpoint.restore(ckpt, tr.params0)
        assert got == step
        # resume: a fresh trainer continues from the restored weights
        tr2 = _make_trainer(m=2, **engine_config(name))
        tr2.params0 = restored
        rep2 = tr2.train(rounds=2)
        assert np.isfinite(rep2.losses).all()
        # it continues from TRAINED weights, not from scratch
        assert rep2.losses[0] < 1.05 * threshold

    def test_generator_raises_bad_config_on_first_next(self):
        tr = _make_trainer(m=2, fused_outer=False, uneven_batches=True)
        with pytest.raises(ValueError, match="uneven"):
            next(iter(tr.run(1)))

    def test_break_stops_cleanly_and_rerun_works(self):
        tr = _make_trainer(m=2)
        for _ev in tr.run(5):
            break                            # caller walks away mid-stream
        rep = tr.train(rounds=2)             # the trainer is reusable
        assert len(rep.losses) == 2


class TestRoundWallClock:
    def test_stacked_round_excludes_data_prep(self, monkeypatch):
        """Regression: the Eq. 8 wall must start AFTER the host batch draw
        — a slow input pipeline must not inflate the virtual clock, the
        sync-wait, or the IDPA duration feedback."""
        tr = _make_trainer(m=2)
        orig = tr.dataset.stacked_round_batches
        delay = 0.2

        def slow_draw(*args, **kwargs):
            time.sleep(delay)
            return orig(*args, **kwargs)

        monkeypatch.setattr(tr.dataset, "stacked_round_batches", slow_draw)
        events = list(tr.run(2))
        # round 1 is compile-free: its clock increment is pure compute
        # wall and must exclude the injected data-prep delay entirely
        increment = events[1].virtual_clock - events[0].virtual_clock
        assert increment < delay

    def test_scan_round_excludes_data_prep(self, monkeypatch):
        tr = _make_trainer(m=1, **engine_config("scan"))
        orig = tr.dataset.node_batch
        delay = 0.1

        def slow_draw(*args, **kwargs):
            time.sleep(delay)
            return orig(*args, **kwargs)

        monkeypatch.setattr(tr.dataset, "node_batch", slow_draw)
        events = list(tr.run(2))
        increment = events[1].virtual_clock - events[0].virtual_clock
        # two local steps -> two slow draws per round, all excluded
        assert increment < 2 * delay


class TestEngineClasses:
    def test_registry_matches_backends(self):
        assert ENGINES == {"scan": ScanEngine, "sequential": SequentialEngine,
                           "vmap": VmapEngine, "device": ShardMapEngine,
                           "heap": HeapEngine, "heap-device": HeapDeviceEngine}
        for name, cls in ENGINES.items():
            assert cls.backend == name
