"""The program's host spans (``repro.sanitize.span``) in a profiler trace.

Two SGWU rounds on ``VmapEngine`` (on the training set kept on the device,
and again on the host draw) and two AGWU pushes on ``HeapEngine`` run under
``jax.profiler.trace`` at a tiny CNN size; the trace file is read
back with ``ProfileData.from_file``.  Each event is one ``bpt.round`` span
holding every host step of the merge, every sanctioned sync is one
``bpt.sync.<label>`` span (as many as the sanitizer's audit log counts),
and attaching the profiler changes no number the run computes.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core.engine as engine
from repro.core.bpt_trainer import BPTTrainer
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss, init_cnn
from repro.sanitize import clear_sync_log, sync_log

M, H, B = 2, 2, 16                # nodes, local steps, per-node batch
EVENTS = 2
STEPS = ("pull", "draw", "place", "dispatch", "merge")


def _trainer(strategy):
    cfg = CNNConfig(name="spans", image_size=8, conv_layers=1, filters=4,
                    fc_layers=1, fc_neurons=32)
    xs, ys = image_dataset(64 * M * 2, size=8, seed=0)
    xe, ye = image_dataset(32, size=8, seed=9)
    held_out = {"images": jnp.asarray(xe), "labels": jnp.asarray(ye)}
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=M, batches=3,
                     frequencies=[1.0, 0.5])
    tc = TrainConfig(outer_strategy=strategy, outer_nodes=M,
                     optimizer="adamw", learning_rate=2e-3, total_steps=100,
                     warmup_steps=5, local_steps=H, seed=0)
    return BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}),
                      init_cnn(jax.random.PRNGKey(0), cfg), ds, tc,
                      batch_size=B, speed_factors=[1.0, 2.0],
                      eval_fn=jax.jit(lambda p: cnn_accuracy(p, held_out,
                                                             cfg)))


def _drive(strategy, resident=True):
    """EVENTS events of a fresh trainer: (losses, IDPA allocation batch
    after each event).  ``resident=False`` keeps an SGWU set on the host
    (the CPU reports no memory limit, so ``set_fits`` would take it)."""
    t = _trainer(strategy)
    losses, allocs = [], []
    with pytest.MonkeyPatch.context() as mp:
        if not resident:
            mp.setattr(engine, "set_fits", lambda *a: False)
        gen = t.run(10)
        for _ in range(EVENTS):
            ev = next(gen)
            losses.append(np.asarray(ev.node_losses))
            allocs.append(t.dataset.part.current_batch)
        gen.close()
    return losses, allocs, t.last_plan.backend


def _spans(path):
    """The ``bpt.*`` spans of the trace under ``path``: (name, start, end,
    stats) in start order."""
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bpt."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module", params=[("sgwu", True), ("sgwu", False),
                                        ("agwu", False)],
                ids=["sgwu", "sgwu-host", "agwu"])
def traced(request, tmp_path_factory):
    """One traced run of each strategy (SGWU on a resident set and on the
    host draw), with the sanitizer's gate on so that its audit log counts
    the same syncs as the trace."""
    strategy, resident = request.param
    path = str(tmp_path_factory.mktemp(f"trace_{strategy}"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_SANITIZE", "1")
        clear_sync_log()
        with jax.profiler.trace(path):
            losses, allocs, backend = _drive(strategy, resident)
        log = sync_log()
        clear_sync_log()
    return {"strategy": strategy, "resident": resident,
            "spans": _spans(path), "log": log, "losses": losses,
            "allocs": allocs, "backend": backend}


def _inside(spans, outer):
    _, s, e, _ = outer
    return [x for x in spans if x is not outer and s <= x[1] and x[2] <= e]


def test_one_round_span_per_event_holds_every_host_step(traced):
    spans = traced["spans"]
    rounds = [s for s in spans if s[0] == "bpt.round"]
    assert [r[3]["round"] for r in rounds] == list(range(EVENTS))
    assert {r[3]["backend"] for r in rounds} == {traced["backend"]}
    assert traced["backend"] == {"sgwu": "vmap",
                                 "agwu": "heap"}[traced["strategy"]]
    sync = {"sgwu": ["round.losses", "eval-nodes"],
            "agwu": ["local-round.loss"]}[traced["strategy"]]
    for r in rounds:
        names = [x[0] for x in _inside(spans, r)]
        for step in STEPS:
            assert f"bpt.{step}" in names, (step, names)
        assert [n[len("bpt.sync."):] for n in names
                if n.startswith("bpt.sync.") and n != "bpt.sync.eval"] \
            == sync
        draws = [x for x in _inside(spans, r) if x[0] == "bpt.draw"]
        places = [x for x in _inside(spans, r) if x[0] == "bpt.place"]
        merge = [x for x in _inside(spans, r) if x[0] == "bpt.merge"]
        assert len(merge) == 1 and merge[0][3]["version"] == r[3]["round"]
        if traced["strategy"] == "sgwu" and traced["resident"]:
            assert len(draws) == len(places) == 1
            # the round's batch is gathered on the device and placing it
            # moves nothing
            assert draws[0][3] == {"nodes": M, "steps": H, "batch": B,
                                   "resident": 1}
            assert places[0][3]["bytes"] == 0
        elif traced["strategy"] == "sgwu":
            assert len(draws) == len(places) == 1
            assert draws[0][3] == {"nodes": M, "steps": H, "batch": B,
                                   "resident": 0}
            # images (B, 8, 8, 3) float32 and int labels, for m x h draws
            assert places[0][3]["bytes"] == M * H * B * (8 * 8 * 3 * 4 + 4)
        else:
            assert len(draws) == len(places) == H
            assert draws[0][3] == {"nodes": 1, "steps": 1, "batch": B,
                                   "resident": 0}
            # one step's batch and its step scalar
            assert places[0][3]["bytes"] == B * (8 * 8 * 3 * 4 + 4) + 4


def test_the_stacked_engine_places_the_set_once_before_the_first_round(
        traced):
    """VmapEngine uploads the whole training set once, outside every
    round, where the set fits; the host draw and the AGWU heap engine
    upload nothing before the first round."""
    spans = traced["spans"]
    first = min(s[1] for s in spans if s[0] == "bpt.round")
    before = [s for s in spans if s[0] == "bpt.place" and s[1] < first]
    if traced["resident"]:
        n = 64 * M * 2           # images (n, 8, 8, 3) f32, int32 labels
        assert [s[3]["bytes"] for s in before] == [n * (8 * 8 * 3 * 4 + 4)]
    else:
        assert before == []


def test_idpa_span_marks_each_event_that_moves_the_allocation(traced):
    spans = traced["spans"]
    rounds = [s for s in spans if s[0] == "bpt.round"]
    before = [1] + traced["allocs"][:-1]
    moved = [a != b for a, b in zip(traced["allocs"], before)]
    assert any(moved)
    for r, m in zip(rounds, moved):
        idpa = [x for x in _inside(spans, r) if x[0] == "bpt.idpa"]
        assert len(idpa) == int(m)


def test_sync_spans_and_the_audit_log_count_the_same_pulls(traced):
    syncs = [s[0][len("bpt.sync."):] for s in traced["spans"]
             if s[0].startswith("bpt.sync.")]
    assert len(syncs) == len(traced["log"]) > 0
    assert sorted(syncs) == sorted(traced["log"])


def test_attaching_the_profiler_changes_no_loss(traced):
    losses, _, _ = _drive(traced["strategy"], traced["resident"])
    for a, b in zip(losses, traced["losses"]):
        np.testing.assert_array_equal(a, b)

