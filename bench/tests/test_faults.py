"""A run's ``correct`` against the cells' limits, on the CPU at a size a
test can hold: a sound program passes; the program with one fault
planted underneath the timed path fails; and the reference computed in
bfloat16 (the control) fails in the program's place.

The run is the harness's own (``harness.execute``), with the look for a
chip left out: the CPU's devices stand in, and the program runs its jnp
kernels.  The ``sgwu`` traffic keeps its nodes, local steps and
optimizer; only the sizes shrink.  Beside the fused SGWU round, the runs
cover the two engines a later cell can select by its traffic file alone:
AGWU pushes on the host heap, and the SGWU round sharded one node per
chip, whose merge is an exchange between chips.  All are held to the
limits of ``case7.sgwu``."""
import json
import os
import time

import jax
import numpy as np
import pytest

import harness
import outer_reference as outer_ref

TINY = {"name": "tiny", "family": "cnn", "image_size": 16, "in_channels": 3,
        "conv_layers": 2, "filters": 4, "filter_size": 3, "fc_layers": 3,
        "fc_neurons": 64, "num_classes": 10, "pool_every": 1,
        "pool_min_size": 8}
# path -> (changes to the sgwu traffic, chips): the engines whose faults
# the runs must catch
PATHS = {
    "sgwu": ({}, 1),
    "agwu": ({"strategy": "agwu", "engine": "heap", "checked_events": 6,
              "warm_events": 9, "trace_events": 20}, 1),
    "sgwu.nodes4": ({"engine": "device", "mesh": "nodes4",
                     "use_eval": False}, 4),
}
CELLS = list(PATHS)
LIMITS = "case7.sgwu"
SEED = 2**31 + 17


@pytest.fixture(autouse=True)
def jnp_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")


def tiny_cell(path: str) -> harness.Cell:
    changes, chips = PATHS[path]
    traffic = dict(harness.load_traffic("sgwu"), **changes, n_train=512,
                   n_eval=64, batch=8)
    return harness.Cell(f"tiny.{path}", chips, dict(TINY), traffic,
                        ["images_per_s", "merge_ms_p95", "setup_s"], {})


def run(path: str, **faults) -> dict:
    cell = tiny_cell(path)
    return harness.execute(cell, SEED, 0.2, False, time.perf_counter(),
                           jax.devices()[:cell.chips],
                           harness.load_limits(LIMITS), **faults)


def half_batch(loss_fn):
    def loss(params, batch):
        half = batch["labels"].shape[0] // 2
        return loss_fn(params, {k: v[:half] for k, v in batch.items()})
    return loss


def labels_altered(batch):
    labels = (np.asarray(batch["labels"]) + 1) % TINY["num_classes"]
    return dict(batch, labels=labels)


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["metrics"]["images_per_s"]["value"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_step_that_leaves_the_state_unchanged_fails(workload, monkeypatch):
    import repro.core.bpt_trainer as bpt
    monkeypatch.setattr(bpt, "apply_updates", lambda params, updates: params)
    res = run(workload)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("workload", CELLS)
def test_half_the_batch_left_out_fails(workload):
    assert not run(workload, loss_wrap=half_batch)["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_labels_altered_where_the_feed_hands_them_over_fails(workload):
    assert not run(workload, corrupt=labels_altered)["correct"]


def test_the_merge_without_the_exchange_between_chips_fails(monkeypatch):
    """The Eq. 7 all-reduce receives nothing from the other chips: each
    sums chip 0's weighted share alone (a plain identity would fail the
    shard_map's replication check before the run, not the run)."""
    import jax.numpy as jnp
    from repro.core import gwu
    psum = jax.lax.psum

    def chip_zero_only(x, axis_name):
        mine = jax.lax.axis_index(axis_name) == 0
        return psum(jnp.where(mine, x, jnp.zeros_like(x)), axis_name)
    gwu._sharded_merge_fn.cache_clear()
    monkeypatch.setattr(jax.lax, "psum", chip_zero_only)
    try:
        res = run("sgwu.nodes4")
    finally:
        gwu._sharded_merge_fn.cache_clear()
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_fails_in_the_programs_place(workload):
    cell = tiny_cell(workload)
    built = harness.build(cell, SEED)
    gen = built.trainer.run(10**9)
    _, nodes = harness.checked_events(cell, built, gen, outer_ref)
    gen.close()
    ref = harness.replay(cell, built, nodes, harness.reference(
        cell, built, outer_ref, outer_ref.REFERENCE))
    control = harness.replay(cell, built, nodes, harness.reference(
        cell, built, outer_ref, outer_ref.CONTROL))
    gaps = outer_ref.gaps(control, ref)
    limits = harness.load_limits(LIMITS)
    assert any(gaps[k] > limits[k] for k in harness.CHECKS), gaps


def test_limits_files_name_their_readings():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for workload in cells:
        with open(os.path.join(harness.BENCH, "limits",
                               f"{workload}.json")) as f:
            doc = json.load(f)
        assert set(doc["limits"]) == set(harness.CHECKS)
        assert doc["doc"]


def test_a_change_of_the_right_size_in_the_wrong_direction_fails():
    """Each leaf moved by as much as the reference moved it, in another
    direction: the norms agree, the direction does not."""
    rng = np.random.default_rng(0)
    ref_change = [rng.standard_normal(s).astype(np.float32)
                  for s in [(64, 32), (32,), (32, 10)]]
    wrong = [np.roll(x, 1) for x in ref_change]
    losses = [np.ones(4)]
    grads = np.ones((4, len(ref_change)))
    gaps = outer_ref.gaps(outer_ref.Readings(losses, wrong),
                          outer_ref.Readings(losses, ref_change, grads))
    assert gaps["change_gap"] < 1e-6
    assert gaps["change_dir"] > 0.5
