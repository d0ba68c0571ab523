"""The training set kept on the device for the stacked SGWU round.

Where the set fits the device (``engine.set_fits``), ``VmapEngine`` places
it once (``IDPADataset.to_device``) and ``IDPADataset.stacked_round_batches``
gathers each round's batch there.  The gather draws the same indices with the same RNG, so its
batch equals the host draw bit for bit, and the round runs transfer-clean
under the sanitizer with the host draw's losses.
"""
import jax
import numpy as np
import pytest

import repro.core.engine as engine
from repro.core.bpt_trainer import BPTTrainer
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.models.cnn import CNNConfig, cnn_loss, init_cnn

M, H, B = 3, 2, 16


def _dataset():
    xs, ys = image_dataset(240, size=8, seed=1)
    return IDPADataset({"images": xs, "labels": ys}, num_nodes=M,
                       batches=3, frequencies=[1.0, 0.5, 0.25])


@pytest.mark.parametrize("uneven", [False, True])
def test_resident_draw_equals_the_host_draw(uneven):
    ds = _dataset()
    host_rng, dev_rng = np.random.default_rng(7), np.random.default_rng(7)
    want = ds.stacked_round_batches(B, H, host_rng, uneven=uneven)
    rows = ds.to_device()
    got = ds.stacked_round_batches(B, H, dev_rng, uneven=uneven,
                                   resident=rows)
    assert sorted(got) == sorted(want)
    assert ("mask" in got) == uneven
    for k in want:
        assert isinstance(got[k], jax.Array)
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    assert dev_rng.bit_generator.state == host_rng.bit_generator.state
    # the host arrays stay as they were; the device copy holds one
    # sample a row
    for k, v in ds.arrays.items():
        assert isinstance(v, np.ndarray)
        np.testing.assert_array_equal(np.asarray(rows[k]),
                                      v.reshape(len(v), -1) if v.ndim > 2
                                      else v)


def test_the_resident_draw_refuses_an_index_past_the_set():
    ds = _dataset()
    views = ds.node_views()
    views[0] = views[0] + ds.n       # past the set, as the host draw sees it
    ds.node_views = lambda: views
    with pytest.raises(IndexError):
        ds.stacked_round_batches(B, H, np.random.default_rng(0))
    with pytest.raises(IndexError):
        ds.stacked_round_batches(B, H, np.random.default_rng(0),
                                 resident=ds.to_device())


def _trainer():
    cfg = CNNConfig(name="resident", image_size=8, conv_layers=1,
                    filters=4, fc_layers=1, fc_neurons=32)
    tc = TrainConfig(outer_strategy="sgwu", outer_nodes=M,
                     optimizer="adamw", learning_rate=2e-3, total_steps=100,
                     warmup_steps=5, local_steps=H, seed=0,
                     uneven_batches=True)
    return BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}),
                      init_cnn(jax.random.PRNGKey(0), cfg), _dataset(), tc,
                      batch_size=B)


def _losses(trainer, rounds=3):
    return [np.asarray(ev.node_losses) for ev in trainer.run(rounds)]


def _record_draws(monkeypatch, trainer):
    """The batches of the trainer's stacked draws, as they are made."""
    draws, orig = [], trainer.dataset.stacked_round_batches

    def draw(*a, **kw):
        draws.append(orig(*a, **kw))
        return draws[-1]

    monkeypatch.setattr(trainer.dataset, "stacked_round_batches", draw)
    return draws


def test_vmap_rounds_on_the_resident_set_are_transfer_clean(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    resident = _trainer()
    draws = _record_draws(monkeypatch, resident)
    got = _losses(resident)
    assert resident.last_plan.backend == "vmap"
    assert all(isinstance(b["images"], jax.Array) for b in draws)
    monkeypatch.setattr(engine, "set_fits", lambda *a: False)
    host = _trainer()
    draws = _record_draws(monkeypatch, host)
    want = _losses(host)
    assert all(isinstance(b["images"], np.ndarray) for b in draws)
    for a, b in zip(got, want, strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("share,temp,resident", [
    (0.5, 0.0, False), (8.0, 0.0, True), (6.0, 1.0, False)])
def test_the_device_memory_limit_decides_residency(monkeypatch, share, temp,
                                                   resident):
    """A device that reports a ``bytes_limit`` takes the set only where
    the set and its gather's temporaries (here a multiple ``temp`` of the
    set) are at most a quarter of that limit."""
    trainer = _trainer()
    nbytes = trainer.dataset.nbytes
    monkeypatch.setattr(type(jax.local_devices()[0]), "memory_stats",
                        lambda self: {"bytes_limit": int(share * nbytes)})
    if temp:
        monkeypatch.setattr(trainer.dataset, "gather_temp_bytes",
                            lambda shape, device: int(temp * nbytes))
    draws = _record_draws(monkeypatch, trainer)
    _losses(trainer, rounds=1)
    assert isinstance(draws[0]["images"], jax.Array) == resident


def test_the_gather_temporaries_are_read_from_its_compiled_program():
    """The compiled gather's memory analysis, here on the CPU, where a
    gather of rows needs none."""
    ds = _dataset()
    assert ds.gather_temp_bytes((M, H, B), jax.local_devices()[0]) == 0
