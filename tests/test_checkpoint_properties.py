"""Property tests for the crash-safe checkpoint layer (repro.checkpointing).

Runs under real ``hypothesis`` when installed, else under the vendored
fallback (``repro.testing.hypothesis_fallback``, installed by conftest) —
the properties draw from integer seed strategies and build pytrees
deterministically from the seed, which both generators support.

Covered contracts:

* save/restore round-trips arbitrary NESTED pytrees — dicts (DictKey),
  lists (SequenceKey ``#i``), registered dataclasses (GetAttrKey) — with
  mixed dtypes (float32 / bfloat16 / int32), bit-exactly;
* ``latest_step`` ignores strays: ``*.tmp`` files, manifests, other
  checkpoint kinds, unrelated names;
* corrupt / truncated / drifted payloads raise ``CheckpointError`` naming
  the offending file — never a bare numpy traceback;
* atomic-write discipline: a save never leaves ``*.tmp`` strays, a kill
  mid-write publishes nothing, and the manifest is published BEFORE the
  payload so a visible ``.npz`` always has its manifest.
"""
import dataclasses
import json
import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpointing import checkpoint
from repro.checkpointing.checkpoint import CheckpointError


@dataclasses.dataclass
class OptSlot:
    """Registered dataclass node: leaves reached via GetAttrKey paths."""
    mu: object
    nu: object
    count: object


jax.tree_util.register_dataclass(
    OptSlot, data_fields=["mu", "nu", "count"], meta_fields=[])

DTYPES = ("float32", "bfloat16", "int32")


def _leaf(rng, dtype):
    shape = tuple(int(s) for s in
                  rng.integers(1, 4, size=int(rng.integers(0, 3))))
    if dtype == "int32":
        return np.asarray(rng.integers(-1000, 1000, size=shape), np.int32)
    a = rng.standard_normal(shape).astype(np.float32) * 8
    if dtype == "bfloat16":
        return jnp.asarray(a, dtype=jnp.bfloat16)
    return a


def make_tree(seed: int):
    """Deterministic nested pytree: dict + list + dataclass structure,
    mixed dtypes, shapes drawn from the seed."""
    rng = np.random.default_rng(seed)
    dt = lambda: DTYPES[int(rng.integers(len(DTYPES)))]      # noqa: E731
    return {
        "params": {
            "dense": [_leaf(rng, dt()) for _ in
                      range(int(rng.integers(1, 4)))],
            "conv": {"w": _leaf(rng, "float32"),
                     "b": _leaf(rng, dt())},
        },
        "opt": OptSlot(mu=_leaf(rng, dt()), nu=_leaf(rng, "bfloat16"),
                       count=np.asarray(int(rng.integers(0, 99)), np.int32)),
    }


def _zeros_like(tree):
    return jax.tree_util.tree_map(lambda x: np.zeros_like(x), tree)


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        assert x.shape == y.shape
        np.testing.assert_array_equal(x, y)


# ----------------------------------------------------------------------
class TestRoundTripProperties:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_save_restore_round_trips_bit_exactly(self, seed):
        with tempfile.TemporaryDirectory() as path:
            tree = make_tree(seed)
            step = seed % 1000
            checkpoint.save(path, tree, step=step)
            restored, got = checkpoint.restore(path, _zeros_like(tree),
                                               step=step)
            assert got == step
            _assert_trees_equal(tree, restored)
            # atomicity: a completed save leaves no temp strays behind
            assert not [f for f in os.listdir(path) if f.endswith(".tmp")]

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_state_round_trip_preserves_scalars(self, seed):
        tree = make_tree(seed)
        rng = np.random.default_rng(seed)
        scalars = {"clock": float(rng.random() * 100),
                   "heap": [[float(rng.random()), int(j), 0, 0]
                            for j in range(int(rng.integers(1, 5)))],
                   "down": sorted(int(x) for x in
                                  rng.integers(0, 8, size=2)),
                   "nested": {"epoch": [1, 2, 3], "label": "run"}}
        with tempfile.TemporaryDirectory() as path:
            checkpoint.save_state(path, tree, seed % 1000, scalars)
            arrays, got_scalars, _ = checkpoint.restore_state(
                path, _zeros_like(tree))
        _assert_trees_equal(tree, arrays)
        # scalars survive the JSON round trip verbatim
        assert json.loads(json.dumps(scalars)) == got_scalars

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_latest_step_picks_max_and_ignores_strays(self, seed):
        rng = np.random.default_rng(seed)
        steps = sorted({int(s) for s in rng.integers(0, 5000, size=4)})
        tree = {"w": np.ones(2, np.float32)}
        with tempfile.TemporaryDirectory() as tmp:
            path = Path(tmp)
            for s in steps:
                checkpoint.save(str(path), tree, step=s)
            # strays that must all be invisible to latest_step(kind="ckpt")
            (path / "ckpt_99999999.npz.tmp").write_bytes(b"partial")
            (path / "notes.txt").write_text("hi")
            (path / "ckpt_abc.npz").write_bytes(b"junk")
            checkpoint.save(str(path), tree, step=7777, kind="state")
            assert checkpoint.latest_step(str(path)) == steps[-1]
            assert checkpoint.latest_step(str(path), kind="state") == 7777
            restored, got = checkpoint.restore(str(path),
                                               _zeros_like(tree))
            assert got == steps[-1]


# ----------------------------------------------------------------------
class TestCorruptionHandling:
    def _saved(self, tmp_path, step=3):
        tree = make_tree(0)
        checkpoint.save(str(tmp_path), tree, step=step)
        return tree, str(tmp_path), \
            tmp_path / checkpoint._payload_name("ckpt", step)

    def test_truncated_payload_raises_checkpoint_error(self, tmp_path):
        tree, path, payload = self._saved(tmp_path)
        raw = payload.read_bytes()
        payload.write_bytes(raw[: len(raw) // 2])
        with pytest.raises(CheckpointError, match="corrupt or was "
                           "truncated"):
            checkpoint.restore(path, _zeros_like(tree))

    def test_garbage_payload_raises_checkpoint_error(self, tmp_path):
        tree, path, payload = self._saved(tmp_path)
        payload.write_bytes(b"\x00" * 128)
        with pytest.raises(CheckpointError, match=str(payload)):
            checkpoint.restore(path, _zeros_like(tree))

    def test_manifest_drift_raises_checkpoint_error(self, tmp_path):
        tree, path, _ = self._saved(tmp_path)
        mpath = tmp_path / "ckpt_00000003.json"
        manifest = json.loads(mpath.read_text())
        key = next(iter(manifest["keys"]))
        manifest["keys"][key]["dtype"] = "float64"
        mpath.write_text(json.dumps(manifest))
        with pytest.raises(CheckpointError, match="drifted"):
            checkpoint.restore(path, _zeros_like(tree))

    def test_manifest_missing_key_raises_checkpoint_error(self, tmp_path):
        tree, path, _ = self._saved(tmp_path)
        mpath = tmp_path / "ckpt_00000003.json"
        manifest = json.loads(mpath.read_text())
        manifest["keys"].pop(next(iter(manifest["keys"])))
        mpath.write_text(json.dumps(manifest))
        with pytest.raises(CheckpointError, match="out of sync"):
            checkpoint.restore(path, _zeros_like(tree))

    def test_corrupt_manifest_raises_checkpoint_error(self, tmp_path):
        tree, path, _ = self._saved(tmp_path)
        (tmp_path / "ckpt_00000003.json").write_text("{not json")
        with pytest.raises(CheckpointError, match="manifest"):
            checkpoint.restore(path, _zeros_like(tree))

    def test_shape_mismatch_raises_checkpoint_error(self, tmp_path):
        path = str(tmp_path)
        checkpoint.save(path, {"w": np.ones((2, 3), np.float32)}, step=1)
        with pytest.raises(CheckpointError, match="shape"):
            checkpoint.restore(path, {"w": np.zeros((4, 4), np.float32)})

    def test_missing_template_keys_raise_key_error(self, tmp_path):
        path = str(tmp_path)
        checkpoint.save(path, {"w": np.ones(2, np.float32)}, step=1)
        with pytest.raises(KeyError, match="missing keys"):
            checkpoint.restore(path, {"w": np.zeros(2, np.float32),
                                      "extra": np.zeros(1, np.float32)})

    def test_empty_dir_raises_file_not_found(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="no checkpoints"):
            checkpoint.restore(str(tmp_path), {"w": np.zeros(1)})

    def test_bad_kind_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="kind"):
            checkpoint.save(str(tmp_path), {"w": np.zeros(1)}, kind="weird")


# ----------------------------------------------------------------------
class TestAtomicity:
    def test_kill_mid_payload_write_publishes_nothing(self, tmp_path):
        """A crash inside the payload write must leave the published name
        absent — only a ``.tmp`` stray, which latest_step ignores and the
        next save overwrites."""
        final = tmp_path / "ckpt_00000001.npz"

        def boom(f):
            f.write(b"half a payload")
            raise OSError("disk gone")

        with pytest.raises(OSError):
            checkpoint._atomic_write_bytes(str(final), boom)
        assert not final.exists()
        assert (tmp_path / "ckpt_00000001.npz.tmp").exists()
        assert checkpoint.latest_step(str(tmp_path)) is None
        # recovery: a clean save at the same step just works
        checkpoint.save(str(tmp_path), {"w": np.ones(1, np.float32)}, step=1)
        assert checkpoint.latest_step(str(tmp_path)) == 1

    def test_manifest_published_before_payload(self, tmp_path, monkeypatch):
        """Kill between manifest and payload: no ``.npz`` becomes visible,
        so latest_step never points at a manifest-only step."""
        def no_savez(*a, **k):
            raise OSError("killed between manifest and payload")

        monkeypatch.setattr(checkpoint.np, "savez", no_savez)
        with pytest.raises(OSError):
            checkpoint.save(str(tmp_path), {"w": np.ones(1, np.float32)},
                            step=5)
        assert (tmp_path / "ckpt_00000005.json").exists()
        assert checkpoint.latest_step(str(tmp_path)) is None

    def test_every_visible_payload_has_its_manifest(self, tmp_path):
        tree = make_tree(1)
        checkpoint.save(str(tmp_path), tree, step=9)
        manifest = checkpoint.load_manifest(str(tmp_path), 9)
        flat_keys = set(manifest["keys"])
        # the manifest records exactly the flattened key set with the
        # documented path encoding: '/'-joined, '#i' for list positions,
        # attribute names for dataclass fields
        assert any(k.startswith("params/dense/#") for k in flat_keys)
        assert "opt/mu" in flat_keys and "opt/count" in flat_keys

    def test_bfloat16_survives_npz_void_encoding(self, tmp_path):
        """np.savez demotes ml_dtypes extension arrays to raw void bytes;
        restore must reinterpret them via the manifest, not fail."""
        tree = {"w": jnp.arange(4, dtype=jnp.bfloat16)}
        checkpoint.save(str(tmp_path), tree, step=1)
        restored, _ = checkpoint.restore(str(tmp_path), _zeros_like(tree))
        assert np.asarray(restored["w"]).dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                      [0.0, 1.0, 2.0, 3.0])
