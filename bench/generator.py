"""Training traffic: the images a cell trains on, made from the seed.

A traffic mix is a JSON file under ``bench/traffic/`` (one per mix); the
harness and this generator read it.  The keys:

- ``strategy``: ``sgwu`` (one merge per round of all nodes) or ``agwu``
  (one merge per node push);
- ``engine``, ``mesh``: the outer-layer engine the program runs
  (``engine_config`` name) and its named mesh, or ``""``;
- ``nodes``, ``batch``, ``local_steps``: m, the per-node batch B and the
  local steps h between merges;
- ``optimizer``, ``learning_rate``, ``warmup_steps``, ``total_steps``,
  ``grad_clip``: the optimizer as the program is asked to run it;
- ``idpa_mode``, ``allocation_batches``, ``speed_factors``: IDPA and the
  emulated node speeds;
- ``n_train``: training images drawn from the seed; ``n_eval`` and
  ``eval_seed``: the held-out set behind the Eq. 7/10 accuracy Q.  The
  held-out set is the same for every seed: the eval program bakes it in
  as a constant, so a per-seed set would compile anew in every run;
- ``use_eval``: whether the program gets the Q evaluation (``eval_fn``);
- ``checked_events``: the first events, whose readings the reference
  checks; ``warm_events``: untimed events after them, before the window
  opens; ``trace_events``: the events of a traced window.

The image generator is a copy of the program's synthetic stripes
(``repro.data.synthetic.image_dataset``), kept here so that the inputs
stay the same whatever the program does to its own copy.
"""
from __future__ import annotations

import numpy as np

__all__ = ["image_dataset", "check_traffic", "TRAFFIC_KEYS"]

TRAFFIC_KEYS = ("strategy", "engine", "mesh", "nodes", "batch",
                "local_steps", "optimizer", "learning_rate", "warmup_steps",
                "total_steps", "grad_clip", "idpa_mode",
                "allocation_batches", "speed_factors", "n_train", "n_eval",
                "eval_seed", "use_eval", "checked_events", "warm_events",
                "trace_events")


def image_dataset(n: int, size: int = 32, channels: int = 3,
                  num_classes: int = 10, seed: int = 0, noise: float = 0.35):
    """Class-conditional patterns: class c => stripes of frequency c+1 in a
    class-specific channel mix, plus Gaussian noise."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, n).astype(np.int32)
    xs = np.zeros((n, size, size, channels), np.float32)
    grid = np.arange(size, dtype=np.float32)
    for c in range(num_classes):
        idx = labels == c
        if not idx.any():
            continue
        freq = (c % 5) + 1
        vertical = c % 2 == 0
        stripe = np.sin(2 * np.pi * freq * grid / size)
        img = np.tile(stripe[:, None] if vertical else stripe[None, :],
                      (1, size) if vertical else (size, 1))
        mix = np.zeros(channels, np.float32)
        mix[c % channels] = 1.0
        mix[(c // channels) % channels] += 0.5
        xs[idx] = img[None, :, :, None] * mix[None, None, None, :]
    xs += noise * rng.standard_normal(xs.shape).astype(np.float32)
    return xs, labels


def check_traffic(traffic: dict) -> dict:
    """Reject a traffic file with a missing or unknown key."""
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    extra = [k for k in traffic if k not in TRAFFIC_KEYS and k != "doc"]
    if missing or extra:
        raise ValueError(f"traffic file: missing {missing}, unknown {extra}")
    return traffic
