"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bpt_trainer import BPTTrainer
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.launch.runtime import maybe_enable_compilation_cache
from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss, init_cnn
from repro.sanitize import compile_budget, install_compile_listener

# persistent XLA cache: repeat benchmark runs skip compiles (the
# measured regions all warm up first, so timings are unaffected)
maybe_enable_compilation_cache()
# compile-event counter: time_call() asserts its measured repeats hit
# the dispatch cache — a benchmark that recompiles mid-measurement is
# timing XLA, not the kernel, and must fail loudly
install_compile_listener()

ROWS = []


def emit(name: str, us_per_call: float, derived: str):
    line = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(line)
    print(line)


def section(title: str):
    """Human-facing section banner.  Goes to STDERR on purpose: stdout is
    the machine-parseable ``name,us_per_call,derived`` CSV stream the CI
    benchmark gate consumes."""
    print(f"== {title} ==", file=sys.stderr)


def time_call(fn, *args, repeats=3):
    fn(*args)                                  # warmup/compile
    with compile_budget(0, label="time_call measured region"):
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def cnn_experiment(strategy: str, partitioning: str, *, nodes=3, rounds=6,
                   local_steps=3, n_train=1200, n_eval=300, seed=0,
                   idpa_mode="balanced", lr=2e-3, image_size=16):
    """One BPT-CNN training run; returns (TrainReport, wall_seconds)."""
    cfg = CNNConfig(name="bench", image_size=image_size, conv_layers=2,
                    filters=8, fc_layers=2, fc_neurons=64)
    xs, ys = image_dataset(n_train, size=image_size, seed=seed)
    xe, ye = image_dataset(n_eval, size=image_size, seed=seed + 77)
    params = init_cnn(jax.random.PRNGKey(seed), cfg)
    eval_batch = {"images": jnp.asarray(xe), "labels": jnp.asarray(ye)}
    eval_fn = jax.jit(lambda p: cnn_accuracy(p, eval_batch, cfg))
    speeds = 1.0 + 0.6 * np.arange(nodes) / max(nodes - 1, 1)
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=nodes,
                     batches=3, frequencies=1.0 / speeds,
                     partitioning=partitioning, idpa_mode=idpa_mode)
    # fair comparison: the single-node sync baseline runs the same TOTAL
    # optimizer steps per round as the m parallel nodes combined
    eff_local = local_steps * (nodes if strategy == "sync" else 1)
    tc = TrainConfig(outer_strategy=strategy, partitioning=partitioning,
                     outer_nodes=nodes, optimizer="adamw",
                     learning_rate=lr, warmup_steps=10,
                     total_steps=rounds * local_steps * nodes,
                     local_steps=eff_local, seed=seed)
    tr = BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}), params, ds, tc,
                    batch_size=64, eval_fn=eval_fn, speed_factors=speeds)
    t0 = time.perf_counter()
    rep = tr.train(rounds=rounds)
    return rep, time.perf_counter() - t0
