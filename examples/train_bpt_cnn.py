"""End-to-end driver: train a ~100M-parameter CNN for a few hundred steps
with the full BPT-CNN stack (IDPA + AGWU + inner-layer parallelism).

This is the paper's own workload at the largest scale this container
sustains: Table-2 "case2" topology at 32px with a widened FC stack
(~100M params), 4 virtual heterogeneous nodes, a few hundred optimizer
steps.  Reports the accuracy trace, sync-wait and communication volume.

Run:  python examples/train_bpt_cnn.py [--steps 200]
(`pip install -e .` first; bare checkouts can prefix `PYTHONPATH=src`.)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bpt_trainer import BPTTrainer, TrainHooks
from repro.core.engine import ENGINES, engine_config
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.launch import runtime
from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss, init_cnn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=240,
                    help="total optimizer steps across all nodes")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=3)
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--fc-neurons", type=int, default=2000,
                    help="2000 -> ~100M params (paper case5-7 FC scale)")
    ap.add_argument("--strategy", choices=("sgwu", "agwu"), default="agwu")
    ap.add_argument("--engine", choices=sorted(ENGINES), default="",
                    help="select the outer-layer execution engine by name "
                    "(overrides --strategy/--device-outer; see "
                    "repro.core.engine.ENGINES)")
    ap.add_argument("--device-outer", action="store_true",
                    help="shard the node axis over a real `nodes` device "
                    "mesh (needs >= --nodes devices, e.g. XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4; falls back "
                    "to the fused vmap emulation otherwise)")
    ap.add_argument("--uneven-batches", action="store_true",
                    help="IDPA-proportional per-node batch loads "
                    "(padded+masked stripes; needs --strategy sgwu)")
    ap.add_argument("--small", action="store_true",
                    help="tiny demo (fast)")
    args = ap.parse_args(argv)

    if args.small:
        args.fc_neurons, args.image_size, args.steps = 256, 16, 60
    print(f"[bpt-cnn] compilation cache: "
          f"{runtime.maybe_enable_compilation_cache()}")

    cfg = CNNConfig(name="case2-wide", image_size=args.image_size,
                    conv_layers=4, filters=4, fc_layers=3,
                    fc_neurons=args.fc_neurons)
    params = init_cnn(jax.random.PRNGKey(0), cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"[bpt-cnn] model: {cfg.conv_layers} conv + {cfg.fc_layers} fc, "
          f"{n/1e6:.1f}M params, {args.image_size}px")

    xs, ys = image_dataset(4000, size=args.image_size, seed=0)
    xe, ye = image_dataset(800, size=args.image_size, seed=7)
    eval_batch = {"images": jnp.asarray(xe), "labels": jnp.asarray(ye)}
    eval_fn = jax.jit(lambda p: cnn_accuracy(p, eval_batch, cfg))

    speeds = 1.0 + 0.5 * np.arange(args.nodes)
    rounds = max(1, args.steps // (args.nodes * args.local_steps))
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=args.nodes,
                     batches=min(3, rounds), frequencies=1.0 / speeds,
                     idpa_mode="balanced")
    common = dict(outer_nodes=args.nodes, optimizer="adamw",
                  learning_rate=1e-3, warmup_steps=10,
                  total_steps=args.steps, local_steps=args.local_steps,
                  uneven_batches=args.uneven_batches)
    if args.engine:     # engine selected by name through the engine API
        tc = TrainConfig(**engine_config(args.engine, **common))
    else:
        tc = TrainConfig(outer_strategy=args.strategy,
                         device_outer=args.device_outer, **common)
    trainer = BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}), params, ds,
                         tc, batch_size=32, eval_fn=eval_fn,
                         speed_factors=speeds)
    hooks = TrainHooks(on_round=lambda ev: print(
        f"[bpt-cnn]   event {ev.round + 1}: loss={ev.loss:.4f} "
        f"clock={ev.virtual_clock:.1f}s", flush=True))
    t0 = time.time()
    rep = trainer.train(rounds=rounds, hooks=hooks)
    print(f"[bpt-cnn] {rep.steps} pushes in {time.time()-t0:.0f}s wall "
          f"({rep.strategy}/{rep.backend} outer backend, "
          f"{len(jax.devices())} device(s))")
    if rep.fallback:
        print(f"[bpt-cnn] engine fallback: {rep.fallback}")
    print(f"[bpt-cnn] accuracy trace: "
          f"{[(round(t,1), round(a,3)) for t, a in rep.accuracies]}")
    print(f"[bpt-cnn] IDPA allocation (samples/node): {rep.allocation}")
    print(f"[bpt-cnn] sync_wait={rep.sync_wait:.2f}s (AGWU -> 0) "
          f"comm={rep.comm_bytes/2**20:.1f}MB")
    # sanity: beat 10-class chance.  AGWU applies m× more global updates
    # than SGWU in the same --steps budget, so it clears a higher bar.
    floor = 0.3 if rep.strategy == "agwu" else 0.15
    assert rep.accuracies[-1][1] > floor, "should beat 10-class chance"


if __name__ == "__main__":
    main()
