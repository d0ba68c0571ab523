"""End-to-end training driver: BPT-CNN outer layer over any assigned arch.

CPU-scale by default (reduced configs + small synthetic corpus) so the same
driver that launches on a pod runs as a demo here (`pip install -e .`
first; bare checkouts can prefix `PYTHONPATH=src`):

    python -m repro.launch.train --arch yi-6b --reduced \
        --outer agwu --partitioning idpa --rounds 8

``--device-outer`` places the node axis on a real `nodes` device mesh
(``--mesh nodes4`` to name a `launch.mesh.MESHES` member; emulate with
XLA_FLAGS=--xla_force_host_platform_device_count=4), ``--uneven-batches``
realizes IDPA-proportional per-node loads, and ``--engine`` selects the
outer-layer execution engine by name (`repro.core.engine.ENGINES`).  The
outer layer (IDPA + AGWU/SGWU — the paper's contribution) runs with real
jitted steps on CPU.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpointing import checkpoint
from repro.core.bpt_trainer import BPTTrainer, TrainHooks
from repro.core.engine import ENGINES, engine_config
from repro.core.faults import FaultSchedule
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset, host_batch, pack_sequences
from repro.data.synthetic import lm_corpus
from repro.launch import runtime
from repro.models import lm
from repro.models.frontends import random_frontend_embeds


def build_lm_dataset(cfg, seq_len: int, num_rows: int, nodes: int,
                     batches: int, partitioning: str, frequencies):
    corpus = lm_corpus(num_rows * seq_len + 1, cfg.vocab_size, seed=0)
    rows = pack_sequences(corpus, seq_len)
    return IDPADataset({"rows": rows}, num_nodes=nodes, batches=batches,
                       frequencies=frequencies, partitioning=partitioning)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=configs.ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--outer", default="agwu",
                    choices=["agwu", "sgwu", "sync"])
    ap.add_argument("--engine", default="", choices=sorted(ENGINES),
                    help="select the execution engine by name (overrides "
                    "--outer/--device-outer)")
    ap.add_argument("--device-outer", action="store_true",
                    help="shard the node axis over a real `nodes` device "
                    "mesh (one node per device; falls back to the fused "
                    "vmap emulation when fewer than --nodes devices exist)")
    ap.add_argument("--mesh", default="",
                    help="named launch.mesh.MESHES entry for the node axis "
                    "(e.g. nodes4; needs a `nodes` axis of size --nodes); "
                    "empty = auto 1-D nodes mesh")
    ap.add_argument("--uneven-batches", action="store_true",
                    help="IDPA-proportional per-node batch loads "
                    "(padded+masked stripes; needs the SGWU stacked paths)")
    ap.add_argument("--partitioning", default="idpa",
                    choices=["idpa", "udpa"])
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a weight checkpoint AND a resumable "
                    "train-state checkpoint into --ckpt-dir every N merge "
                    "events (0 = only the final weights)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest train-state checkpoint from "
                    "--ckpt-dir before the first round (a fresh dir just "
                    "starts from scratch — safe to always pass)")
    ap.add_argument("--faults", default="",
                    help="fault schedule: comma-separated "
                    "kind:node@event[xfactor] atoms, e.g. "
                    "'fail:1@3,rejoin:1@6,slow:2@4x2.5' — node churn "
                    "injected into the outer layer (core.faults)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    print(f"[train] compilation cache: "
          f"{runtime.maybe_enable_compilation_cache()}")

    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get_config(args.arch)
    if cfg.arch_type == "encdec":
        raise SystemExit("use examples/train_bpt_cnn.py or a decoder arch "
                         "for the LM demo driver")
    print(f"[train] {cfg.name} ({cfg.arch_type}) reduced={args.reduced} "
          f"outer={args.outer} partitioning={args.partitioning}")

    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(key, cfg)
    n_params = sum(np.prod(x.shape) for x in jax.tree_util.tree_leaves(params))
    print(f"[train] params: {n_params/1e6:.1f}M")

    frontend = None
    if cfg.frontend:
        frontend = random_frontend_embeds(key, cfg, args.batch_size)

    def loss_fn(p, batch):
        rows = batch["rows"]
        b = host_batch(rows)
        if "mask" in batch:
            # uneven stripes: padded rows (mask 0) carry no loss — label
            # them -1, which chunked_cross_entropy excludes from the mean
            b["labels"] = jnp.where(batch["mask"][:, None] > 0,
                                    b["labels"], -1)
        if frontend is not None:
            b["frontend_embeds"] = frontend[:rows.shape[0]]
        return lm.loss_fn(p, b, cfg)

    speeds = 1.0 + 0.4 * np.arange(args.nodes) / max(args.nodes - 1, 1)
    ds = build_lm_dataset(cfg, args.seq_len, args.rows, args.nodes,
                          batches=min(4, args.rounds),
                          partitioning=args.partitioning,
                          frequencies=1.0 / speeds)
    common = dict(learning_rate=args.lr, partitioning=args.partitioning,
                  outer_nodes=args.nodes, local_steps=args.local_steps,
                  warmup_steps=5, seed=args.seed,
                  total_steps=args.rounds * args.local_steps * args.nodes,
                  mesh_name=args.mesh, uneven_batches=args.uneven_batches)
    if args.engine:     # engine selected by name through the engine API
        tc = TrainConfig(**engine_config(args.engine, **common))
    else:
        tc = TrainConfig(outer_strategy=args.outer,
                         device_outer=args.device_outer, **common)
    faults = FaultSchedule.from_spec(args.faults, num_nodes=args.nodes) \
        if args.faults else None
    trainer = BPTTrainer(loss_fn, params, ds, tc,
                         batch_size=args.batch_size, speed_factors=speeds,
                         fault_schedule=faults)
    hooks = None
    if args.ckpt_every:
        if not args.ckpt_dir:
            raise SystemExit("--ckpt-every needs --ckpt-dir")
        hooks = TrainHooks(checkpoint_every=args.ckpt_every,
                           checkpoint_dir=args.ckpt_dir,
                           resume=args.resume)
    elif args.resume:
        raise SystemExit("--resume needs --ckpt-every and --ckpt-dir")
    t0 = time.time()
    report = trainer.train(args.rounds, hooks)
    wall = time.time() - t0
    if report.fallback:
        print(f"[train] engine fallback: {report.fallback}")
    print(f"[train] done in {wall:.1f}s wall; report:")
    print(json.dumps(report.summary(), indent=2, default=str))
    if report.losses:
        first, last = report.losses[0], report.losses[-1]
        print(f"[train] loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    else:
        # --resume from a state checkpoint of an already-finished run:
        # nothing left to train, no new events
        print("[train] resumed past the final round; no new rounds ran")
    if args.ckpt_dir and report.losses:
        # last_event, not steps: on a resumed run, steps counts only the
        # events this process produced and would mislabel the checkpoint
        path = checkpoint.save(args.ckpt_dir, report.final_params,
                               step=report.last_event,
                               metadata={"arch": cfg.name})
        print(f"[train] checkpoint: {path}")
    return report


if __name__ == "__main__":
    main()
