"""Chip smoke test: train the Table-2 case7 CNN through BPTTrainer on a TPU.

Run from the repository root, as one process that starts no others:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip outer layer only

With no option it checks the training set's draw from the chip against the
host draw (``check_resident_gather``), and the compiled Pallas path against
the jnp reference on the chip (one case7 forward and gradient), then trains
case7
(10 conv layers of 12 filters, 7 FC layers of 2000 neurons, 32x32x3
images) with m=4 nodes, a per-node batch of 64 and 2 local steps: SGWU
rounds on ``VmapEngine`` — again under the jnp reference, whose losses
and merged weights the Pallas run must match — and AGWU pushes on
``HeapEngine``.  With
``--chips 4`` it runs SGWU on ``ShardMapEngine`` over the ``nodes4`` mesh
against ``VmapEngine`` on the same data and seed, and AGWU on
``HeapDeviceEngine`` with each node's weights on its own chip.

Every layer runs a compiled Pallas kernel.  The run fails (non-zero exit)
when JAX finds no TPU, on a recorded kernel fallback, an engine fallback,
a non-finite loss or a gap past the stated tolerances.  The times it
prints are readings of one run, not a benchmark.  The last line of stdout
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Pallas path vs the jnp reference on the same chip: relative L2 gap of
# the logits, the loss and every gradient leaf.  Both paths compute in
# f32, but the kernels accumulate in their own tile and K-step order and
# TPU matmuls at default precision round f32 operands through bf16
# passes — the two orders round differently, layer after layer.
REF_RTOL = 5e-2
# ShardMapEngine vs VmapEngine: the same kernels on the same data; only
# the Eq. 7 merge (an all-reduce over `nodes` vs a sum over the stacked
# axis) and XLA's fusion of the two programs differ.
OUTER_RTOL = 1e-3


@dataclasses.dataclass(frozen=True)
class Smoke:
    """Sizes of one smoke run (the defaults are the chip run's)."""
    case: str = "case7"
    image_size: int = 32
    nodes: int = 4
    batch: int = 64
    local_steps: int = 2
    sgwu_rounds: int = 3
    agwu_rounds: int = 2
    n_train: int = 4096
    n_eval: int = 256
    seed: int = 0


def _log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _flat(tree) -> np.ndarray:
    import jax
    return np.concatenate([np.ravel(x) for x in jax.tree_util.tree_leaves(
        jax.device_get(tree))])


def _check(name: str, gap: float, tol: float) -> None:
    _log(f"{name}: relative gap {gap!r} (tolerance {tol})")
    if not gap <= tol:
        raise AssertionError(f"{name}: gap {gap} exceeds {tol}")


@contextlib.contextmanager
def _impl(name: str):
    """Select the kernel impl for the calls traced inside the block."""
    saved = os.environ.get("REPRO_KERNEL_IMPL", "")
    os.environ["REPRO_KERNEL_IMPL"] = name
    try:
        yield
    finally:
        os.environ["REPRO_KERNEL_IMPL"] = saved


def _data(s: Smoke):
    from repro.data.synthetic import image_dataset
    xs, ys = image_dataset(s.n_train, size=s.image_size, seed=s.seed)
    xe, ye = image_dataset(s.n_eval, size=s.image_size, seed=s.seed + 7)
    return xs, ys, xe, ye


def check_against_ref(s: Smoke, cfg, params, xs, ys) -> None:
    """One forward and gradient of the case under the compiled Pallas
    kernels against the jnp reference, both on the same device."""
    import jax
    import jax.numpy as jnp
    from repro.models.cnn import cnn_forward, cnn_loss
    batch = jax.device_put({"images": jnp.asarray(xs[:s.batch]),
                            "labels": jnp.asarray(ys[:s.batch])})
    out = {}
    for impl in ("pallas", "ref"):
        with _impl(impl):
            logits = jax.jit(lambda p, b: cnn_forward(p, b["images"], cfg))(
                params, batch)
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p, b: cnn_loss(p, b, cfg)))(params, batch)
            out[impl] = jax.device_get((logits, loss, grads))
    (lp, vp, gp), (lr, vr, gr) = out["pallas"], out["ref"]
    _check("forward logits", _rel(lp, lr), REF_RTOL)
    _check("loss", _rel(vp, vr), REF_RTOL)
    leaves_p = jax.tree_util.tree_leaves_with_path(gp)
    leaves_r = jax.tree_util.tree_leaves(gr)
    worst = max((_rel(g, r), jax.tree_util.keystr(path))
                for (path, g), r in zip(leaves_p, leaves_r, strict=True))
    _check(f"gradient (worst leaf {worst[1]})", worst[0], REF_RTOL)


# the benchmark cells' sets and rounds: (images, size, nodes, steps, batch)
GATHER_SHAPES = ((4096, 32, 4, 2, 64), (1024, 224, 4, 2, 16))


def check_resident_gather(seed: int = 0) -> None:
    """At each of ``GATHER_SHAPES``, a round drawn from the set kept on the
    chip (``IDPADataset.to_device``) equals the host draw of the same
    indices bit for bit, with and without ``uneven``, and leaves the RNG
    where the host draw leaves it."""
    import jax
    from repro.data.pipeline import IDPADataset
    gen = np.random.default_rng(seed)
    for n, size, m, h, b in GATHER_SHAPES:
        ds = IDPADataset(
            {"images": gen.standard_normal((n, size, size, 3), np.float32),
             "labels": gen.integers(0, 1000, n, dtype=np.int32)},
            num_nodes=m, batches=3, frequencies=1.0 / (1.0 + 0.5 *
                                                       np.arange(m)),
            idpa_mode="balanced")
        rows = ds.to_device()
        for uneven in (False, True):
            host_rng = np.random.default_rng(seed + n)
            dev_rng = np.random.default_rng(seed + n)
            want = ds.stacked_round_batches(b, h, host_rng, uneven=uneven)
            got = jax.device_get(ds.stacked_round_batches(
                b, h, dev_rng, uneven=uneven, resident=rows))
            if sorted(got) != sorted(want) or any(
                    got[k].dtype != want[k].dtype or
                    not np.array_equal(got[k], want[k]) for k in want):
                raise AssertionError(f"resident draw of {n} x {size} px "
                                     f"(uneven={uneven}) differs")
            if dev_rng.bit_generator.state != host_rng.bit_generator.state:
                raise AssertionError("resident draw consumed the RNG "
                                     "differently")
        temp = ds.gather_temp_bytes((m, h, b), jax.local_devices()[0])
        _log(f"resident draw of {n} x {size} px equals the host draw; "
             f"set {sum(x.on_device_size_in_bytes() for x in rows.values())}"
             f" bytes on the chip ({ds.nbytes} on the host), gather "
             f"temporaries {temp} bytes")


def make_trainer(s: Smoke, engine: str, cfg, params, data, *, rounds: int,
                 eval_fn=None, batches: int = 3, **overrides):
    """A BPTTrainer on the named engine, as the example drivers build it."""
    from repro.core.bpt_trainer import BPTTrainer
    from repro.core.engine import engine_config
    from repro.core.types import TrainConfig
    from repro.data.pipeline import IDPADataset
    from repro.models.cnn import cnn_loss
    xs, ys = data
    speeds = 1.0 + 0.5 * np.arange(s.nodes)
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=s.nodes,
                     batches=batches, frequencies=1.0 / speeds,
                     idpa_mode="balanced")
    tc = TrainConfig(**engine_config(
        engine, outer_nodes=s.nodes, optimizer="adamw", learning_rate=1e-3,
        warmup_steps=2, total_steps=rounds * s.nodes * s.local_steps,
        local_steps=s.local_steps, seed=s.seed, **overrides))
    return BPTTrainer(lambda p, b: (cnn_loss(p, b, cfg), {}), params, ds,
                      tc, batch_size=s.batch, eval_fn=eval_fn,
                      speed_factors=speeds)


def train(s: Smoke, engine: str, cfg, params, data, *, rounds: int,
          **kw):
    """Train through ``BPTTrainer.train``; returns the report and the wall
    time of each merge event."""
    from repro.core.bpt_trainer import TrainHooks
    trainer = make_trainer(s, engine, cfg, params, data, rounds=rounds, **kw)
    stamps = [time.perf_counter()]
    hooks = TrainHooks(on_round=lambda ev: stamps.append(time.perf_counter()))
    rep = trainer.train(rounds=rounds, hooks=hooks)
    return rep, np.diff(stamps)


def _expect(rep, backend: str, events: int) -> None:
    if rep.backend != backend or rep.fallback is not None:
        raise AssertionError(f"asked for {backend}, ran {rep.backend} "
                             f"(fallback: {rep.fallback})")
    if rep.steps != events or not np.all(np.isfinite(rep.losses)):
        raise AssertionError(f"{backend}: {rep.steps} events, losses "
                             f"{rep.losses}")


def _report_times(name: str, walls, per: str) -> None:
    _log(f"{name}: first {per} {float(walls[0])!r} s (compile included), "
         f"steady {float(np.median(walls[1:]))!r} s per {per} "
         "(smoke reading, not a benchmark)")


def one_chip(s: Smoke) -> None:
    """Correctness against the reference, then SGWU and AGWU training."""
    import jax
    import jax.numpy as jnp
    from repro.models.cnn import cnn_accuracy, init_cnn, make_case
    cfg = make_case(s.case, image_size=s.image_size)
    params = init_cnn(jax.random.PRNGKey(s.seed), cfg)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    _log(f"{s.case}: {cfg.conv_layers} conv x {cfg.filters} filters, "
         f"{cfg.fc_layers} fc x {cfg.fc_neurons}, {n} parameters")
    xs, ys, xe, ye = _data(s)
    check_resident_gather(s.seed)
    check_against_ref(s, cfg, params, xs, ys)

    eval_batch = jax.device_put({"images": jnp.asarray(xe),
                                 "labels": jnp.asarray(ye)})

    def make_eval():                 # a fresh jit traces under the impl
        return jax.jit(lambda p: cnn_accuracy(p, eval_batch, cfg))

    # batches=1 freezes the IDPA allocation, so the reference run below
    # sees the same data whatever the wall times
    rep, walls = train(s, "vmap", cfg, params, (xs, ys), batches=1,
                       rounds=s.sgwu_rounds, eval_fn=make_eval())
    _expect(rep, "vmap", s.sgwu_rounds)
    _log(f"SGWU/vmap losses {rep.losses}")
    _report_times("SGWU/vmap", walls, "round")
    with _impl("ref"):
        ref, _ = train(s, "vmap", cfg, params, (xs, ys), batches=1,
                       rounds=s.sgwu_rounds, eval_fn=make_eval())
    _log(f"SGWU/vmap losses under the jnp reference {ref.losses}")
    _check("SGWU losses, pallas vs ref", _rel(rep.losses, ref.losses),
           REF_RTOL)
    _check("SGWU merged weights, pallas vs ref",
           _rel(_flat(rep.final_params), _flat(ref.final_params)), REF_RTOL)

    rep, walls = train(s, "heap", cfg, params, (xs, ys),
                       rounds=s.agwu_rounds, eval_fn=make_eval())
    _expect(rep, "heap", s.agwu_rounds * s.nodes)
    _log(f"AGWU/heap losses {rep.losses}")
    _report_times("AGWU/heap", walls, "push")


def four_chips(s: Smoke) -> None:
    """SGWU sharded over `nodes` vs the vmapped emulation, and AGWU with
    node-pinned weights."""
    import jax
    from repro.core.engine import resolve_engine
    from repro.models.cnn import init_cnn, make_case
    cfg = make_case(s.case, image_size=s.image_size)
    params = init_cnn(jax.random.PRNGKey(s.seed), cfg)
    xs, ys, _, _ = _data(s)
    # batches=1 freezes the IDPA allocation: wall-time noise cannot change
    # the data the two engines see
    runs = {}
    for engine, extra in (("device", {"mesh_name": f"nodes{s.nodes}"}),
                          ("vmap", {})):
        rep, walls = train(s, engine, cfg, params, (xs, ys),
                           rounds=s.sgwu_rounds, batches=1, **extra)
        _expect(rep, engine, s.sgwu_rounds)
        _log(f"SGWU/{engine} losses {rep.losses}")
        _report_times(f"SGWU/{engine}", walls, "round")
        runs[engine] = rep
    dev, emu = runs["device"], runs["vmap"]
    _check("SGWU device vs vmap losses", _rel(dev.losses, emu.losses),
           OUTER_RTOL)
    _check("SGWU device vs vmap merged weights",
           _rel(_flat(dev.final_params), _flat(emu.final_params)),
           OUTER_RTOL)

    # HeapDeviceEngine through the engine protocol BPTTrainer.run drives,
    # so the node-resident state can be inspected after the pushes
    trainer = make_trainer(s, "heap-device", cfg, params, (xs, ys),
                           rounds=s.agwu_rounds)
    plan = resolve_engine(trainer.tc)
    if plan.backend != "heap-device" or plan.fallback is not None:
        raise AssertionError(f"heap-device plan: {plan}")
    engine = plan.engine_cls(trainer, plan)
    state = engine.setup(s.agwu_rounds)
    losses = [ev.loss for ev in engine.events(s.agwu_rounds, state=state)]
    if len(losses) != s.agwu_rounds * s.nodes or \
            not np.all(np.isfinite(losses)):
        raise AssertionError(f"heap-device losses {losses}")
    homes = []
    for j in range(s.nodes):
        devs = {d for leaf in jax.tree_util.tree_leaves(
            (state.local[j], state.opt_states[j])) for d in leaf.devices()}
        if devs != {plan.devices[j]}:
            raise AssertionError(f"node {j} state on {devs}, "
                                 f"expected {plan.devices[j]}")
        homes.append(plan.devices[j].id)
    if len(set(homes)) != s.nodes:
        raise AssertionError(f"node weights share devices: {homes}")
    _log(f"AGWU/heap-device losses {losses}; node j's weights on device "
         f"ids {homes}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip outer-layer phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch import runtime
    os.environ["REPRO_KERNEL_IMPL"] = "pallas"
    if ops._interpret():
        raise AssertionError("Pallas kernels would run in interpret mode")
    cache = runtime.maybe_enable_compilation_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    _log(f"{len(devices)} x {devices[0].device_kind}; compilation cache "
         f"{cache} ({warm} entries at start)")
    ops.clear_fallback_log()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(Smoke())
    if ops.fallback_events():
        raise AssertionError(f"kernel fallbacks: {ops.fallback_events()}")
    _log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
