"""BPTTrainer — the paper's bi-layered training loop over real JAX steps.

Outer layer: m virtual computing nodes (data-parallel groups).  Each node
pulls the global weights from the ParameterServer, runs ``local_steps``
jitted train steps on its IDPA-assigned data stripe, and pushes back under
SGWU (barrier, Eq. 7) or AGWU (event-ordered, Eq. 9-10).  Node heterogeneity
is emulated with per-node speed factors scaling measured step times into
virtual completion times — the event order (and therefore the staleness
pattern AGWU sees) is exactly the paper's.

The outer layer's execution substrates are pluggable engines
(``repro.core.engine``): the sync scan baseline, the legacy sequential
loop, the fused vmap(nodes) x scan(local_steps) dispatch, the
shard_map round on a real `nodes` device mesh, and the AGWU event heap
(host-server or node-pinned delta-push variants).
``engine.resolve_engine`` is the single point that maps a TrainConfig to
an engine — it owns every flag-combination rule and the transparent
device-count fallback, which is recorded in the ``EnginePlan`` and
surfaced on ``TrainReport.fallback``.

Two entry points:

- ``run(rounds, hooks)`` — a generator yielding one ``RoundEvent`` per
  merge (per round for SGWU/sync, per push for AGWU) so callers stream
  losses, evaluate on their own cadence, checkpoint mid-run and
  early-stop.  ``TrainHooks`` supplies the eval / checkpoint / callback
  cadences.
- ``train(rounds, hooks)`` — drains ``run`` into a ``TrainReport``; the
  historical API every test and driver keeps using.

Inner layer: the jitted step itself — XLA/Pallas task parallelism
(DESIGN.md §3) — plus optional activation remat.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import checkpoint
from repro.data.pipeline import IDPADataset
from repro.sanitize import sanctioned_scope, sanctioned_sync
from repro.optim.optimizers import (apply_updates, clip_by_global_norm,
                                    make_optimizer, warmup_cosine)

from .engine import RoundEvent, TrainHooks, resolve_engine
from .types import TrainConfig

__all__ = ["BPTTrainer", "TrainReport", "TrainHooks", "RoundEvent"]


@dataclasses.dataclass
class TrainReport:
    strategy: str
    steps: int
    losses: list
    accuracies: list            # (virtual_time, accuracy) pairs
    virtual_makespan: float
    sync_wait: float
    comm_bytes: int
    allocation: np.ndarray
    final_params: object = None
    # which outer-layer execution backend actually ran: "device" (sharded
    # over a real `nodes` mesh), "vmap" (fused single-device emulation),
    # "sequential" (legacy loop), "heap"/"heap-device" (AGWU), "scan"
    # (sync baseline).  The device path falls back to "vmap" when the
    # backend has too few devices — callers can assert on this.
    backend: str = ""
    # the EnginePlan's recorded device-count fallback reason when the
    # executed backend differs from the requested one, else None
    fallback: Optional[str] = None
    # global index just past the last event (= its round + 1); differs
    # from ``steps`` when the run resumed from a state checkpoint, where
    # ``steps`` counts only the events this process produced
    last_event: int = 0

    def summary(self) -> dict:
        out = {
            "strategy": self.strategy,
            "backend": self.backend,
            "steps": self.steps,
            "final_loss": round(float(self.losses[-1]), 4) if self.losses else None,
            "final_acc": round(float(self.accuracies[-1][1]), 4)
            if self.accuracies else None,
            "makespan": round(self.virtual_makespan, 3),
            "sync_wait": round(self.sync_wait, 3),
            "comm_MB": round(self.comm_bytes / 2**20, 2),
        }
        if self.fallback:
            out["fallback"] = self.fallback
        return out


class BPTTrainer:
    def __init__(self,
                 loss_fn: Callable,                 # (params, batch) -> (loss, aux)
                 init_params,
                 dataset: IDPADataset,
                 train_cfg: TrainConfig,
                 batch_size: int,
                 eval_fn: Optional[Callable] = None,   # (params) -> accuracy
                 speed_factors: Optional[Sequence[float]] = None,
                 accuracy_weighting: str = "normalized",
                 model_cfg=None,
                 plan_family: str = "",
                 fault_schedule=None):
        # accuracy_weighting:
        #   "paper"      — Eq. (10) verbatim: scale = gamma * Q.  With small
        #     absolute accuracies early in training this under-applies local
        #     progress (the paper's full-epoch/30-node regime hides it).
        #   "normalized" — beyond-paper fix: Q is divided by its running
        #     mean, so the *relative* contribution weighting the paper wants
        #     is kept while the update magnitude stays ~gamma.
        self.loss_fn = loss_fn
        self.dataset = dataset
        self.tc = train_cfg
        self.batch_size = batch_size
        self.eval_fn = eval_fn
        # optional model config (e.g. CNNConfig): lets the 2-D hybrid-mesh
        # engine plan per-layer parallelization (core.planner); without it
        # a 2-D mesh runs the generic batch-family plan.  ``plan_family``
        # forces a planner family ("batch"/"channel", tests & search);
        # "" lets the cost model pick.
        self.model_cfg = model_cfg
        self.plan_family = plan_family
        self.m = train_cfg.outer_nodes
        # optional FaultSchedule (core.faults): node churn the engines
        # replay — fail/rejoin/slow transitions keyed on event indices
        self.faults = fault_schedule
        if fault_schedule is not None and not fault_schedule.empty:
            fault_schedule.validate_nodes(self.m)
        self.speed = np.asarray(speed_factors if speed_factors is not None
                                else np.ones(self.m), np.float64)
        self.opt = make_optimizer(train_cfg.optimizer)
        self.schedule = warmup_cosine(train_cfg.learning_rate,
                                      train_cfg.warmup_steps,
                                      train_cfg.total_steps)
        self.params0 = init_params
        self.rng = np.random.default_rng(train_cfg.seed)
        self.accuracy_weighting = accuracy_weighting
        self._q_ema = None
        self._eval_vmapped = None    # lazily-built vmap of eval_fn (fused)
        self.last_plan = None        # EnginePlan of the most recent run()
        self.last_engine = None      # engine instance of the most recent run()

        node_round = self._make_node_round()
        self._train_step = jax.jit(self._make_step_body())
        # single-node round: ONE dispatch per local round (sync baseline)
        self._scan_round = jax.jit(node_round)
        # fused outer layer: nodes × local_steps in ONE dispatch.  The
        # node-stacked params/opt-state buffers are donated — each round
        # consumes the previous round's stack instead of copying it m×.
        self._fused_round = jax.jit(
            jax.vmap(node_round, in_axes=(0, 0, 0, None)),
            donate_argnums=(0, 1))
        self._node_round = node_round
        self._device_rounds = {}     # (mesh, plan) -> shard_mapped round

    def _make_step_body(self, combine=None):
        """One optimizer step.  ``combine`` (model-axis rounds) recombines
        the per-shard loss/grads BEFORE clipping, so the clip sees the
        same global norm the unsharded paths clip."""
        grad_clip = self.tc.grad_clip

        def step_body(params, opt_state, batch, step):
            (loss, aux), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(params, batch)
            if combine is not None:
                loss, grads = combine(loss, grads, batch)
            if grad_clip:
                grads, _ = clip_by_global_norm(grads, grad_clip)
            lr = self.schedule(step)
            updates, opt_state = self.opt.update(grads, opt_state, params, lr)
            params = apply_updates(params, updates)
            return params, opt_state, loss

        return step_body

    def _make_node_round(self, combine=None):
        """One node's local iteration as a lax.scan over local_steps.

        ``batches`` leaves are (local_steps, B, ...); ``step`` is the
        round index, held constant across the scan exactly like the
        sequential loop held it constant across its local steps.
        """
        step_body = self._make_step_body(combine)

        def node_round(params, opt_state, batches, step):
            def body(carry, batch):
                params, opt_state = carry
                params, opt_state, loss = step_body(
                    params, opt_state, batch, step)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), batches)
            return params, opt_state, losses[-1]

        return node_round

    def _q_effective(self, q: float) -> float:
        """Relative contribution weight Q (see accuracy_weighting above)."""
        q = max(q, 1e-3)
        if self.accuracy_weighting == "paper":
            return q
        self._q_ema = q if self._q_ema is None else \
            0.9 * self._q_ema + 0.1 * q
        return float(np.clip(q / max(self._q_ema, 1e-3), 0.25, 2.0))

    # ------------------------------------------------------------------
    def _local_round(self, params, opt_state, node: int, step: int):
        """One node's local iteration: ``local_steps`` steps on its stripe."""
        t0 = time.perf_counter()
        loss = None
        for _ in range(self.tc.local_steps):
            batch = self.dataset.node_batch(node, self.batch_size, self.rng)
            # one explicit placement for batch + step scalar: the train
            # step dispatch never uploads implicitly (transfer-guard clean)
            batch, step_dev = jax.device_put((batch, np.int32(step)))
            params, opt_state, loss = self._train_step(
                params, opt_state, batch, step_dev)
        # Eq. 8 measurement boundary — a sanctioned sync, not a hidden one
        loss = float(sanctioned_sync(loss, "local-round.loss"))
        wall = time.perf_counter() - t0
        return params, opt_state, loss, wall * self.speed[node]

    def _eval(self, params):
        # accuracy evals PULL by design (the scalar feeds Eq. 7/10
        # weighting), and eval_fns are caller-supplied host code — the
        # whole call is a sanctioned scope under the transfer sanitizer
        if not self.eval_fn:
            return 0.0
        with sanctioned_scope("eval"):
            return float(self.eval_fn(params))

    @staticmethod
    def _node_slice(stacked, node: int):
        """Node ``j``'s view of a node-stacked pytree."""
        return jax.tree_util.tree_map(lambda x: x[node], stacked)

    def _eval_nodes(self, stacked) -> list:
        """Per-node accuracies for a node-stacked pytree.

        One vmapped dispatch when ``eval_fn`` is traceable (keeping the
        fused round's O(1)-in-m dispatch property); an eval_fn that fails
        its FIRST vmapped trace/execution (host-side numpy code, python
        control flow) downgrades permanently to the per-node slice loop.
        Failures after a successful first call propagate — they signal a
        real runtime problem, not untraceability.
        """
        if self._eval_vmapped is None:       # first use: probe traceability
            try:
                fn = jax.jit(jax.vmap(self.eval_fn))
                qs = sanctioned_sync(fn(stacked), "eval-nodes")
                self._eval_vmapped = fn
                return [max(float(q), 1e-3) for q in qs]
            except Exception:
                self._eval_vmapped = False
        if self._eval_vmapped is not False:
            qs = sanctioned_sync(self._eval_vmapped(stacked), "eval-nodes")
            return [max(float(q), 1e-3) for q in qs]
        return [max(self._eval(self._node_slice(stacked, j)), 1e-3)
                for j in range(self.m)]

    def _get_device_round(self, mesh, netplan=None):
        """shard_map the fused round over the mesh's `nodes` axis: node
        axis = device axis, so each device runs ITS node's scan on ITS
        resident block of the stacked pytrees — no cross-device traffic
        until the merge all-reduce.

        On a 2-D ``(nodes, model)`` mesh the round executes ``netplan``
        (``core.planner.NetworkPlan``): batches are placed with the
        plan's ``batch_spec`` (batch family: the per-node stripe splits
        over ``model`` too), and a batch-family plan recombines the
        per-shard loss/grads with the exact sample-count-weighted psum
        over ``model`` — restricted to the ``model`` axis only, so the
        Eq. 7 merge psum stays a pure ``nodes`` collective.  Cached per
        (mesh, plan) so repeated runs reuse the compiled dispatch."""
        key = (mesh, netplan)
        if key not in self._device_rounds:
            from jax.experimental.shard_map import shard_map
            P = jax.sharding.PartitionSpec
            node_round = self._node_round
            batch_spec = P("nodes")
            if netplan is not None and netplan.model > 1:
                from repro.core import planner
                batch_spec = netplan.batch_spec
                if netplan.combine_grads:
                    node_round = self._make_node_round(
                        planner.grad_combine(netplan))

            def shard_body(stacked_w, stacked_opt, batches, step):
                # per-device blocks keep a leading node axis (m/devices)
                return jax.vmap(node_round, in_axes=(0, 0, 0, None))(
                    stacked_w, stacked_opt, batches, step)

            # check_rep=False: pallas_call carries no replication rule
            # (the shard_map checker rejects any kernel-impl round), and
            # the planned 2-D body's custom-VJP collectives already
            # encode the model-axis replication the checker would try to
            # infer.  The equivalence suite gates the semantics instead.
            sm = shard_map(shard_body, mesh=mesh,
                           in_specs=(P("nodes"), P("nodes"), batch_spec,
                                     P()),
                           out_specs=(P("nodes"), P("nodes"), P("nodes")),
                           check_rep=False)
            self._device_rounds[key] = jax.jit(sm, donate_argnums=(0, 1))
        return self._device_rounds[key]

    # ------------------------------------------------------------------
    def run(self, rounds: int,
            hooks: Optional[TrainHooks] = None) -> Iterator[RoundEvent]:
        """Stream the outer layer: one ``RoundEvent`` per merge.

        Resolves the execution engine (``engine.resolve_engine``), then
        yields each merge event — round index, per-node losses, virtual
        clock, cumulative sync-wait and comm-bytes, and the pull-able
        post-merge global weights.  Callers evaluate / checkpoint /
        early-stop at will; breaking out of the iterator stops training.

        ``hooks`` layers cadences on the stream: accuracy evals every
        ``eval_every`` events (0 keeps the engine's historical default),
        ``checkpoint_every`` saves ``event.params`` into
        ``checkpoint_dir`` via ``repro.checkpointing`` — plus, for
        resumable engines, a ``kind="state"`` checkpoint carrying the
        engine snapshot, parameter-server log, IDPA allocation state and
        host RNG state — and ``on_round`` observes every event before it
        is yielded.  ``hooks.resume=True`` restores the latest state
        checkpoint before the first event, so a killed run relaunched
        with the same config continues losslessly (and a first launch
        with ``resume=True`` simply starts from scratch).

        A generator: config errors raise at the first ``next()``.
        """
        hooks = hooks or TrainHooks()
        plan = resolve_engine(self.tc)
        self.last_plan = plan
        engine = plan.engine_cls(self, plan)
        self.last_engine = engine
        eval_every = hooks.eval_every or engine.default_eval_every
        state = engine.setup(rounds)
        start = 0
        if hooks.resume and hooks.checkpoint_dir:
            start = self._restore_run(engine, state, hooks.checkpoint_dir)
        for ev in engine.events(rounds, start=start, state=state):
            n = ev.round + 1
            if self.eval_fn and n % eval_every == 0:
                ev.accuracy = self._eval(ev.params)
            if hooks.checkpoint_every and hooks.checkpoint_dir \
                    and n % hooks.checkpoint_every == 0:
                checkpoint.save(hooks.checkpoint_dir, ev.params, step=n)
                self._save_run_state(engine, state, hooks.checkpoint_dir, n)
            if hooks.on_round:
                hooks.on_round(ev)
            yield ev

    def _save_run_state(self, engine, state, ckpt_dir: str, n: int) -> None:
        """Write the resumable train state (``kind="state"``) at event n."""
        snap = engine.snapshot(state)
        if snap is None:
            return                       # engine is not resumable
        arrays, scalars = snap
        scalars["trainer"] = {
            "next_event": n,
            "rng": self.rng.bit_generator.state,
            "dataset": self.dataset.state_dict(),
            "q_ema": self._q_ema,
        }
        checkpoint.save_state(ckpt_dir, arrays, n, scalars)

    def _restore_run(self, engine, state, ckpt_dir: str) -> int:
        """Restore the latest state checkpoint into ``state``; returns the
        event index to resume from (0 when no state checkpoint exists)."""
        step = checkpoint.latest_step(ckpt_dir, kind="state")
        if step is None:
            return 0
        snap = engine.snapshot(state)
        if snap is None:
            raise ValueError(
                f"{type(engine).__name__} does not support resumption but "
                f"{ckpt_dir} holds a state checkpoint")
        arrays_like, _ = snap
        arrays, scalars, _ = checkpoint.restore_state(
            ckpt_dir, arrays_like, step)
        engine.restore_snapshot(state, arrays, scalars)
        tr = scalars["trainer"]
        self.rng.bit_generator.state = tr["rng"]
        self.dataset.load_state_dict(tr["dataset"])
        self._q_ema = tr["q_ema"]
        return int(tr["next_event"])

    def train(self, rounds: int,
              hooks: Optional[TrainHooks] = None) -> TrainReport:
        """Drain ``run`` into a ``TrainReport`` (the historical API)."""
        losses, accs = [], []
        last = None
        for ev in self.run(rounds, hooks):
            losses.append(ev.loss)
            if ev.accuracy is not None:
                accs.append((ev.virtual_clock, ev.accuracy))
            last = ev
        plan = self.last_plan
        return TrainReport(
            plan.strategy, len(losses), losses, accs,
            last.virtual_clock if last else 0.0,
            last.sync_wait if last else 0.0,
            last.comm_bytes if last else 0,
            self.dataset.totals,
            last.params if last is not None else self.params0,
            backend=plan.backend, fallback=plan.fallback,
            last_event=last.round + 1 if last is not None else 0)
