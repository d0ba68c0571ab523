"""Plain reference of the BPT outer layer over a family's plain model.

It replays the first events of a run from the batches that the run drew
and the order in which its nodes pushed, and computes what the paper's
algorithm gives for them, independent of the program:

- each node's local iteration: ``local_steps`` AdamW steps (optionally
  after clipping the gradient's global norm) at the warm-up/cosine rate
  of the node's round index; the node's loss is that of its last step;
- Q: the node's accuracy on the held-out set, floored at 1e-3 (1 for
  every node where the traffic gives the program no evaluation);
- SGWU (Eq. 7): W = sum_j Q_j W_j / sum_k Q_k over all nodes, each node
  starting the round from W and keeping its own AdamW state;
- AGWU (Eq. 9-10): W += gamma_j(k) * Q_eff * (W_j - W(k)), with gamma
  from the base versions of every node's last pull and Q_eff the pushed
  Q over its running mean (0.9 / 0.1), clipped to [0.25, 2]; the pushing
  node then pulls W again.

``mode`` picks the arithmetic: ``REFERENCE`` is float32 at ``HIGHEST``
matmul precision; ``CONTROL`` is bfloat16 throughout (weights, AdamW
state, the update and the merge) at ``DEFAULT``.  ``fault`` plants
one of the faults the benchmark must catch into the replay, so the
replay can stand in for a broken program: ``"half_batch"`` (the loss is
the mean over the first half of each batch), ``"labels"`` (every label
shifted by one class, as a feed that altered the answers it hands over
would give), ``"no_exchange"`` (the Eq. 7 merge keeps node 0's weighted
share, as an all-reduce that never summed would).

AdamW is the one the configurations state and the program's
``make_optimizer("adamw")`` runs: ``ADAMW`` below.

A replay returns ``Readings``: per-event node losses, the global
weights' change after the last replayed event (one float32 array per
leaf, on the host), and the per-node per-leaf norms of the reference's
own AdamW first moment after the first event, by which leaves whose
gradient is nought to rounding are left out of the comparison.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

REFERENCE = {"dtype": jnp.float32, "precision": jax.lax.Precision.HIGHEST}
CONTROL = {"dtype": jnp.bfloat16, "precision": jax.lax.Precision.DEFAULT}
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.01}


@dataclasses.dataclass
class Readings:
    losses: list            # [event] -> np.ndarray of node losses
    change: list            # per leaf: float32 W_last - W_0 on the host
    grad_norms: np.ndarray | None = None  # (nodes, leaves) reference mu


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _change(w, w0):
    return [a.astype(jnp.float32) - b.astype(jnp.float32)
            for a, b in zip(jax.tree_util.tree_leaves(w),
                            jax.tree_util.tree_leaves(w0), strict=True)]


def leaf_norms(tree) -> np.ndarray:
    """Per-leaf L2 norms of a pytree, as float64 on the host."""
    return np.asarray(jax.device_get(_norms(tree)), np.float64)


def change(w, w0) -> list:
    """``w - w0`` leaf by leaf, in float32, on the host."""
    return [np.asarray(x) for x in jax.device_get(_change(w, w0))]


@jax.jit
def _weighted_sum(coeffs, trees):
    """Eq. 7: sum_j coeffs[j] * trees[j], leaf by leaf, in the trees' type."""
    return jax.tree_util.tree_map(
        lambda *xs: sum(coeffs[i] * x for i, x in enumerate(xs)
                        ).astype(xs[0].dtype), *trees)


@jax.jit
def _push(scale, w, local, base):
    """Eq. 10: W + scale * (W_j - W(k)), in W's type."""
    return jax.tree_util.tree_map(
        lambda g, lw, b: (g + scale * (lw - b)).astype(g.dtype),
        w, local, base)


def lr_at(traffic: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to a tenth of the base rate."""
    base = np.float32(traffic["learning_rate"])
    warm, total = traffic["warmup_steps"], traffic["total_steps"]
    s = np.float32(step)
    if s < warm:
        return float(base * s / np.float32(max(warm, 1)))
    frac = np.clip((s - np.float32(warm)) / np.float32(max(total - warm, 1)),
                   0.0, 1.0)
    return float(base * (np.float32(0.1) + np.float32(0.9) * np.float32(0.5)
                         * (1 + np.cos(np.float32(np.pi) * frac))))


class OuterReference:
    """Replays SGWU rounds or AGWU pushes for one family configuration."""

    def __init__(self, family_ref, cfg: dict, traffic: dict, mode: dict,
                 eval_images, eval_labels, fault: str = ""):
        self.cfg, self.traffic, self.mode, self.fault = cfg, traffic, mode, fault
        self.dtype = mode["dtype"]
        ad = ADAMW
        clip = float(traffic["grad_clip"])
        frozen = tuple(sorted((k, v) for k, v in cfg.items()
                              if isinstance(v, (int, float, str))))
        ref = family_ref

        def loss(p, images, labels):
            if fault == "half_batch":
                half = images.shape[0] // 2
                images, labels = images[:half], labels[:half]
            elif fault == "labels":
                labels = (labels + 1) % cfg["num_classes"]
            return ref.loss(p, images, labels, dict(frozen), **mode)

        def step(params, opt, images, labels, lr):
            value, g = jax.value_and_grad(loss)(params, images, labels)
            if clip:
                norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                    for x in jax.tree_util.tree_leaves(g)))
                scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
                g = jax.tree_util.tree_map(lambda x: x * scale, g)
            count = opt["count"] + 1
            dt = self.dtype
            mu = jax.tree_util.tree_map(
                lambda m, x: (ad["b1"] * m + (1 - ad["b1"]) * x).astype(dt),
                opt["mu"], g)
            nu = jax.tree_util.tree_map(
                lambda n, x: (ad["b2"] * n + (1 - ad["b2"]) * x * x
                              ).astype(dt), opt["nu"], g)
            c1 = (1 - ad["b1"] ** count.astype(jnp.float32)).astype(dt)
            c2 = (1 - ad["b2"] ** count.astype(jnp.float32)).astype(dt)
            params = jax.tree_util.tree_map(
                lambda p, m, n: (p - lr.astype(dt) * (
                    (m / c1) / (jnp.sqrt(n / c2) + ad["eps"])
                    + ad["weight_decay"] * p)).astype(dt),
                params, mu, nu)
            return params, {"mu": mu, "nu": nu, "count": count}, value

        self._step = jax.jit(step)
        self._acc = jax.jit(lambda p: ref.accuracy(
            p, eval_images, eval_labels, dict(frozen), **mode))

        self.cast = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda x: x.astype(self.dtype), tree))

    @staticmethod
    @jax.jit
    def init_opt(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return {"mu": zeros, "nu": jax.tree_util.tree_map(jnp.zeros_like,
                                                          params),
                "count": jnp.zeros((), jnp.int32)}

    def _local(self, params, opt, images, labels, round_index: int):
        """One node's local iteration over its (steps, B, ...) batches."""
        lr = jnp.float32(lr_at(self.traffic, round_index))
        value = None
        for s in range(images.shape[0]):
            params, opt, value = self._step(params, opt, images[s],
                                            labels[s], lr)
        return params, opt, float(value)

    def _q(self, params) -> float:
        if not self.traffic["use_eval"]:
            return 1.0
        return max(float(self._acc(params)), 1e-3)

    def sgwu(self, params0, rounds: list) -> Readings:
        """``rounds``: per round {"images": (m, h, B, ...), "labels": ...}."""
        m = rounds[0]["images"].shape[0]
        w = self.cast(params0)
        opts = [self.init_opt(w) for _ in range(m)]
        losses, grad = [], None
        for r, batch in enumerate(rounds):
            locals_, qs, node_losses = [], [], []
            for j in range(m):
                p, opts[j], value = self._local(
                    w, opts[j], jnp.asarray(batch["images"][j]),
                    jnp.asarray(batch["labels"][j]), r)
                locals_.append(p)
                qs.append(self._q(p))
                node_losses.append(value)
            losses.append(np.asarray(node_losses))
            if r == 0:
                grad = np.stack([leaf_norms(o["mu"]) for o in opts])
            q = np.asarray(qs, np.float32)
            coeffs = (q / q.sum()).astype(np.float32)
            if self.fault == "no_exchange":      # node 0's share, unsummed
                coeffs[1:] = 0.0
            w = _weighted_sum(jnp.asarray(coeffs), locals_)
        return Readings(losses, change(w, params0), grad)

    def agwu(self, params0, pushes: list, nodes: int) -> Readings:
        """``pushes``: per push (node, {"images": (h, B, ...), ...})."""
        w, version = self.cast(params0), 0
        local = {j: w for j in range(nodes)}
        base = {j: (w, 0) for j in range(nodes)}
        opts = {j: self.init_opt(w) for j in range(nodes)}
        done = np.zeros(nodes, np.int64)
        q_ema = None
        losses, grad = [], None
        for i, (j, batch) in enumerate(pushes):
            p, opts[j], value = self._local(
                local[j], opts[j], jnp.asarray(batch["images"]),
                jnp.asarray(batch["labels"]), int(done[j]))
            losses.append(np.asarray([value]))
            if i == 0:
                grad = leaf_norms(opts[j]["mu"])[None, :]
            q = self._q(p)
            q_ema = q if q_ema is None else 0.9 * q_ema + 0.1 * q
            q_eff = float(np.clip(q / max(q_ema, 1e-3), 0.25, 2.0))
            k = base[j][1]
            latest = max(version, 1)
            versions = [base[o][1] for o in range(nodes) if o != j] + [k]
            gamma = math.exp(k / latest) / sum(math.exp(v / latest)
                                               for v in versions)
            w = _push(jnp.float32(gamma * q_eff), w, p, base[j][0])
            version += 1
            done[j] += 1
            base[j] = (w, version)
            local[j] = w
        return Readings(losses, change(w, params0), grad)


def gaps(program: Readings, reference: Readings) -> dict:
    """The numbers compared, each the worst over events, nodes or leaves.

    - ``loss_gap``: relative gap of each node loss of each event;
    - ``change_gap``: gap between the program's and the reference's norm
      of the global weights' change after the last replayed event, per
      leaf, over the larger of that leaf's reference norm and the median
      leaf's;
    - ``change_dir``: 1 - cosine between the program's and the
      reference's change, per leaf (1 where either moved nothing), so a
      change of the right size in the wrong direction shows.

    ``change_dir_median`` (the median leaf's) is printed, not compared.
    Leaves whose reference first moment after the first event is under a
    thousandth of the median leaf's (gradient nought to rounding) are
    left out of the change numbers."""
    loss = max(float(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))
               for p, r in zip(program.losses, reference.losses, strict=True))
    g_ref = reference.grad_norms
    med = np.median(g_ref, axis=1, keepdims=True)
    keep = (g_ref >= 1e-3 * med).all(axis=0)
    norm_p, norm_r, cos = [], [], []
    for a, b in zip(program.change, reference.change, strict=True):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
        norm_p.append(na)
        norm_r.append(nb)
        cos.append(float(a @ b) / (na * nb) if na and nb else 0.0)
    norm_p, norm_r = np.asarray(norm_p), np.asarray(norm_r)
    c_med = np.median(norm_r[keep])
    size = np.abs(norm_p - norm_r) / np.maximum(norm_r, c_med)
    direction = 1.0 - np.asarray(cos)
    return {"loss_gap": loss,
            "change_gap": float(np.max(size[keep])),
            "change_dir": float(np.max(direction[keep])),
            "change_dir_median": float(np.median(direction[keep]))}
