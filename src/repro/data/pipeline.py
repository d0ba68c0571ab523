"""Data pipeline: sequence packing, sharded host loading, IDPA partitioning.

``IDPADataset`` glues the paper's partitioner (core/idpa.py) to an actual
dataset: each virtual computing node (data-parallel group) owns the sample
stripe the partitioner assigned it, re-partitioned incrementally as measured
throughputs arrive — the production analogue of Alg. 3.1 where the "main
server" is the input pipeline.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.idpa import IDPAPartitioner, UDPAPartitioner
from repro.sanitize import span

__all__ = ["pack_sequences", "IDPADataset", "host_batch"]


def pack_sequences(corpus: np.ndarray, seq_len: int) -> np.ndarray:
    """Pack a token stream into (N, seq_len+1) rows (inputs+shifted labels)."""
    if seq_len < 1:
        raise ValueError(f"seq_len must be >= 1, got {seq_len}")
    if len(corpus) < seq_len + 1:
        raise ValueError(
            f"corpus of {len(corpus)} tokens is too short to pack even one "
            f"row: need at least seq_len + 1 = {seq_len + 1} tokens")
    n = (len(corpus) - 1) // seq_len
    rows = np.stack([corpus[i * seq_len:(i + 1) * seq_len + 1]
                     for i in range(n)])
    return rows.astype(np.int32)


def host_batch(rows: np.ndarray):
    """(B, S+1) rows -> {'tokens': (B,S), 'labels': (B,S)}."""
    return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


@partial(jax.jit, static_argnums=2)
def _gather_rows(rows: dict, idx, shapes: tuple):
    """Each leaf's rows at ``idx``, shaped ``idx.shape + shapes[k]``
    (``shapes``: the ``(name, sample shape)`` pairs of the leaves)."""
    return {k: rows[k][idx].reshape(idx.shape + shape) for k, shape in shapes}


class IDPADataset:
    """Per-node dataset views driven by the IDPA/UDPA partitioner.

    Usage:
        ds = IDPADataset(data_arrays, num_nodes=4, batches=4,
                         frequencies=[...])
        for epoch_round in range(...):
            views = ds.node_views()          # list of per-node index arrays
            ...train...
            ds.report_durations(durations)   # feeds Alg. 3.1
    """

    def __init__(self, arrays: dict, num_nodes: int, batches: int,
                 frequencies: Optional[Sequence[float]] = None,
                 partitioning: str = "idpa", idpa_mode: str = "paper"):
        self.arrays = arrays
        self.n = len(next(iter(arrays.values())))
        if partitioning == "idpa":
            if frequencies is None:
                frequencies = np.ones(num_nodes)
            self.part = IDPAPartitioner(self.n, num_nodes, batches,
                                        frequencies=frequencies,
                                        mode=idpa_mode)
        else:
            self.part = UDPAPartitioner(self.n, num_nodes, batches)
        self.part.first_batch()

    @property
    def totals(self) -> np.ndarray:
        return self.part.totals

    @property
    def nbytes(self) -> int:
        """The host training arrays' total size."""
        return sum(v.nbytes for v in self.arrays.values())

    @property
    def _shapes(self) -> tuple:
        return tuple((k, v.shape[1:]) for k, v in self.arrays.items())

    def _rows(self) -> dict:
        """The training arrays with each sample one row: a leaf of more
        than two dimensions is viewed as ``(n, features)``.  On the TPU a
        gather of a ``(n, H, W, 3)`` set relays the whole set out on
        every call; one of rows reads only the rows it takes, where XLA
        need not slice a long row (``gather_temp_bytes`` says when)."""
        return {k: v.reshape(len(v), -1) if v.ndim > 2 else v
                for k, v in self.arrays.items()}

    def to_device(self) -> dict:
        """The training arrays as rows (``_rows``) on the default device,
        placed once as the span ``bpt.place``; the host arrays stay as
        they are.  Given to ``stacked_round_batches`` as ``resident``, the
        round's batch is gathered there.  The allocation never moves a
        sample between nodes (paper §3.3.1), so the stripes stay where
        the nodes compute."""
        with span("place", bytes=self.nbytes):
            return jax.device_put(self._rows())

    def gather_temp_bytes(self, round_shape: tuple, device) -> int:
        """The device temporaries of the resident gather of a round's
        ``round_shape`` indices on ``device``, from its compiled memory
        analysis (nothing is placed or run)."""
        on = jax.sharding.SingleDeviceSharding(device)
        rows = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=on)
                for k, v in self._rows().items()}
        idx = jax.ShapeDtypeStruct(round_shape, np.int32, sharding=on)
        compiled = _gather_rows.lower(rows, idx, self._shapes).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def report_durations(self, durations, active=None) -> bool:
        """Feed measured per-node durations; returns True if re-allocated.

        ``active`` masks failed nodes out of the next allocation batch
        (node churn): a dead node keeps its existing stripe but receives
        nothing new until it rejoins.
        """
        if self.part.done:
            return False
        with span("idpa"):
            if isinstance(self.part, IDPAPartitioner):
                self.part.next_batch(durations, active=active)
            else:
                self.part.next_batch(None, active=active)
        return True

    # -- crash-safe checkpointing: the partitioner's incremental state ---
    def state_dict(self) -> dict:
        return self.part.state_dict()

    def load_state_dict(self, state: dict) -> None:
        self.part.load_state_dict(state)

    def node_views(self) -> list[np.ndarray]:
        """Contiguous index stripes per node (no migration — paper §3.3.1)."""
        totals = self.part.totals
        starts = np.concatenate([[0], np.cumsum(totals)[:-1]])
        return [np.arange(starts[j], starts[j] + totals[j]) % self.n
                for j in range(len(totals))]

    @staticmethod
    def _select(view: np.ndarray, node: int, batch_size: int,
                rng: np.random.Generator) -> np.ndarray:
        """Sample indices from one node's stripe — the ONE sampling rule
        both the sequential and the stacked batch paths share, so their
        numerical equivalence holds by construction."""
        take = min(batch_size, len(view))
        if take == 0:
            raise ValueError(f"node {node} has no samples allocated yet")
        return rng.choice(view, size=batch_size, replace=take < batch_size)

    def node_batch(self, node: int, batch_size: int, rng: np.random.Generator):
        with span("draw", nodes=1, steps=1, batch=batch_size, resident=0):
            sel = self._select(self.node_views()[node], node, batch_size, rng)
            return {k: v[sel] for k, v in self.arrays.items()}

    @property
    def num_nodes(self) -> int:
        return self.part.num_nodes

    def node_round_batch_sizes(self, batch_size: int) -> np.ndarray:
        """Per-node effective batch sizes ∝ the current IDPA allocation.

        The fastest node (largest stripe) trains on the full
        ``batch_size``; slower nodes get proportionally smaller effective
        loads — the heterogeneity-aware workload the partitioner encodes,
        carried into each round's compute.
        """
        totals = np.maximum(self.totals, 1).astype(np.float64)
        sizes = np.ceil(batch_size * totals / totals.max()).astype(np.int64)
        return np.clip(sizes, 1, batch_size)

    def stacked_round_batches(self, batch_size: int, local_steps: int,
                              rng: np.random.Generator, *,
                              uneven: bool = False,
                              resident: Optional[dict] = None):
        """One SGWU round's data for ALL nodes: ``(m, local_steps, B, ...)``.

        Draws node-by-node, step-by-step — the exact RNG consumption
        order of the sequential per-node loop's ``node_batch`` calls — so
        the fused vmapped round sees bit-identical batches and stays
        numerically equivalent to the legacy path on a fixed seed.  The
        index stripes are built once for the round (the allocation only
        changes between rounds, via ``report_durations``).

        With ``uneven=True`` each node draws only its
        ``node_round_batch_sizes`` share and the stripe is padded back to
        ``batch_size`` (cycling the drawn samples) with a float ``mask``
        leaf of shape ``(m, local_steps, B)`` marking the real rows — the
        static-shape realization of IDPA's per-node loads that the
        fused/device-sharded round needs (the loss must honour
        ``batch["mask"]``).

        Given ``resident``, the set's copy on the device (``to_device``),
        the same indices (and mask) are put on the device explicitly and
        every leaf is gathered there in one call: the batch is
        bit-identical to the host draw and already on the device (the
        span's ``resident`` is 1).
        """
        m = self.num_nodes
        with span("draw", nodes=m, steps=local_steps, batch=batch_size,
                  resident=int(resident is not None)):
            views = self.node_views()
            sizes = self.node_round_batch_sizes(batch_size) if uneven \
                else np.full(m, batch_size, np.int64)
            mask = np.zeros((m, local_steps, batch_size), np.float32)
            sels = []
            for j in range(m):
                node = []
                for s in range(local_steps):
                    sel = self._select(views[j], j, int(sizes[j]), rng)
                    if len(sel) < batch_size:      # pad by cycling; masked out
                        sel = np.resize(sel, batch_size)
                    node.append(sel)
                    mask[j, s, :sizes[j]] = 1.0
                sels.append(node)
            # one gather straight into the (m, local_steps, B, ...) batch:
            # the round's images are copied once, not once per stacking
            idx = np.asarray(sels)
            if resident is not None:
                # the stripes' indices lie in [0, n); past them the host
                # gather raises where the device one would clamp
                if idx.min() < 0 or idx.max() >= self.n:
                    raise IndexError(f"index out of [0, {self.n})")
                # explicit uploads: the round body runs under the transfer
                # guard (REPRO_SANITIZE=1)
                out = _gather_rows(resident, jax.device_put(idx),
                                   self._shapes)
                if uneven:
                    mask = jax.device_put(mask)
            else:
                out = {k: v[idx] for k, v in self.arrays.items()}
            if uneven:
                out["mask"] = mask
            return out
