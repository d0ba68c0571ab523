"""The paper's figures and Table 1, one function each (virtual time where
the paper used a 30-node cluster; real JAX training where the paper
measured accuracy).

Fig. 11  accuracy parity (sync vs SGWU vs AGWU, real CNN training)
Fig. 12  execution time vs data size / cluster scale (event-driven sim)
Tab. 1 / Fig. 13  iterations & time to fixed accuracy (real training)
Fig. 14  AGWU/SGWU x IDPA/UDPA strategy grid (sim + real)
Fig. 15  communication volume & workload balance vs cluster size (sim)
Fig. 10  inner-layer task scheduling (Alg. 4.2 scheduler)

Run:  python examples/paper_figures.py
(`pip install -e .` first; bare checkouts can prefix `PYTHONPATH=src`.)

Stdout is one ``name,value,derived`` CSV row per point.  ``value`` is the
point's makespan: virtual microseconds for the cluster figures (in the
training figures the virtual clock is built from measured step times, so
it varies from run to run), the scheduler's cost units for Fig. 10.  It
is not a speed of this machine; speed is measured on the TPU by
``bench/run.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bpt_trainer import BPTTrainer
from repro.core.cluster_sim import ClusterSim, make_heterogeneous_speeds
from repro.core.dag import cnn_training_dag, priority_schedule
from repro.core.types import TrainConfig
from repro.data.pipeline import IDPADataset
from repro.data.synthetic import image_dataset
from repro.models.cnn import CNNConfig, cnn_accuracy, cnn_loss, init_cnn


def emit(name: str, value: float, derived: str):
    print(f"{name},{value:.1f},{derived}")


def cnn_experiment(strategy: str, partitioning: str, *, nodes=3, rounds=6,
                   local_steps=3, n_train=1200, n_eval=300, seed=0,
                   idpa_mode="balanced", lr=2e-3, image_size=16):
    """One BPT-CNN training run on a virtual cluster; returns its
    TrainReport."""
    cfg = CNNConfig(name="paper_figures", image_size=image_size,
                    conv_layers=2, filters=8, fc_layers=2, fc_neurons=64)
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
    return tr.train(rounds=rounds)


def _agwu_sim(n: int, m: int):
    return ClusterSim(n, make_heterogeneous_speeds(m, 0.6),
                      iterations=10, batches=4, strategy="agwu",
                      partitioning="idpa", idpa_mode="balanced").run()


def fig11_accuracy():
    """Accuracy parity: AGWU must match or beat the sync baseline."""
    accs = {}
    for strat in ("sync", "sgwu", "agwu"):
        rep = cnn_experiment(strat, "idpa", rounds=8)
        acc = rep.accuracies[-1][1] if rep.accuracies else float("nan")
        accs[strat] = acc
        emit(f"fig11_accuracy_{strat}", rep.virtual_makespan * 1e6,
             f"final_acc={acc:.3f}")
    emit("fig11_agwu_vs_sync_delta", 0.0,
         f"delta={accs['agwu'] - accs['sync']:+.3f}")


def fig12_exec_time():
    """Virtual makespan vs data size and cluster scale (paper Fig. 12)."""
    for n in (100_000, 300_000, 700_000):
        r = _agwu_sim(n, 10)
        emit(f"fig12a_datasize_{n}", r.makespan * 1e6,
             f"makespan={r.makespan:.1f}")
    for m in (5, 15, 25, 35):
        r = _agwu_sim(300_000, m)
        emit(f"fig12b_cluster_{m}", r.makespan * 1e6,
             f"makespan={r.makespan:.1f} speedup_vs_m5=see_csv")


def tab1_fixed_accuracy(target: float = 0.5):
    """Rounds needed to reach the target accuracy (paper Table 1)."""
    for strat in ("sync", "sgwu", "agwu"):
        rep = cnn_experiment(strat, "idpa", rounds=10)
        hit = next((i + 1 for i, (t, a) in enumerate(rep.accuracies)
                    if a >= target), None)
        emit(f"tab1_rounds_to_{target}_{strat}",
             rep.virtual_makespan * 1e6,
             f"rounds={hit if hit else 'not_reached'}")


def fig14_strategies():
    """AGWU/SGWU x IDPA/UDPA grid — virtual makespan + real accuracy."""
    for strat in ("sgwu", "agwu"):
        for part in ("udpa", "idpa"):
            rep = cnn_experiment(strat, part, rounds=5)
            acc = rep.accuracies[-1][1] if rep.accuracies else float("nan")
            emit(f"fig14_{strat}_{part}", rep.virtual_makespan * 1e6,
                 f"acc={acc:.3f};sync_wait={rep.sync_wait:.2f}")


def fig15_comm_balance():
    """Communication volume and workload balance vs cluster size."""
    for m in (5, 15, 25, 35):
        r = _agwu_sim(600_000, m)
        emit(f"fig15_m{m}", r.makespan * 1e6,
             f"comm_MB={r.comm_bytes/2**20:.3f};balance={r.balance_degree:.3f}")


def fig10_inner_scheduling():
    """Alg. 4.2 thread scheduling of the CNN task DAG."""
    dag = cnn_training_dag([
        {"kind": "conv", "hx": 32, "wx": 32, "hf": 3, "wf": 3, "depth": 3},
        {"kind": "pool", "hx": 32, "wx": 32, "k": 2},
        {"kind": "conv", "hx": 16, "wx": 16, "hf": 3, "wf": 3, "depth": 8},
        {"kind": "fc", "in": 2048, "out": 500},
    ], tile=4)
    serial = priority_schedule(dag, 1).makespan
    for threads in (2, 4, 8, 16):
        r = priority_schedule(dag, threads)
        emit(f"fig10_threads_{threads}", r.makespan,
             f"speedup={serial / r.makespan:.2f};balance="
             f"{r.balance_degree:.3f};waiting={r.waiting_time:.1f}")


def main():
    print("name,value,derived")
    fig11_accuracy()
    fig12_exec_time()
    tab1_fixed_accuracy()
    fig14_strategies()
    fig15_comm_balance()
    fig10_inner_scheduling()


if __name__ == "__main__":
    main()
