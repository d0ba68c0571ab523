"""The benchmark's driver of the program: one cell, one seed, one run.

A run builds the program's ``BPTTrainer`` for the cell from the seed,
opens ``BPTTrainer.run`` and drives that one stream through three parts:

1. checked events: the first ``checked_events`` merges, on which the
   program's own readings are taken (node losses and the global weights'
   change);
2. warm events: more merges, so every program the window runs is
   compiled before it opens;
3. the window: merges for ``--seconds`` (``--trace 0``), or
   ``trace_events`` merges under the profiler (``--trace 1``).

Set-up is everything from process start to the window's first merge.
Once the window has closed, the device memory peak is read, the program
is freed, and the plain reference replays the checked events from the
batches the run drew and the order in which its nodes pushed; the gaps
between the two decide ``correct`` against the cell's limits.

Everything particular to a configuration, a traffic mix or a metric is a
file found by name: ``configs/<config>.json`` (with its family's reference
beside it), ``families/<family>.py``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from generator import check_traffic, image_dataset  # noqa: E402
EVAL_PROGRAM = "jit_bench_eval_q"
# the numbers that decide ``correct`` (PERF.md, "How correct is decided"),
# and those printed beside them but not compared
CHECKS = ("loss_gap", "change_gap")
UNCOMPARED = ("change_dir", "change_dir_median")
# JAX's persistent-cache floor in seconds, pinned: runs read sound at
# JAX's default of 1 s, not yet at 0 (PERF.md, section 7)
CACHE_MIN_COMPILE_SECS = "1.0"


class Refused(Exception):
    """The run cannot measure this cell (no chip, wrong engine, ...)."""


@functools.lru_cache(maxsize=None)
def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list        # end-to-end metric names
    per_layer: dict         # per-layer metric name -> unit

    @property
    def family(self):
        return load_module(os.path.join(BENCH, "families",
                                        f"{self.cfg['family']}.py"),
                           f"bench_family_{self.cfg['family']}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    return Cell(workload, int(w["chips"]), cfg, load_traffic(w["traffic"]),
                [m["name"] for m in manifest["end_to_end"]
                 if _applies(m, workload)],
                {m["name"]: m["unit"] for m in manifest["per_layer"]
                 if _applies(m, workload)})


def load_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return check_traffic(json.load(f))


def load_limits(workload: str) -> dict:
    with open(os.path.join(BENCH, "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


def load_metric(name: str):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       f"bench_metric_{name}")


def pin_environment(cache_floor: str = CACHE_MIN_COMPILE_SECS) -> None:
    """Before JAX is imported: the compile cache at the fixed path
    ``<checkout>/.jax_cache`` with its floor pinned, the program's kernels
    compiled, and the program importable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = cache_floor
    os.environ["REPRO_KERNEL_IMPL"] = "pallas"
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def devices_for(chips: int):
    """The first ``chips`` TPU devices; ``Refused`` when JAX has fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise Refused(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                      f"{devs[0].platform} device(s)")
    return devs[:chips]


# ----------------------------------------------------------------------
# the feed: the program's data pipeline, with its draws recorded
# ----------------------------------------------------------------------
class Feed:
    """Wraps an ``IDPADataset``'s two batch draws so the benchmark keeps
    what the program trained on while ``recording`` is on, and marks each
    draw with a ``bench.draw`` span for the trace."""

    def __init__(self, dataset, corrupt=None):
        import jax
        self.recording = True
        self.rounds, self.node_draws = [], []
        stacked, single = dataset.stacked_round_batches, dataset.node_batch
        annotate = jax.profiler.TraceAnnotation

        def stacked_round_batches(*a, **kw):
            with annotate("bench.draw"):
                out = stacked(*a, **kw)
            if self.recording:
                self.rounds.append(out)
            return corrupt(out) if corrupt else out

        def node_batch(node, *a, **kw):
            with annotate("bench.draw"):
                out = single(node, *a, **kw)
            if self.recording:
                self.node_draws.append((node, out))
            return corrupt(out) if corrupt else out

        dataset.stacked_round_batches = stacked_round_batches
        dataset.node_batch = node_batch


def make_data(cfg: dict, traffic: dict, seed: int):
    kw = dict(size=cfg["image_size"], channels=cfg["in_channels"],
              num_classes=cfg["num_classes"])
    xs, ys = image_dataset(traffic["n_train"], seed=seed, **kw)
    xe, ye = image_dataset(traffic["n_eval"], seed=traffic["eval_seed"], **kw)
    return xs, ys, xe, ye


@dataclasses.dataclass
class Built:
    trainer: object
    feed: Feed
    params0: object         # the starting weights, copied to the host
    eval_images: np.ndarray
    eval_labels: np.ndarray
    images_per_event: int


def build(cell: Cell, seed: int, *, loss_wrap=None, corrupt=None) -> Built:
    """The program under test for this cell and seed: weights made on the
    device from the seed, data from the traffic generator, and a
    ``BPTTrainer`` on the traffic's engine.  ``loss_wrap`` and ``corrupt``
    plant faults (tests only)."""
    import jax
    import jax.numpy as jnp
    from repro.core.bpt_trainer import BPTTrainer
    from repro.core.engine import engine_config
    from repro.core.types import TrainConfig
    from repro.data.pipeline import IDPADataset
    cfg, tr = cell.cfg, cell.traffic
    fam = cell.family
    ref = fam.reference()
    params0 = ref.init_params(seed, cfg)
    # the program may donate its starting weights; the reference and the
    # change are taken from this copy
    params0_host = jax.device_get(params0)
    xs, ys, xe, ye = make_data(cfg, tr, seed)
    loss_fn, accuracy = fam.program(cfg)
    if loss_wrap is not None:
        loss_fn = loss_wrap(loss_fn)
    eval_fn = None
    if tr["use_eval"]:
        held_out = jax.device_put({"images": jnp.asarray(xe),
                                   "labels": jnp.asarray(ye)})

        def bench_eval_q(params):
            return accuracy(params, held_out)
        eval_fn = jax.jit(bench_eval_q)
    speeds = np.asarray(tr["speed_factors"], np.float64)
    ds = IDPADataset({"images": xs, "labels": ys}, num_nodes=tr["nodes"],
                     batches=tr["allocation_batches"],
                     frequencies=1.0 / speeds, idpa_mode=tr["idpa_mode"])
    feed = Feed(ds, corrupt)
    extra = {"mesh_name": tr["mesh"]} if tr["mesh"] else {}
    tc = TrainConfig(**engine_config(
        tr["engine"], outer_nodes=tr["nodes"], optimizer=tr["optimizer"],
        learning_rate=tr["learning_rate"], warmup_steps=tr["warmup_steps"],
        total_steps=tr["total_steps"], grad_clip=tr["grad_clip"],
        local_steps=tr["local_steps"], allocation_batches=tr[
            "allocation_batches"], seed=seed, **extra))
    trainer = BPTTrainer(loss_fn, params0, ds, tc, batch_size=tr["batch"],
                         eval_fn=eval_fn, speed_factors=speeds)
    per_event = tr["local_steps"] * tr["batch"]
    if tr["strategy"] == "sgwu":
        per_event *= tr["nodes"]
    return Built(trainer, feed, params0_host, xe, ye, per_event)


# ----------------------------------------------------------------------
# compile events (a copy of the program's jax.monitoring listener)
# ----------------------------------------------------------------------
class CompileCounter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == self._BACKEND:
            self.count += 1


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------
def checked_events(cell: Cell, built: Built, gen, outer_ref):
    """Drive the first events through the window's own stream and take
    the program's readings of them: the node losses, and the global
    weights' change (copied before the next merge may donate them)."""
    losses, nodes, last = [], [], None
    for _ in range(cell.traffic["checked_events"]):
        last = next(gen)
        losses.append(np.asarray(last.node_losses, np.float64))
        nodes.append(int(last.node))
    change = outer_ref.change(last.params, built.params0)
    built.feed.recording = False
    return outer_ref.Readings(losses, change), nodes


def reference(cell: Cell, built: Built, outer_ref, mode, fault: str = ""):
    """The plain reference of the cell, in ``mode``, with ``fault``."""
    return outer_ref.OuterReference(cell.family.reference(), cell.cfg,
                                    cell.traffic, mode, built.eval_images,
                                    built.eval_labels, fault)


def replay(cell: Cell, built: Built, nodes: list, ref):
    """``ref``'s readings of the checked events (the batches the run drew,
    in the order its nodes pushed)."""
    tr = cell.traffic
    if tr["strategy"] == "sgwu":
        return ref.sgwu(built.params0, built.feed.rounds)
    h = tr["local_steps"]
    draws = built.feed.node_draws
    pushes = []
    for i, j in enumerate(nodes):
        steps = draws[i * h:(i + 1) * h]
        if any(n != j for n, _ in steps):
            raise Refused(f"push {i} of node {j} drew for nodes "
                          f"{[n for n, _ in steps]}")
        pushes.append((j, {key: np.stack([b[key] for _, b in steps])
                           for key in steps[0][1]}))
    return ref.agwu(built.params0, pushes, tr["nodes"])


def _window(gen, seconds: float):
    from time import perf_counter
    stamps = [perf_counter()]
    while stamps[-1] - stamps[0] < seconds:
        next(gen)
        stamps.append(perf_counter())
    return stamps


def _traced_window(gen, n: int, path: str):
    import jax
    from time import perf_counter
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(path, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = perf_counter()
            for _ in range(n):
                with jax.profiler.TraceAnnotation("bench.event"):
                    next(gen)
            t1 = perf_counter()
    found = []
    for dirpath, _, files in os.walk(path):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise Refused(f"expected one trace file under {path}, found {found}")
    return found[0], t1 - t0


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start,
            devices, limits: dict, **faults) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import outer_reference as outer_ref
    from repro.kernels import ops
    ops.clear_fallback_log()
    compiles = CompileCounter()
    marks = [("start", t_start), ("import", time.perf_counter())]
    built = build(cell, seed, **faults)
    marks.append(("build", time.perf_counter()))
    gen = built.trainer.run(10**9)
    program, nodes = checked_events(cell, built, gen, outer_ref)
    marks.append(("checked", time.perf_counter()))
    plan = built.trainer.last_plan
    if plan.backend != cell.traffic["engine"] or plan.fallback:
        raise Refused(f"asked for engine {cell.traffic['engine']}, ran "
                      f"{plan.backend} ({plan.fallback})")
    for _ in range(cell.traffic["warm_events"]):
        next(gen)
    marks.append(("warm", time.perf_counter()))
    print("[bench] set-up seconds: " + ", ".join(
        f"{name} {t - prev!r}" for (_, prev), (name, t)
        in zip(marks, marks[1:])) + f"; {compiles.count} compiles",
        file=sys.stderr)
    before = compiles.count
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    if trace:
        path = os.path.join(ROOT, ".bench_trace")
        setup_s = time.perf_counter() - t_start
        xplane, window_s = _traced_window(gen, cell.traffic["trace_events"],
                                          path)
        events = cell.traffic["trace_events"]
    else:
        stamps = _window(gen, seconds)
        setup_s = stamps[0] - t_start
        window_s = stamps[-1] - stamps[0]
        events = len(stamps) - 1
        merge_ms = np.diff(stamps) * 1e3
    window_compiles = compiles.count - before
    result["attempted"] = events
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    gen.close()
    if ops.fallback_events():
        raise Refused(f"kernel fallbacks: {ops.fallback_events()}")
    trainer, built.trainer, gen = built.trainer, None, None
    del trainer
    gc.collect()

    print(f"[bench] {cell.name} seed {seed}: {events} merges in "
          f"{window_s!r} s after {setup_s!r} s of set-up; "
          f"{window_compiles} compiles in the window", file=sys.stderr)
    if trace:
        result["metrics"], result["breakdown"], dev = _trace_metrics(
            cell, xplane, events * built.images_per_event, events, window_s,
            devices[0].device_kind)
        shutil.rmtree(os.path.join(ROOT, ".bench_trace"), ignore_errors=True)
    else:
        print(f"[bench] merge_ms over {len(merge_ms)} merges: median "
              f"{float(np.median(merge_ms))!r}, p95 "
              f"{float(np.percentile(merge_ms, 95))!r}", file=sys.stderr)
        values = {
            "images_per_s": (events * built.images_per_event / window_s,
                             "images/s"),
            "merge_ms_p95": (float(np.percentile(merge_ms, 95)), "ms"),
            "setup_s": (setup_s, "s"),
        }
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in values.items()
                             if k in cell.end_to_end}
        dev = {}
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak,
                        **dev}

    t_ref = time.perf_counter()
    readings = replay(cell, built, nodes,
                      reference(cell, built, outer_ref, outer_ref.REFERENCE))
    gaps = outer_ref.gaps(program, readings)
    print(f"[bench] reference replay of {len(nodes)} merges: "
          f"{time.perf_counter() - t_ref!r} s", file=sys.stderr)
    for k in UNCOMPARED:
        print(f"[bench] {k} (not compared) {gaps[k]!r}", file=sys.stderr)
    checks = {k: {"value": gaps[k], "limit": limits[k]} for k in CHECKS}
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def _trace_metrics(cell: Cell, xplane: str, images: int, events: int,
                   window_s: float, kind: str):
    """(per-layer metrics, breakdown, device busy and window seconds) of
    one traced window on devices of ``kind``."""
    import devtrace
    tr = devtrace.Trace(devtrace.load(xplane))
    chips = tr.chips()
    if not chips:
        raise Refused("the trace holds no TPU device plane")
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    ctx = {"trace": tr, "cfg": cell.cfg, "traffic": cell.traffic,
           "family": cell.family, "peak": peaks[kind], "chips": len(chips),
           "window_images": images, "window_events": events,
           "window_s": window_s, "eval_program": EVAL_PROGRAM}
    metrics = {}
    for name in cell.per_layer:
        value = load_metric(name).read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": cell.per_layer[name]}
    busy = sum(tr.busy_ns(c) for c in chips) / len(chips) / 1e9
    breakdown = {"device_ops": tr.top_ops(chips[0]),
                 "idle_gaps": tr.idle_gaps(chips[0])}
    return metrics, breakdown, {"busy_s": busy,
                                "window_s": tr.window_ns / 1e9}

