"""Serving CLI: thin front-end over ``repro.serving``.

Replays a Poisson request stream through the resolved serve engine and
prints per-request latency plus aggregate throughput — the CPU-scale
twin of the decode path the dry-run lowers at production shapes:

    python -m repro.launch.serve --arch mamba2-370m \
        --requests 16 --rate 50 --slots 4

(``pip install -e .`` first; bare checkouts can prefix ``PYTHONPATH=src``.)
"""
from __future__ import annotations

import argparse
import warnings

import jax
import numpy as np

from repro import configs
from repro.launch import runtime
from repro.serving import ServeConfig, make_serve_engine, poisson_requests


def greedy_generate(params, cfg, prompts, max_seq: int, gen: int):
    """DEPRECATED shim over ``ServeEngine.generate`` — same contract as
    the old token-by-token loop: prompts (B, P) int32 → (B, gen) ids."""
    warnings.warn(
        "launch.serve.greedy_generate is deprecated; use "
        "repro.serving.make_serve_engine(...).generate(prompts, gen)",
        DeprecationWarning, stacklevel=2)
    B = prompts.shape[0]
    eng = make_serve_engine(params, cfg, ServeConfig(
        slots=B, max_seq=max_seq, max_new_tokens=gen))
    return eng.generate(prompts, gen)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-370m",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (requests/sec)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batching", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--timing", default="measured",
                    choices=["measured", "model"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # persistent XLA cache: repeat serve runs skip the prefill/decode
    # compiles (JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache)
    print(f"[serve] compilation cache: "
          f"{runtime.maybe_enable_compilation_cache()}")

    cfg = configs.get_reduced(args.arch)
    # params and prompt stream draw from SPLIT keys (the old demo reused
    # one key for both, correlating weights with the prompt ids)
    key_params, key_prompts = jax.random.split(jax.random.PRNGKey(args.seed))
    from repro.models import lm
    params = lm.init_params(key_params, cfg)
    prompt_seed = int(jax.random.randint(key_prompts, (), 0, 2**31 - 1))

    eng = make_serve_engine(params, cfg, ServeConfig(
        slots=args.slots, max_seq=args.max_seq, max_new_tokens=args.gen,
        batching=args.batching, timing=args.timing))
    reqs = poisson_requests(args.requests, args.rate, seed=prompt_seed,
                            vocab_size=cfg.vocab_size)

    lat, toks = {}, 0
    for ev in eng.run(reqs):
        if ev.kind == "prefill":
            print(f"[serve] req {ev.request:3d} slot {ev.slot} "
                  f"prefill {ev.prefill_ms:7.2f} ms  ttft {ev.ttft_ms:7.2f} ms")
        elif ev.kind == "complete":
            lat[ev.request] = ev.latency_ms
            toks += len(ev.tokens)
            print(f"[serve] req {ev.request:3d} done  t={ev.t_ms:8.1f} ms  "
                  f"latency {ev.latency_ms:7.1f} ms  "
                  f"tokens {np.asarray(ev.tokens)[:8]}...")
            makespan = ev.t_ms
    ls = np.asarray(sorted(lat.values()))
    print(f"[serve] {cfg.name} {eng.batching}: {len(lat)} requests, "
          f"{toks} tokens in {makespan:.1f} ms "
          f"({toks / makespan * 1e3:.1f} tok/s) | latency "
          f"p50 {np.percentile(ls, 50):.1f} ms "
          f"p99 {np.percentile(ls, 99):.1f} ms")
    assert len(lat) == args.requests
    return lat


if __name__ == "__main__":
    main()
