"""The readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload case7.sgwu --seeds 11 12 13 \
        --controls 3 --out chiprun_out/calib_case7.sgwu.jsonl

Run from the root of a checkout, on the chips the cell asks for; the
benchmark's own runs never run it.  For each seed it builds the program
exactly as a run does and drives the same stream through the checked
events (no window), replays them with the plain reference, and prints
one JSON line of gaps (``loss_gap``, ``change_gap``, ``change_dir``,
``change_dir_median``) per reading:

- ``program``: the program against the reference (every seed): the lower
  readings;
- ``control``: the reference computed in bfloat16, one step below the
  configuration's float32, in the program's place (the first
  ``--controls`` seeds);
- ``fault:<name>``: the float32 reference in the program's place with one
  fault planted: ``half_batch`` on every seed, ``labels`` (and
  ``no_exchange`` in a cell whose merge crosses chips) on the first
  ``--controls`` seeds.  A step that leaves the state unchanged reads 1
  by construction and needs no run.

``--cache-floor`` sets JAX's persistent-cache floor in seconds (the
benchmark pins its own); it is there to compare two floors on the same
seeds.
"""
import argparse
import gc
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--cache-floor", default=None)
    args = ap.parse_args(argv)
    import harness
    harness.pin_environment(*([args.cache_floor] if args.cache_floor
                              else []))
    import outer_reference as outer_ref
    cell = harness.load_cell(args.workload)
    harness.devices_for(cell.chips)
    faults = ["half_batch", "labels"] + (["no_exchange"] if cell.chips > 1
                                         else [])
    out = open(args.out, "a") if args.out else None
    refs = {}
    every_seed = ("fault:half_batch",)
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        built = harness.build(cell, seed)
        gen = built.trainer.run(10**9)
        program, nodes = harness.checked_events(cell, built, gen, outer_ref)
        gen.close()
        built.trainer = gen = None
        gc.collect()
        if not refs:        # the held-out set is the same for every seed
            refs["reference"] = harness.reference(cell, built, outer_ref,
                                                  outer_ref.REFERENCE)
            refs["control"] = harness.reference(cell, built, outer_ref,
                                                outer_ref.CONTROL)
            for f in faults:
                refs[f"fault:{f}"] = harness.reference(
                    cell, built, outer_ref, outer_ref.REFERENCE, f)
        t1 = time.perf_counter()
        ref = harness.replay(cell, built, nodes, refs["reference"])
        rows = {"program": outer_ref.gaps(program, ref)}
        t2 = time.perf_counter()
        for kind in [k for k in refs if k != "reference"]:
            if i < args.controls or kind in every_seed:
                rows[kind] = outer_ref.gaps(
                    harness.replay(cell, built, nodes, refs[kind]), ref)
        print(f"[calibrate] seed {seed}: program {t1 - t0:.1f} s, "
              f"reference replay {t2 - t1:.1f} s", file=sys.stderr)
        for kind, g in rows.items():
            line = json.dumps({"workload": cell.name, "seed": seed,
                               "kind": kind, **g})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
