"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload case7.sgwu --seed 1234 --seconds 20 --trace 0

Run from the root of a checkout.  ``--trace 0`` measures the cell's
end-to-end metrics over ``--seconds`` of merges; ``--trace 1`` traces a
short window and reports its per-layer metrics.  Either way the last
line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and ``checks``: each number that
decided ``correct`` beside its limit), and the last lines of stderr
repeat the checks.  Without the TPU chips the cell asks for, or without
the program beside it, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    harness.pin_environment()
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.devices_for(cell.chips)
        limits = harness.load_limits(cell.name)
        result = harness.execute(cell, args.seed, args.seconds,
                                 bool(args.trace), T_START, devices, limits)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    result["checks"] = checks                 # the last key of the line
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
