"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Everything before the last line of standard output is progress
and detail for a reader; the last line is the one JSON object of the
contract (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` in a traced run, and last ``compared``: each number the checks
compared beside its limit, which are also the last lines of standard error). Any error, a platform other than a TPU
and too few chips are a non-zero exit with no such line. No flag and no
variable makes this command run off the chip: the CPU rehearsal calls
:func:`benchmark.lib.runner.run_cell` with the platform as an argument
(``benchmark/tests/``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "benchmark"), help="where the run's detail "
        "goes (a directory inside the checkout)")
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave a traced run's profile in --out")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        from benchmark.lib import runner, spec

        cell = spec.load(ROOT).cell(args.workload)
        line = runner.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), expected_platform="tpu",
            t_start=T_START, out_dir=args.out,
            keep_trace=args.keep_trace,
            log=lambda msg: print(msg, flush=True))
    except Exception:  # any failure: exit != 0 and no result line
        traceback.print_exc()
        print(f"benchmark: {args.workload} FAILED", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
