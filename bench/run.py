#!/usr/bin/env python3
"""Serving benchmark of SALS: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration with random weights from the seed, serves
the cell's traffic through the program's scheduler for ``--seconds``,
checks what it served against the plain reference, and prints one JSON
line last on standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of part of the window),
``device``, ``breakdown`` (traced runs) and, last, ``checks``: each number
compared with its limit.  The checks are also the last lines of standard
error.

Needs a TPU: anywhere else, or without the program (``src/``) beside it,
it exits non-zero and prints no result.

``--control 1`` also runs the fp8 control of the reference over the same
tokens, reports its gap and judges ``correct`` by it, in the program's
place: a control run has to come out not correct.  ``--fault <name>``
plants one of ``bench/faults.py``'s faults under the timed path.  Both are
for setting and checking limits; the benchmark's own runs use neither.
``--save-extract PATH`` writes the traced run's trace extract and decode
steps there (the recording the tests replay).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    from bench.faults import FAULTS
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="", choices=("",) + tuple(FAULTS))
    ap.add_argument("--save-extract", default="")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 4
    from bench import harness
    try:
        res = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START,
                          control=bool(args.control),
                          fault=FAULTS.get(args.fault))
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    recording = res.pop("_recording")
    if args.save_extract and recording is not None:
        os.makedirs(os.path.dirname(os.path.abspath(args.save_extract)),
                    exist_ok=True)
        with open(args.save_extract, "w") as f:
            json.dump(recording, f)
    for name, c in res["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"[check] correct {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
