"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell comes from ``BENCHMARK.json``. Set-up
(weights from the seed, the engine, the cell's own shapes) is timed as
``setup_s``; then requests are offered for ``--seconds`` seconds, the
program's state is freed and what it served is checked against the plain
reference. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a profiler trace of the window,
the program's counters and its host spans. Without a TPU it prints no result
and exits non-zero.

``--control lower`` also reads the comparison for the reference put in the
program's place one precision step below the configuration's (float8 for
bfloat16), on the same sessions, holds it to the same limits and reports the
verdict as ``control_correct``; the benchmark's own runs do not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("lower",), default=None)
    opts = ap.parse_args(argv)

    from bench import harness

    bench = harness.benchmark(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if opts.workload not in cells:
        print(f"bench: no workload {opts.workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(cells[opts.workload], opts.seed, opts.seconds,
                                  bool(opts.trace), bench=bench,
                                  control=opts.control, t_start=T_START)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
