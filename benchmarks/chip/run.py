"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Starts the program as ``make_distributed_agent`` assembles it (multiprocess
actors, central inference server, learner and replay in this process, which
alone holds the chip), warms every shape the cell uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Exits non-zero,
printing no result, unless JAX finds as many TPU chips as the cell asks for.
"""
from __future__ import annotations

import time

PROCESS_START_NS = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    root = os.path.dirname(os.path.dirname(HERE))
    sys.path[:0] = [HERE, os.path.join(root, "src")]
    import harness
    bench = harness.load_benchmark(root)
    cell, config, traffic, limits = harness.resolve_cell(bench, args.workload)

    def log(message):
        print(message, file=sys.stderr, flush=True)

    result = harness.run_cell(
        cell, config, traffic, limits, bench, args.seed, args.seconds,
        bool(args.trace), process_start_ns=PROCESS_START_NS, log=log)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
