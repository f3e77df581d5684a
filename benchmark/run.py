"""``python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell of ``BENCHMARK.json`` once, in this process, on the chip this
machine holds, and prints the result as the last line of standard output:
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` with ``--trace 1``). Without a TPU, with fewer
chips than the cell asks for, or in a directory that holds the benchmark
without the program, it exits non-zero and prints no result.

Everything a run does is in ``benchmark/harness.py`` and in the files it
finds by the names in the cell's entry.
"""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark import harness

    try:
        line = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace), PROCESS_START
        )
    except harness.Refused as exc:
        print(f"benchmark: refused: {exc}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
