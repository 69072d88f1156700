"""Run one benchmark cell of the served scheduler on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``.  The run sets up the service (compiles or loads every
device shape the cell can reach, replays the mix's warm prefix), measures
``--seconds`` of unpaced replay, checks every answer against the
references, and prints one JSON object as the last line of standard
output: the end-to-end metrics with ``--trace 0``, the per-layer metrics
(read from a profiler trace of the window's first seconds and from the
harness's spans) with ``--trace 1``.  The numbers compared, each beside
its limit, are the last lines of standard error and the line's last key.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    try:
        result = cell.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, log=log)
    except cell.NoDevice as e:
        log(f"bench: {e}; this benchmark measures the chip only")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
