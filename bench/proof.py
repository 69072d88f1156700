"""Readings behind the limits of ``correct``: sound seeds and the control.

    python3 bench/proof.py --workload <name> --seconds <s> \
        --seeds <n,n,...> [--control-seeds <n,n,...>] [--out <file.jsonl>]

Runs the cell, in one process (set-up and compiles are paid once), on
each seed as the benchmark runs it, and prints one JSON line per run with
the numbers compared.  On the seeds of ``--control-seeds`` it also reads
the control: the references computed one precision below the
configuration's (the scheduler's ledger and deficit counters in
float32, the builder's space in float16) put in the program's place and compared like its answers.
The limits in ``bench/limits.json`` are set between the largest sound
reading and the smallest control reading.  The benchmark's own runs never
read the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    seeds += sorted(ctl - set(seeds))
    out = open(args.out, "a") if args.out else None
    try:
        for seed in seeds:
            try:
                res = cell.run(args.workload, seed, args.seconds, False,
                               control=seed in ctl,
                               log=lambda line: print(line, file=sys.stderr,
                                                      flush=True))
            except cell.NoDevice as e:
                print(f"proof: {e}", file=sys.stderr)
                return 2
            rec = {"workload": args.workload, "seed": seed,
                   "correct": res["correct"], "attempted": res["attempted"],
                   "numbers": {k: v["value"]
                               for k, v in res["checks"].items()},
                   "metrics": {k: v["value"]
                               for k, v in res["metrics"].items()}}
            if "control" in res:
                rec["control"] = {
                    "correct": res["control"]["correct"],
                    "numbers": {k: v["value"] for k, v in
                                res["control"]["checks"].items()}}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
