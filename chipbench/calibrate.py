#!/usr/bin/env python3
"""The builder's tool for setting the limits of `correct`; no run of the benchmark calls it.

    python3 chipbench/calibrate.py --workload <name> --seed <n> [--seconds 2] [--stand-ins control,half_batch]

Drives the cell as ``run.py`` does, with a short window, and then puts each stand-in in the
program's place: ``control``, the reference in the precision below the configuration's, and
the faults the reference can plant (``half_batch``).  Each goes through the same
``harness.judge`` as the program and must come out with ``correct: false``; the line printed
holds the program's numbers and each stand-in's, from which PERF.md's readings are taken.
Exit code 1 where the program is not correct or a stand-in is.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--stand-ins", default="control,half_batch")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from chipbench import harness

    t_start = harness.start_process(args.workload)
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, False, rehearse=args.rehearse, t_start=t_start,
            stand_ins=[x for x in args.stand_ins.split(",") if x],
        )
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    verdicts = {"program": result["correct"], **{k: v["correct"] for k, v in result.get("stand_ins", {}).items()}}
    print(f"correct: {json.dumps(verdicts)}", file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)
    sound = verdicts.pop("program")
    return 0 if sound and not any(verdicts.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
