#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the attached TPU and print one JSON line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exit code 0 and a result line only where JAX finds the chips the cell asks for.
``--rehearse`` (only with ``JAX_PLATFORMS=cpu`` set by the caller) drives the same code at
tiny sizes on the CPU; its line names the cpu device and is no measurement.
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
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from chipbench import harness

    t_start = harness.start_process(args.workload)  # may start this command again, with the environment the cell states

    try:
        import sheeprl_tpu  # noqa: F401  (the benchmark alone, without the program, stops here)
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout: {e}", file=sys.stderr)
        return 3
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), rehearse=args.rehearse, t_start=t_start
        )
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    compared = result["compared"]
    print("compared (value <= limit): " + "  ".join(
        f"{k}={v['value']:.6g}<={v['limit']:.6g}" for k, v in compared.items()), file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
