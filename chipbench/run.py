#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the correctness check compared, beside its limit. The same
checks are the last lines of standard error.

No TPU, a chip not in the peaks table, fewer chips than the cell asks
for, or no program beside this directory: exit 2, and no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402
from chipbench.peaks import UnknownChip  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        harness.import_program()
        dev, count = harness.check_device(cell.chips)
    except (harness.Refused, UnknownChip, OSError) as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out_dir = harness.ROOT / ".chipbench_out" / (
        f"{args.workload}.{args.seed}.{args.trace}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, dev, count, out_dir)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
