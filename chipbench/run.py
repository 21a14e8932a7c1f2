#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

  python3 chipbench/run.py --workload qwen1.5-0.5b.pair-k2 --seed 7 \\
      --seconds 40 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), and last
``checks``, each compared number beside its limit.  The same comparisons
are the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    cell = harness.resolve(args.workload)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START)
    except harness.NoChipError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for text in harness.check_lines(line["checks"]):
        print(text, file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
