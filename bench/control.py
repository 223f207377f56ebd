#!/usr/bin/env python3
"""Readings behind a cell's correctness limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 15

For each seed, one process runs the cell as ``bench/run.py`` does, at
the cell's own load and sizes, with a short window, and reads over the
same sample of served requests:

- ``program``: the compared numbers of the program's own tokens and
  posteriors (the lower readings);
- ``control``: the same numbers with the float32 reference rounded to
  W8A8 int8 in the program's place, the step below the configuration's
  bfloat16 (the upper readings).

Prints one JSON line per seed and a last line with the largest program
reading and the smallest control reading of each number. The benchmark's
own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    """Run the seeds; print their readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(args.workload)
    lows, highs = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run(cell, seed, args.seconds, False,
                      log=lambda m: print(m, file=sys.stderr, flush=True),
                      control="int8")
        line = {"seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "program": res["program"], "control": res["control"],
                "metrics": res["metrics"], "extra": res["extra"]}
        print(json.dumps(line), flush=True)
        for k in ("logit_gap", "probe_gap"):
            lows[k] = max(lows.get(k, 0.0), res["program"][k])
            highs[k] = min(highs.get(k, float("inf")), res["control"][k])
    print(json.dumps({"lower": lows, "upper": highs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
