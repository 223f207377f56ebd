#!/usr/bin/env python3
"""Finds a cell's knee: the highest offered rate its queue keeps up with.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 20

Runs the cell as ``bench/run.py`` does at each rate in one process
(with the cell file's rate replaced) and prints, per rate, the growth of
the engine's queue over the window (least squares, requests/s), the
requests unfinished at the drain's cap and the end-to-end numbers. The
knee is the highest rate whose queue does not grow over the window; the
cell files record the sweep and the rate chosen from it.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    """Sweep the rates; print one JSON line per rate."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.params = dict(cell.params, rate_rps=rate)
        res = run.run(cell, args.seed, args.seconds, False,
                      log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({"rate_rps": rate, "attempted": res["attempted"],
                          "failed": res["failed"], "correct": res["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()},
                          "extra": res["extra"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
