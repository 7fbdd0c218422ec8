"""Read the compared numbers of many seeds, for the program and for its
control, in one process on the chip: the readings each limit in
``limits/<cell>.json`` is set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6]

Each seed is one run of ``run.py`` with a window of one whole step (the
same set-up, window code and check as the benchmark's runs).  The control
is the program with its own lower-precision path switched on: the force
kernels' per-pair arithmetic in bfloat16 (``dtype="mixed"``), the nearest
precision below the float32 that the configuration states.  The benchmark's
own runs never run this.  One JSON line per run on standard output, then a
summary: the largest program reading and the smallest control reading of
each number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.run import main  # noqa: E402

CONTROL = {"dtype": "mixed"}


def one(workload: str, seed: int, overrides=None) -> dict:
    t0 = time.perf_counter()
    out = main(["--workload", workload, "--seed", str(seed),
                "--seconds", "0", "--trace", "0"], overrides=overrides,
                t_process=t0)
    return {k: v[0] for k, v in out["checks"].items()}


def summarize(program, control) -> dict:
    names = sorted({k for r in program + control for k in r})
    out = {}
    for k in names:
        lo = [r[k] for r in program if r.get(k) is not None]
        up = [r[k] for r in control if r.get(k) is not None]
        out[k] = {"program_max": max(lo) if lo else None,
                  "control_min": min(up) if up else None}
    return out


def cli(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    program, control = [], []
    for label, seeds_, ov, acc in (("program", seeds, None, program),
                                   ("control", cseeds, CONTROL, control)):
        for s in seeds_:
            r = one(args.workload, s, ov)
            acc.append(r)
            print(json.dumps({"run": label, "seed": s, "numbers": r}),
                  flush=True)
    print(json.dumps({"summary": summarize(program, control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(cli())
