#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place with one stated guarantee broken
(``:info`` operations treated as failed, so an indeterminate write that
took effect makes its reads look impossible). At the cell's own size and
sample, over the given seeds, it prints the numbers ``run.py`` compares
for the control's answers; each must be able to exceed its limit.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

The benchmark's own runs never run it. It needs no accelerator: the
control is host code.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import generator, run  # noqa: E402


def readings(config: dict, traffic: dict, seed: int) -> dict:
    """The compared numbers of the control's answers for one seed."""
    pool = generator.generate(
        config, traffic["histories_per_call"] * traffic["pool_calls"],
        seed, traffic["corrupt_histories"])
    rows = run.sample_rows(pool, traffic["sample_histories"], seed)
    want = run.reference_verdicts(config["reference"], pool, rows)
    ctl = run.reference_verdicts(config["reference"], pool, rows,
                                 info_as_fail=True)
    return run.compare({r: [ctl[r]] for r in rows}, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    for s in args.seeds.split(","):
        got = readings(c["config"], c["traffic"], int(s))
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          "control": got,
                          "fails": any(got[k] > run.LIMITS[k]
                                       for k in run.LIMITS)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
