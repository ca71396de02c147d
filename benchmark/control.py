"""The control: one cell at its own size on the chip, with one guarantee
of its configuration broken underneath the timed path (``faults.py``).
``correct`` has to come out false.

    python benchmark/control.py --workload g1k-sat --seed 5 --seconds 5 --fault replica_behind

Prints what ``run.py`` prints; exits 0 when the run came out as not
correct (the comparison caught the fault) and 1 when it passed.  The
benchmark's own runs never run this; ``PERF.md`` records the runs made.
"""

import argparse
import json
import sys

import run  # noqa: F401  (also puts the checkout on the path)
import faults


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), required=True)
    ap.add_argument("--nth", type=int, default=3,
                    help="which write to the name is broken: the warm-up "
                         "write is the first")
    args = ap.parse_args()
    cell, config, traffic, specs, e2e = run.load_cell(args.workload)

    if run.reach_chip(cell["chips"]) is None:
        return 2
    with faults.FAULTS[args.fault](run.cell_names(config)[0], nth=args.nth):
        result = run.run_cell(config, traffic, specs, e2e, args.seed,
                              args.seconds, False, "tpu",
                              chips=cell["chips"])
    print(json.dumps({"control": args.fault, **result}), flush=True)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
