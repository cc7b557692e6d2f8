"""One-off measurements that set a cell's parameters, in one process.

    python3 chipbench/calibrate.py knee --workload <name> --seed <n> \\
        --seconds <s> --rates 1.6,2.0,2.4
    python3 chipbench/calibrate.py control --workload <name> \\
        --seeds 1,2,3 --seconds <s>

``knee`` runs the cell's mix at each Poisson rate given (in place of the
mix's own) and prints what each rate did: requests sent and failed, the
engine's busy share of the window, and the TTFT tails.  The knee is the
highest rate at which the queue does not grow through the window.

``control`` runs the cell on each seed with the float8 control
(``reference.py``) in the program's place in the logit-gap comparison,
on the same sample of served requests, and prints both widest gaps: the
program's (``diagnostics``) and the control's (``checks``).  The cell's
limit lies between the largest of the first and the smallest of the
second, and each control run must come out not correct.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("knee", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(run.ROOT, "src")]
    bench, cell, c, mix = run.load_cell(args.workload)
    run.setup_cache()
    metrics = run.cell_metrics(bench, cell["name"], "end_to_end")
    if args.what == "knee":
        runs = [(args.seed, float(r)) for r in args.rates.split(",")]
    else:
        runs = [(int(s), None) for s in args.seeds.split(",")]
    for seed, rate in runs:
        m = copy.deepcopy(mix)
        if rate is not None:
            m["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        limit = c["logit_gap_limit"]
        out = run.run_cell(cell, c, m, metrics, seed, args.seconds, False,
                           control=args.what == "control",
                           limit=float("inf") if limit is None else limit)
        vals = {k: v["value"] for k, v in out["metrics"].items()}
        print(json.dumps({"what": args.what, "workload": cell["name"],
                          "seed": seed, "rate": rate,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "metrics": vals,
                          "checks": {k: v["value"] for k, v in
                                     out["checks"].items()},
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"],
                          "diagnostics": out["diagnostics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
