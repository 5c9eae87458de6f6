"""One-off measurements that set a cell's numbers; the benchmark's own
runs never run this.

    # the compared numbers on many seeds, and the fp8 control on some,
    # in one process (set-up once per seed, compiles shared):
    python3 -m benchmarks.chip.calibrate readings --workload <cell> \\
        --seeds 11 12 13 --control-seeds 11 12 13 --seconds 10

    # the same with the program's bfloat16 solver path as the control,
    # or with a fault of ``faults.py`` planted:
    python3 -m benchmarks.chip.calibrate readings --workload <cell> \\
        --seeds 11 12 13 --precision bf16 --seconds 10
    python3 -m benchmarks.chip.calibrate readings --workload <cell> \\
        --seeds 11 12 13 --fault lane --seconds 10

    # an open-loop cell's latency at several offered rates (the knee):
    python3 -m benchmarks.chip.calibrate sweep --workload <cell> \\
        --rates 8 10 12 14 --seconds 15

Each result is one JSON line on standard output, also appended to a
file under ``--out`` when given.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys

from . import run


def _emit(line: dict, name: str, out: str | None) -> None:
    text = json.dumps(line)
    print(text, flush=True)
    if out:
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, name), "a") as f:
            f.write(text + "\n")


def readings(cell, seeds, control_seeds, seconds, out=None,
             precision=None, fault=None) -> None:
    from . import faults
    for seed in seeds:
        undo = faults.plant(fault) if fault else None
        faults.REACHED.clear()
        try:
            res, _ = run.measure(cell, seed, seconds, False,
                                 control=seed in control_seeds,
                                 precision=precision)
        finally:
            if undo:
                undo()
        _emit({"cell": cell.name, "seed": seed, "seconds": seconds,
               "precision": precision, "fault": fault,
               "attempted": res["attempted"], "failed": res["failed"],
               "correct": res["correct"],
               "checks": res["checks"], "per_request": res["x0_rel_err"],
               "reached": [r in faults.REACHED for r in res["sample_rids"]],
               "control": res.get("control"),
               "check_s": res["check_s"], "metrics": res["metrics"]},
              f"readings_{cell.name}.jsonl", out)


def sweep(cell, rates, seconds, seed, out=None) -> None:
    from .stats import quantile
    for rate in rates:
        c = dataclasses.replace(cell, traffic=copy.deepcopy(cell.traffic),
                                limits=dict(cell.limits, sample=0))
        c.traffic["arrivals"]["rate_per_s"] = rate
        _, measured = run.measure(c, seed, seconds, False)
        recs = measured.records
        lat = [r.done - r.due for r in recs if r.status == "ok"]
        lag = [r.submit - r.due for r in recs if r.submit is not None]
        last = max(r.done for r in recs if r.done is not None)
        _emit({"cell": cell.name, "rate_per_s": rate, "seconds": seconds,
               "requests": len(recs), "ok": len(lat),
               "p50_s": quantile(lat, 0.5), "p95_s": quantile(lat, 0.95),
               "max_s": max(lat), "intake_lag_p95_s": quantile(lag, 0.95),
               "drain_past_window_s": last - seconds},
              f"sweep_{cell.name}.jsonl", out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--precision", choices=("f32", "bf16"),
                    help="the solver's precision policy (default: the "
                    "server's)")
    ap.add_argument("--fault", choices=("stuck", "shifted", "lane"))
    ap.add_argument("--sample", type=int,
                    help="answers compared per run (default: the cell's)")
    ap.add_argument("--out", help="directory the lines are also appended to")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    cell = run.resolve(args.workload)
    if args.sample:
        cell.limits = dict(cell.limits, sample=args.sample)
    if args.mode == "readings":
        readings(cell, args.seeds, set(args.control_seeds), args.seconds,
                 args.out, args.precision, args.fault)
    else:
        sweep(cell, args.rates, args.seconds, args.seeds[0] if args.seeds
              else 1, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
