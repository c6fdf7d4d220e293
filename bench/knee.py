"""Sweep the arrival rate of an open-loop cell to find its knee.

    python3 bench/knee.py --workload <cell> --rates 1,2,3 --seconds 30 --seed 7

One process sets the cell up once and drives one window per rate, with the
cell's traffic mix at that rate and everything else as the benchmark runs
it. For each rate it prints the tokens per second completed, the first-token
and per-token tails, and how many requests due in the window still waited
for their first token when it closed (a backlog that grows with the window
means the rate is above what the server sustains). Runs on a TPU only.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from run import cache_everything, find_chips, reader
from harness import readers, serving, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = find_chips(cell.chips)
    if devs is None:
        return 1
    cache_everything()
    sess = serving.Session(cell, devs)
    sess.make_weights(args.seed)
    sess.build_server()
    sess.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        sess.cell = copy.copy(cell)
        sess.cell.traffic = copy.deepcopy(cell.traffic)
        sess.cell.traffic["arrival"]["rate_per_s"] = rate
        run = sess.run(args.seed, args.seconds)
        steps = [s for s in run["steps"]
                 if run["t0"] <= s.t0 and s.t1 <= run["t_stop"]]
        rec = {**run, "steps": steps}
        late = [r for r in run["records"]
                if r.due <= run["t_stop"] and not (r.t_first and
                                                  r.t_first <= run["t_stop"])]
        print(json.dumps({
            "rate_per_s": rate, "seconds": run["seconds"],
            "output_tok_s": reader("output_tok_s")(rec),
            **{f"{name}_p{q}_ms": readers.percentile(values(rec), q)
               for name, values in (("ttft", readers.ttfts_ms),
                                    ("tpot", readers.tpots_ms))
               for q in (50, 90)},
            "due": sum(1 for r in run["records"] if r.due <= run["t_stop"]),
            "waiting_at_close": len(late),
            "unfinished": sum(1 for r in run["records"] if r.error)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
