"""Knee sweep of an open-loop cell: one graph, one process, several rates.

    python3 bench/sweep.py --workload graph500.point-zipf --seed <n> \
        --seconds 51 --rates 1,2,3,4 [--drain 30]

Each rate gets a fresh frontend and the cell's warm-up prefix, then the
cell's window at that offered rate. One JSON line per rate gives the
offered and served rates, the latency median and 90th percentile, the
requests left unanswered after the drain and the backlog (lanes busy plus
queries pending) sampled once a second. The knee is the highest rate whose
served rate keeps up with the offer and whose backlog does not grow. This
tool sets the rate a cell's traffic file states; benchmark runs never call
it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=30.0)
    args = ap.parse_args(argv)
    import jax

    cell = run.load_cell(args.workload)
    if cell["traffic"]["driver"] != "open_loop":
        raise SystemExit("the knee sweep is for open-loop cells")
    if jax.devices()[0].platform != "tpu":
        run.log("sweep: needs a TPU")
        return 2
    P = run.program()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    P["compile_cache"].enable_compile_cache()
    cfg = cell["config"]
    n, src, dst = run.gen.make_graph(cfg, args.seed)
    pg = run.partition(P, cfg, n, src, dst)
    for rate in (float(r) for r in args.rates.split(",")):
        r = dict(cell, seed=args.seed, seconds=args.seconds, trace=False,
                 rate=rate, traffic=dict(cell["traffic"], drain_s=args.drain))
        out = run.run_open_loop(P, r, pg, n, src)
        ctx = out["ctx"]
        print(json.dumps({"rate_qps": rate, "offered_qps": ctx["sent"]
                          / args.seconds, **out["e2e"],
                          "unanswered": out["failed"],
                          "backlog": ctx["backlog"]}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
