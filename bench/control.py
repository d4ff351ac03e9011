"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 51

The configurations promise exact answers. The control breaks that promise
the way a tempting shortcut would: the reference BFS, put in the program's
place, stops one level early (a traversal that retires its lanes a sweep
before the frontier empties), so the deepest level reads unreached. For
each seed it builds the cell's graph at the cell's size, draws the same
sample of requests a run of ``--seconds`` checks (open-loop cells) or of
the first ``--sets`` key sets (key-set cells), answers them with the
control, and compares them with the reference through the run's own
comparison. One JSON line per seed; the control must read
``wrong_answers`` above its limit, 0, on every seed. Benchmark runs never
call it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import gen
import reference
import run


def truncated(levels: np.ndarray) -> np.ndarray:
    """``levels`` with the deepest level dropped (marked unreached)."""
    reached = levels[levels != reference.UNREACHED]
    out = levels.copy()
    if reached.size and reached.max() > 0:
        out[out == reached.max()] = reference.UNREACHED
    return out


def control_answer(check: dict, levels: np.ndarray):
    return reference.answer(check["kind"], truncated(levels),
                            check["max_depth"], check["targets"])


def sampled_requests(cell: dict, n: int, src, seed: int, seconds: float,
                     sets: int) -> list:
    """The requests a run of this cell and seed compares."""
    traffic = cell["traffic"]
    if traffic["driver"] == "open_loop":
        count = max(int(round(traffic["rate_qps"] * seconds)), 1)
        plain = run.point_requests(traffic, n, src, seed, count, block=1)
        return [plain[i] for i in gen.sample(count, traffic["check_requests"],
                                             seed)]
    checks = run.KeySetChecks(traffic, seed)
    for i, keys in enumerate(run.key_sets(traffic, n, src, seed)[:sets]):
        checks.add(i, keys, [None] * len(keys))
    return checks.all()


def control(cell: dict, seed: int, seconds: float, sets: int) -> dict:
    n, src, dst = gen.make_graph(cell["config"], seed)
    checks = sampled_requests(cell, n, src, seed, seconds, sets)
    g = reference.Graph(n, src, dst)
    return run.compare(g, checks, answer_of=control_answer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=3)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control(cell, seed, args.seconds, args.sets)
        print(json.dumps({"workload": args.workload, "seed": seed, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
