"""Readings that set a cell's logit-gap limit, on the chip, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 8

The cell is built and warmed once. For each seed the weights are drawn
again (the programs take them as an argument, so nothing recompiles), the
prefix cache is emptied, the cell's traffic for that seed is offered for a
short window at the cell's own rate and served to the end, and the program's
weights are freed. Then the plain reference reads the widest gap of the
served tokens (the program's reading), and, for ``--control-seeds``, the
float8 control's reading at the same positions. The limit lies between the
largest program reading and the smallest control reading. One JSON line per
seed, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, weights
    from bench.traffic import generator
    cell = harness.load_cell(args.workload)
    if args.smoke:
        cell = harness.smoke_cell(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    def traffic(seed):
        specs = generator.trace(cell.mix, cell.cell["rate_per_s"],
                                args.seconds, seed)
        if args.smoke:
            for s in specs:
                s.true_length = min(s.true_length,
                                    cell.config["serving"]["max_output_tokens"])
        return specs

    sess = harness.Session(cell, seeds[0], traffic(seeds[0]),
                           smoke=args.smoke)
    be, core = sess.engine.backend, sess.engine.core
    rows, base = [], 0
    for seed in seeds:
        if be.params is None:
            be.params = weights.make(cell.config, seed)
            jax.block_until_ready(be.params)
        core.allocator.clear_cache()
        specs = traffic(seed)
        reqs, _ = sess.serve(specs, args.seconds, id_base=base)
        base += len(specs) + 1
        kept = sess.conservation(reqs)
        done = [r for r in reqs if r.finish_time is not None]
        sample = [(r.prompt, list(r.generated_tokens))
                  for r in harness._sample(done, seed)]
        core.finished.clear()
        be.params = sess.params = None
        t0 = time.perf_counter()
        prog = harness.check_served(cell.config, seed, sample, sess.prompt_len)
        row = {"seed": seed, "finished": len(done), "offered": len(reqs),
               **kept, "tokens": prog["tokens"], "program_gap": prog["gap"]}
        if seed in ctl_seeds:
            ctl = harness.check_served(cell.config, seed, sample,
                                       sess.prompt_len, quant=True)
            row["control_gap"] = ctl["gap"]
        row["check_s"] = round(time.perf_counter() - t0, 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["program_gap"] for r in rows)
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "lower": lower, "upper": min(ctl) if ctl else None,
                      "seeds": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
