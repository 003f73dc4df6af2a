"""Find a cell's knee: the highest offered rate that meets the cell's TTFT
limit without a growing queue. One process builds and warms the cell once,
then offers windows at rising rates (each a fresh draw of the cell's mix at
that rate), one for each seed.

    python bench/sweep.py --workload <cell> --rates 6,9,12 --seconds 20 \
        --seeds 1,2,3

For each rate it prints the requests offered and finished, the backlog
(requests in the system) and the waiting queue (requests not yet admitted)
in the middle third and the last sixth of the window, how long the loop
served past the window to finish them, the output tokens per second over the
window, and the TTFT tail. A rate is sustained when the TTFT tail meets the
cell's limit and the waiting queue at the end of the window is no longer
than in its middle third (by two requests). The backlog alone cannot say
so: requests that are running, such as a long answer still decoding at the
close, are in it at any rate. A rate is sustained only where every seed
sustains it: the seed orders the arrivals, and a tail depends on the order.
It stops after the first rate that is not sustained. Run it once on the
chip; the cell file records the result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.stats import ttfts
    from bench.traffic import generator
    cell = harness.load_cell(args.workload)
    if args.smoke:
        cell = harness.smoke_cell(cell)
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    traces = {(r, sd): generator.trace(cell.mix, r, args.seconds, sd)
              for r in rates for sd in seeds}
    if args.smoke:
        for t in traces.values():
            for s in t:
                s.true_length = min(s.true_length,
                                    cell.config["serving"]["max_output_tokens"])
    sess = harness.Session(cell, seeds[0],
                           [s for t in traces.values() for s in t],
                           smoke=args.smoke)
    rows = []
    base = 0
    step = sess.probe.on_step
    for (rate, seed), specs in traces.items():
        backlog = []

        def on_step(core, now, backlog=backlog):
            step(core, now)
            backlog.append((now, len(core.scheduler.waiting)
                            + len(core.scheduler.running),
                            len(core.scheduler.waiting)))
        sess.probe.on_step = on_step
        sess.probe.scorer_batch_max = 0
        reqs, end = sess.serve(specs, args.seconds, id_base=base)
        base += len(specs) + 1
        b = np.asarray(backlog)
        t = args.seconds
        in_mid = (b[:, 0] >= t / 3) & (b[:, 0] < 2 * t / 3)
        in_last = (b[:, 0] >= 5 * t / 6) & (b[:, 0] < t)
        mid, last = b[in_mid, 1], b[in_last, 1]
        wait_mid, wait_last = b[in_mid, 2], b[in_last, 2]
        done = [r for r in reqs if r.finish_time is not None]
        in_window = sum(r.true_length for r in done if r.finish_time <= t)
        run = harness.RunData(cell=cell, seconds=t, requests=reqs, end=end,
                              setup_s=0.0, probe=sess.probe,
                              shapes=sess.shapes, peak=sess.peak)
        row = {"rate_per_s": rate, "seed": seed, "offered": len(reqs),
               "finished": len(done),
               "backlog_mid": float(mid.mean()) if len(mid) else 0.0,
               "backlog_end": float(last.mean()) if len(last) else 0.0,
               "waiting_mid": float(wait_mid.mean()) if len(wait_mid) else 0.0,
               "waiting_end": float(wait_last.mean()) if len(wait_last) else 0.0,
               "served_past_window_s": round(end - t, 3),
               "tokens_per_s_in_window": in_window / t,
               "ttft_p95_s": float(np.percentile(ttfts(run), 95)),
               "backlog_max": int(b[:, 1].max()) if len(b) else 0,
               "scorer_batch_max": sess.probe.scorer_batch_max}
        row["sustained"] = (row["ttft_p95_s"] <= cell.cell["limits"]["ttft_s"]
                            and row["waiting_end"] <= row["waiting_mid"] + 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
        sess.engine.core.finished.clear()
        if seed == seeds[-1] and not all(
                r["sustained"] for r in rows if r["rate_per_s"] == rate):
            break
    held = {r["rate_per_s"] for r in rows} - {
        r["rate_per_s"] for r in rows if not r["sustained"]}
    knee = max(held, default=None)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee,
                      "seconds": args.seconds, "seeds": seeds,
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
