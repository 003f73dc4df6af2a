"""End-to-end driver (deliverable (b)): serve a small model with batched
requests through the REAL JAX engine — actual forwards, KV cache, continuous
batching — comparing FCFS / PARS / Oracle wall-clock per-token latency.

    PYTHONPATH=src python examples/serve_e2e.py [--requests 48] [--batch 4]
"""
import argparse

import jax
import numpy as np

from repro import tracing
from repro.core.predictor import TrainSettings, train_predictor
from repro.core.scheduler.policies import fcfs, make_policy, oracle_sjf
from repro.data.synthetic import make_corpus, sample_lengths
from repro.data.workload import burst_arrivals, make_requests
from repro.core.scheduler.scheduler import Scheduler
from repro.launch.serve import build_model
from repro.serving import Engine, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--arch", default="llama3_2_3b",
                    help="smoke-config family to serve")
    ap.add_argument("--max-len", type=int, default=120,
                    help="clip ground-truth lengths for CPU wall-clock")
    ap.add_argument("--seq-prefill", action="store_true",
                    help="disable bucketed prefill (one dispatch per request)")
    args = ap.parse_args()

    # the served LM (reduced config of the selected family, real weights)
    cfg, params = build_model(args.arch, smoke=True)
    print(f"serving {cfg.arch_id} (reduced: {cfg.num_layers}L d{cfg.d_model}) "
          f"on {jax.devices()[0].platform}")

    # train the PARS predictor on a disjoint prompt set
    train_c = make_corpus("alpaca", 1000, seed=1)
    pred = train_predictor(
        train_c.prompts, np.clip(sample_lengths(train_c, "llama"), 1,
                                 args.max_len),
        settings=TrainSettings(method="pairwise", epochs=2,
                               pairs_per_epoch=2048, delta=0.2))

    test_c = make_corpus("alpaca", args.requests, seed=9)
    lengths = np.clip(sample_lengths(test_c, "llama"), 1, args.max_len)

    print(f"\nburst of {args.requests} requests, engine batch={args.batch}, "
          f"real wall-clock:")
    for pol in [fcfs(), make_policy("pars", pred), oracle_sjf()]:
        reqs = make_requests(test_c, lengths, burst_arrivals(args.requests))
        sched = Scheduler(policy=pol, max_batch=args.batch)
        eng = Engine(cfg, params, sched, cache_len=256,
                     bucketed=not args.seq_prefill)
        eng.submit(reqs)
        with tracing.recording():
            finished = eng.run()
        prefill_s = tracing.snapshot()["engine.prefill"]["s"]
        rep = report(pol.name, finished)
        print("  " + rep.row())
        print(f"    admission: {eng.backend.prefill_requests} prefills in "
              f"{eng.backend.prefill_dispatches} dispatches "
              f"({prefill_s * 1e3:.0f} ms)")


if __name__ == "__main__":
    main()
