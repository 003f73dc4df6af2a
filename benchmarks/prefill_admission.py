"""Micro-bench: burst admission latency on the real path — sequential
per-request prefill (the pre-refactor behaviour) vs batched bucketed prefill
(one jitted ``forward_seq`` per prompt-length bucket per cycle).

    PYTHONPATH=src python -m benchmarks.prefill_admission [--batch 8]

Both modes pre-compile their shape grid (``Engine.warmup``, the vLLM-style
startup warmup), then serve full-batch bursts so every rep is one admission
cycle. Reported per mode: warmup seconds, prefill dispatches, and wall
seconds spent in admission (the ``engine.prefill`` span, traced).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from benchmarks.common import emit
from repro import tracing
from repro.configs import get_smoke_config
from repro.core.scheduler.policies import fcfs
from repro.core.scheduler.request import Request
from repro.core.scheduler.scheduler import Scheduler
from repro.models import transformer as tfm
from repro.serving.engine import Engine


def _burst(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        words = int(rng.integers(2, 28))
        prompt = " ".join(f"w{rng.integers(0, 999)}" for _ in range(words))
        reqs.append(Request(i, prompt, 0.0, words + 1, int(rng.integers(2, 6))))
    return reqs


def run(batch: int = 8, reps: int = 4, arch: str = "llama3_2_3b") -> dict:
    cfg = get_smoke_config(arch).replace(dtype="float32", vocab_size=2048)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    results = {}
    for mode, bucketed in (("sequential", False), ("bucketed", True)):
        sched = Scheduler(policy=fcfs(), max_batch=batch)
        eng = Engine(cfg, params, sched, cache_len=64, prompt_len=32,
                     bucketed=bucketed)
        warm_s = eng.warmup()
        with tracing.recording():
            for rep in range(reps):       # full-batch burst = 1 admission cycle
                eng.submit(_burst(batch, seed=rep))
                eng.run()
                assert len(eng.finished) == batch * (rep + 1)
        prefill_s = tracing.snapshot()["engine.prefill"]["s"]
        results[mode] = dict(dispatches=eng.backend.prefill_dispatches,
                             prefill_s=prefill_s, warmup_s=warm_s)
        print(f"{mode:10s} warmup={warm_s:6.1f} s "
              f"dispatches={eng.backend.prefill_dispatches:3d} "
              f"(over {reps} bursts of {batch})  "
              f"admission={prefill_s * 1e3:8.1f} ms")
    seq, buk = results["sequential"], results["bucketed"]
    speedup = seq["prefill_s"] / max(buk["prefill_s"], 1e-9)
    print(f"bucketed admission: {seq['dispatches']}→{buk['dispatches']} "
          f"dispatches, {speedup:.2f}x faster")
    emit("prefill_admission", buk["prefill_s"] * 1e6 / (batch * reps),
         f"admission speedup {speedup:.2f}x "
         f"({seq['dispatches']}->{buk['dispatches']} dispatches)")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--arch", default="llama3_2_3b")
    args = ap.parse_args()
    run(args.batch, args.reps, args.arch)
