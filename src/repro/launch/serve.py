"""Serving launcher: PARS-scheduled continuous batching on the real engine.

    PYTHONPATH=src python -m repro.launch.serve --policy pars --requests 32
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --smoke

Trains the ranking predictor (unless --policy fcfs/oracle), builds the engine
around the chosen architecture at its published widths and dtype (``--smoke``
takes the reduced float32 model instead, for a CPU), serves a burst, and
prints the paper's latency metrics.
"""
from __future__ import annotations

import argparse
from typing import Tuple

import jax
import numpy as np

from repro.configs import ModelConfig, get_config, get_smoke_config
from repro.core.predictor import TrainSettings, train_predictor
from repro.core.scheduler.policies import make_policy
from repro.data.synthetic import MODELS, make_corpus, sample_lengths
from repro.data.workload import burst_arrivals, make_requests, poisson_arrivals
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as tfm
from repro.serving import serve

#: Serving sizes at published widths that fit one 16 GiB TPU v5e for
#: ``llama3_2_3b`` bf16 (the compiled programs' memory is checked in
#: tests/test_tpu_compile.py)
MAX_BATCH, CACHE_LEN, PROMPT_LEN = 8, 2048, 512


def build_model(arch: str, *, smoke: bool = False,
                seed: int = 0) -> Tuple[ModelConfig, dict]:
    """The served LM and its weights, drawn from ``seed``.

    ``smoke=False`` is the published configuration in its published dtype;
    ``smoke=True`` is the family's reduced config in float32 with a
    2048-token vocabulary, small enough for a CPU."""
    cfg = (get_smoke_config(arch).replace(dtype="float32", vocab_size=2048)
           if smoke else get_config(arch))
    init = jax.jit(tfm.init_params, static_argnums=1)
    return cfg, init(jax.random.PRNGKey(seed), cfg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_2_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced float32 model (CPU-sized)")
    ap.add_argument("--policy", default="pars",
                    choices=["fcfs", "pars", "pointwise", "listwise", "oracle"])
    ap.add_argument("--workload", default="llama", choices=list(MODELS))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="poisson req/s (0 = burst)")
    ap.add_argument("--batch", type=int, default=MAX_BATCH)
    ap.add_argument("--max-len", type=int, default=120)
    ap.add_argument("--cache-len", type=int, default=CACHE_LEN,
                    help="KV positions per sequence")
    ap.add_argument("--prompt-len", type=int, default=PROMPT_LEN,
                    help="prompt tokens kept per request")
    ap.add_argument("--starvation", type=float, default=120.0)
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="KV cache budget in 16-token blocks "
                    "(0 = max_batch lanes of cache_len)")
    ap.add_argument("--seq-prefill", action="store_true",
                    help="disable bucketed prefill (one dispatch per request)")
    args = ap.parse_args()
    use_compile_cache()

    cfg, params = build_model(args.arch, smoke=args.smoke)

    predictor = None
    if args.policy in ("pars", "pointwise", "listwise"):
        method = {"pars": "pairwise"}.get(args.policy, args.policy)
        c_train = make_corpus("alpaca", 1000, seed=1)
        predictor = train_predictor(
            c_train.prompts,
            np.clip(sample_lengths(c_train, args.workload), 1, args.max_len),
            settings=TrainSettings(method=method, epochs=2,
                                   pairs_per_epoch=2048,
                                   delta=MODELS[args.workload].delta))
    policy = make_policy(args.policy, predictor)

    c = make_corpus("alpaca", args.requests, seed=9)
    lengths = np.clip(sample_lengths(c, args.workload), 1, args.max_len)
    arrivals = (burst_arrivals(args.requests) if args.rate <= 0
                else poisson_arrivals(args.requests, args.rate, seed=2))
    reqs = make_requests(c, lengths, arrivals)

    rep = serve(cfg, params, reqs, policy, max_batch=args.batch,
                cache_len=args.cache_len, prompt_len=args.prompt_len,
                starvation_threshold=args.starvation,
                kv_blocks=args.kv_blocks or None,
                bucketed=not args.seq_prefill)
    print(rep.row())


if __name__ == "__main__":
    main()
