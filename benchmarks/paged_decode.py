"""Paged-decode benchmark: admitted concurrency under a fixed KV budget.

Full-demand reservation admits a request only when its *entire* KV demand
(prompt + max completion) fits — most of that reservation sits empty while
the request decodes its way toward it. Incremental reservation (the paged-KV
admission policy, ``kv_reservation="incremental"``) admits on prompt + one
decode block and grows the block table one step ahead of decode, so the same
budget holds roughly ``full_demand / prompt_demand`` times more concurrent
requests; when a grow is denied, the core preempts the lowest-ranked running
request (recompute semantics) and the denied request proceeds.

Three sections:

* **sim** — discrete-event run on the shared ServingCore: peak admitted
  concurrency full vs incremental at the same ``kv_blocks`` budget. Asserts
  the ISSUE acceptance bar — **>= 1.5x** — and, on a tighter budget, that
  grow-failure preemption fires and every request still finishes (recovery
  without deadlock), with the grow counters surfaced in ``report()``.
* **real** — the jitted paged engine: greedy outputs bit-identical paged vs
  contiguous, zero KV tokens copied on the prefix-cache hit path, and the
  grow/preempt counters live end to end.
* **kernel** — ``flash_decode_paged`` vs its jnp oracle on a GQA shape with
  shuffled + aliased tables (parity, plus a wall-clock row).

    PYTHONPATH=src python -m benchmarks.paged_decode            # full
    PYTHONPATH=src python -m benchmarks.paged_decode --smoke --json out.json
"""
from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from benchmarks.common import emit, record_serving_bench
from repro.core.scheduler.policies import fcfs
from repro.core.scheduler.request import Request
from repro.core.scheduler.scheduler import Scheduler
from repro.serving.config import ServingConfig
from repro.serving.metrics import report
from repro.serving.simulator import CostModel, simulate


# ---------------------------------------------------------------- simulator
def run_sim(*, n: int = 12, prompt_len: int = 16, out_len: int = 48,
            kv_blocks: int = 16, block_size: int = 16,
            tight_blocks: int = 6) -> dict:
    """Peak-concurrency comparison at a fixed budget, then a deliberately
    tight budget to exercise grow-failure preemption and recovery."""

    def reqs():
        return [Request(i, f"req {i} " + " ".join(f"w{j}" for j in range(8)),
                        0.0, prompt_len, out_len) for i in range(n)]

    def run(reservation, blocks):
        peak = {"running": 0}

        def probe(core, _now):
            peak["running"] = max(peak["running"],
                                  len(core.scheduler.running))

        fin = simulate(reqs(), Scheduler(policy=fcfs(), max_batch=n),
                       cost=CostModel(), kv_blocks=blocks,
                       block_size=block_size, on_step=probe,
                       config=ServingConfig(kv_reservation=reservation))
        assert len(fin) == n, "requests lost — scheduler deadlocked?"
        assert all(r.tokens_done == r.true_length for r in fin)
        return fin, peak["running"]

    out = {"kv_blocks": kv_blocks, "n_requests": n,
           "kv_demand_blocks_per_req": math.ceil((prompt_len + out_len)
                                                 / block_size)}
    for label in ("full", "incremental"):
        fin, peak = run(label, kv_blocks)
        rep = report("fcfs", fin)
        out[label] = {
            "peak_concurrency": peak,
            "makespan_s": rep.makespan,
            "avg_ttft_s": rep.avg_ttft,
            "grow_failures": rep.grow_failures,
            "grow_preemptions": rep.grow_preemptions,
        }
        print(f"  [sim] {label:11s} peak_concurrency={peak:3d}  "
              f"makespan={rep.makespan:7.2f} s  "
              f"grow_failures={rep.grow_failures}")
    # reservation-mode metrics contract: counters exist exactly when the
    # run reserved incrementally (NaN-safe aggregation otherwise)
    assert math.isnan(out["full"]["grow_failures"])
    assert not math.isnan(out["incremental"]["grow_failures"])
    ratio = (out["incremental"]["peak_concurrency"]
             / out["full"]["peak_concurrency"])
    out["concurrency_ratio"] = ratio
    assert ratio >= 1.5, f"admitted-concurrency ratio {ratio:.2f}x < 1.5x"
    print(f"  [sim] incremental admits {ratio:.1f}x more concurrent "
          f"requests at the same budget")

    # tight budget: growth *must* fail; preemption recovers, nothing hangs
    fin, _ = run("incremental", tight_blocks)
    rep = report("fcfs", fin)
    out["tight_budget"] = {
        "kv_blocks": tight_blocks,
        "grow_failures": rep.grow_failures,
        "grow_preemptions": rep.grow_preemptions,
        "preempted_requests": sum(1 for r in fin if r.preempt_count),
    }
    assert rep.grow_failures > 0, "tight budget never denied a grow"
    assert rep.grow_preemptions > 0, "denials never forced a preemption"
    print(f"  [sim] tight budget ({tight_blocks} blocks): "
          f"{rep.grow_failures:.0f} grow failures, "
          f"{rep.grow_preemptions:.0f} preemptions, all {n} finished")
    return out


# -------------------------------------------------------------- real engine
def run_real(*, arch: str = "llama3_2_3b", shared_words: int = 24,
             n_warm: int = 3, out_len: int = 4, prompt_len: int = 32,
             n_tight: int = 5, tight_out: int = 40) -> dict:
    """Paged engine smoke: bit-identity vs contiguous on a shared-prefix
    workload (zero-copy hits), then grow/preempt recovery end to end."""
    import jax
    from repro.configs import get_smoke_config
    from repro.models import transformer as tfm
    from repro.serving.engine import Engine
    from repro.serving.kv_cache import BlockAllocator

    cfg = get_smoke_config(arch).replace(dtype="float32", vocab_size=2048)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prefix = " ".join(f"sys{i}" for i in range(shared_words))

    def shared_run(paged):
        eng = Engine(cfg, params,
                     Scheduler(policy=fcfs(), max_batch=n_warm + 1),
                     cache_len=2 * prompt_len, prompt_len=prompt_len,
                     paged=paged, record_tokens=True,
                     config=ServingConfig(prefix_caching=True))
        eng.submit([Request(0, prefix + " donor tail", 0.0, prompt_len,
                            out_len)])
        eng.run()
        eng.submit([Request(10 + i, prefix + f" user{i} suffix", 0.0,
                            prompt_len, out_len) for i in range(n_warm)])
        eng.run()
        assert len(eng.finished) == n_warm + 1
        return eng

    out = {}
    t0 = time.perf_counter()
    contig = shared_run(False)
    paged = shared_run(True)
    out["wall_s"] = time.perf_counter() - t0
    toks = {p: {r.req_id: r.generated_tokens for r in e.finished}
            for p, e in (("contiguous", contig), ("paged", paged))}
    out["identical_outputs"] = toks["contiguous"] == toks["paged"]
    assert out["identical_outputs"], "paged decode diverged from contiguous"
    out["prefix_installs"] = paged.backend.prefix_installs
    out["prefix_tokens_copied"] = paged.backend.prefix_tokens_copied
    assert out["prefix_installs"] == n_warm
    assert out["prefix_tokens_copied"] == 0, "paged hit path copied KV"
    print(f"  [real] paged outputs identical to contiguous; "
          f"{n_warm} zero-copy prefix hits (0 tokens copied)")

    # incremental + tight budget on the real engine: recovery, live counters.
    # 14-word prompts land in the 16-token bucket, so demand = 16 +
    # tight_out tokens >= 3 blocks/request while admission reserves prompt
    # + one decode block = 2 — the rest *must* come from decode-time grows,
    # and 6 total blocks can't grow everyone at once
    reqs = [Request(i, f"r{i} " + " ".join(f"w{j}" for j in range(13)), 0.0,
                    16, tight_out) for i in range(n_tight)]
    eng = Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=n_tight),
                 cache_len=48, prompt_len=16, allocator=BlockAllocator(6, 16),
                 record_tokens=True,
                 config=ServingConfig(kv_reservation="incremental"))
    eng.submit(reqs)
    fin = eng.run()
    assert len(fin) == n_tight
    assert all(r.tokens_done == r.true_length for r in fin)
    rep = report("fcfs", fin)
    out["tight_budget"] = {"grow_failures": rep.grow_failures,
                           "grow_preemptions": rep.grow_preemptions}
    assert rep.grow_failures > 0 and rep.grow_preemptions > 0
    print(f"  [real] tight budget: {rep.grow_failures:.0f} grow failures, "
          f"{rep.grow_preemptions:.0f} preemptions, all requests finished")
    return out


# ------------------------------------------------------------------ kernel
def run_kernel(*, b: int = 4, h: int = 8, kh: int = 2, bs: int = 16,
               mb: int = 8, dh: int = 64, iters: int = 20) -> dict:
    """Paged Pallas kernel vs jnp oracle on shuffled + aliased tables."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_decode.ops import paged_decode_attention_pallas
    from repro.kernels.flash_decode.ref import flash_decode_paged_ref

    n_blocks = 2 * b * mb
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, kh, bs, dh))
    v_pool = jax.random.normal(ks[1], (n_blocks, kh, bs, dh))
    q = jax.random.normal(ks[2], (b, h, dh))
    rng = np.random.default_rng(0)
    tables = np.stack([rng.permutation(n_blocks)[:mb] for _ in range(b)])
    tables[:, 0] = 0                              # aliased shared block
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray([mb * bs - (11 * i) % (mb * bs - 1)
                           for i in range(b)], jnp.int32)

    out = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    ref = flash_decode_paged_ref(q.reshape(b, kh, h // kh, dh), k_pool,
                                 v_pool, tables, lengths).reshape(b, h, dh)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 2e-5, f"paged kernel off oracle by {err}"
    t0 = time.perf_counter()
    for _ in range(iters):
        out = paged_decode_attention_pallas(q, k_pool, v_pool, tables,
                                            lengths, interpret=True)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    print(f"  [kernel] paged decode parity max|err|={err:.2e}, "
          f"{us:.1f} us/call (interpret-mode on CPU)")
    return {"max_abs_err": err, "us_per_call": us,
            "shape": dict(b=b, h=h, kh=kh, block_size=bs, max_blocks=mb,
                          dh=dh)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config: prove the concurrency bar, "
                         "recovery, zero-copy hits, and kernel parity")
    ap.add_argument("--json", default=None, help="write results to this path")
    ap.add_argument("--mode", choices=("sim", "real", "kernel", "all"),
                    default="all")
    args = ap.parse_args(argv)

    results = {}
    if args.mode in ("sim", "all"):
        print("simulator (A100-scale constants):")
        kw = dict(n=8, out_len=32, kv_blocks=12, tight_blocks=5) \
            if args.smoke else {}
        results["sim"] = run_sim(**kw)
    if args.mode in ("real", "all"):
        print("real engine (smoke-scale model, wall clock):")
        kw = dict(shared_words=16, n_warm=2, prompt_len=32, n_tight=4) \
            if args.smoke else {}
        results["real"] = run_real(**kw)
    if args.mode in ("kernel", "all"):
        print("paged Pallas kernel:")
        kw = dict(b=2, mb=4, iters=5) if args.smoke else {}
        results["kernel"] = run_kernel(**kw)

    if "sim" in results:
        s = results["sim"]
        emit("paged_decode_sim", s["incremental"]["avg_ttft_s"] * 1e6,
             f"incremental reservation holds "
             f"{s['concurrency_ratio']:.1f}x more concurrent requests at "
             f"{s['kv_blocks']} KV blocks; "
             f"{s['tight_budget']['grow_preemptions']:.0f} grow-preemptions "
             f"recovered on the tight budget")
        record_serving_bench("paged_decode", {
            "concurrency_ratio": s["concurrency_ratio"],
            "peak_concurrency_full": s["full"]["peak_concurrency"],
            "peak_concurrency_incremental":
                s["incremental"]["peak_concurrency"],
            "tight_budget_grow_failures":
                s["tight_budget"]["grow_failures"],
            "tight_budget_grow_preemptions":
                s["tight_budget"]["grow_preemptions"],
            "real_prefix_tokens_copied":
                results.get("real", {}).get("prefix_tokens_copied"),
            "real_identical_outputs":
                results.get("real", {}).get("identical_outputs"),
        })
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return results


if __name__ == "__main__":
    main()
