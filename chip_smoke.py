"""Bring-up check: serve full-width Llama 3.2 3B under PARS on a TPU.

    python chip_smoke.py              # one chip: the serving path, then the
                                      # paged decode kernel against its oracle
    python chip_smoke.py --chips 4    # four one-chip replicas behind the
                                      # router, against the same trace on one

The one-chip phase builds ``llama3_2_3b`` at its published widths in bf16
with weights drawn from a seed, trains the PARS predictor (BERT-base width)
for a few steps, and serves a burst of requests that share a long system
prompt through ``Engine`` (paged KV, bucketed and chunked prefill, prefix
caching, re-ranking every 4 steps). It fails unless every request finished,
the scorer never failed, at least one continuation chunk and one prefix hit
happened, and every sampled token is a vocabulary id. The last line of
standard output is one JSON object naming the device; it is printed only
when every check passed on a TPU.

``--smoke`` runs the same phases at the reduced float32 width on whatever
device JAX has, with the kernel interpreted, and prints no result line: it is
the CPU rehearsal of the control flow (``JAX_PLATFORMS=cpu``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SMOKE_SIZES = (4, 1024, 512)
N_REQUESTS = 16
#: Words of the shared system prompt (and of each unique prompt's filler):
#: with the [CLS] token its first 16 blocks of 16 tokens are shared, so a hit
#: resumes at offset 256, one prefill chunk before the 512-token bucket's end
SYSTEM_WORDS = 270
SERVING = dict(prefill_chunk_tokens=256, prefix_caching=True,
               rerank_every_steps=4)


def log(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """Counts backend compilations (JAX's monitoring events)."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


def make_trace(n: int, seed: int, *, vocab_words: int = 4096):
    """``n`` burst requests, each a short task prompt of the synthetic corpus
    made long: even ids follow one seeded system prompt (they share its KV
    blocks), odd ids are followed by their own filler words (the scorer sees
    their task, so their PARS scores differ). Output lengths follow the llama
    profile, clipped to [8, 64] tokens."""
    import numpy as np

    from repro.core.scheduler.request import Request
    from repro.data.synthetic import make_corpus, sample_lengths

    rng = np.random.default_rng(seed)

    def words() -> str:
        return " ".join(f"w{i}" for i in rng.integers(0, vocab_words,
                                                      SYSTEM_WORDS))

    system = words()
    corpus = make_corpus("alpaca", n, seed=seed + 1)
    lengths = np.clip(sample_lengths(corpus, "llama"), 8, 64)
    prompts = [f"{system} {p}" if i % 2 == 0 else f"{p} {words()}"
               for i, p in enumerate(corpus.prompts)]
    return [Request(req_id=i, prompt=p, arrival_time=0.0,
                    prompt_len=len(p.split()), true_length=int(lengths[i]))
            for i, p in enumerate(prompts)]


def train_scorer(smoke: bool, seed: int):
    """The PARS ranker at the paper's width (BERT-base), trained for a few
    pairwise steps from ``seed`` so its train step and scorer both run."""
    import numpy as np

    from repro.configs.pars_bert_base import CONFIG, smoke_config
    from repro.core.predictor import TrainSettings, train_predictor
    from repro.core.predictor.tokenizer import HashTokenizer
    from repro.data.synthetic import make_corpus, sample_lengths

    pcfg = smoke_config() if smoke else CONFIG
    corpus = make_corpus("alpaca", 512, seed=seed)
    return train_predictor(
        corpus.prompts, np.clip(sample_lengths(corpus, "llama"), 1, 512),
        settings=TrainSettings(method="pairwise", epochs=1, batch_size=16,
                               pairs_per_epoch=128, seed=seed),
        tokenizer=HashTokenizer(pcfg.vocab_size, pcfg.max_len), pcfg=pcfg)


def build_engine(cfg, params, policy, sizes, *, device=None):
    from repro.core.scheduler.scheduler import Scheduler
    from repro.serving import Engine, ServingConfig

    max_batch, cache_len, prompt_len = sizes
    return Engine(cfg, params, Scheduler(policy=policy, max_batch=max_batch),
                  cache_len=cache_len, prompt_len=prompt_len,
                  record_tokens=True, device=device,
                  config=ServingConfig(**SERVING))


def check(ok: bool, what: str, failures: list) -> None:
    log(f"  check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        failures.append(what)


def served_tokens_ok(finished, vocab: int) -> bool:
    return all(len(r.generated_tokens) == r.true_length
               and all(0 <= t < vocab for t in r.generated_tokens)
               for r in finished)


def serving_phase(cfg, params, sizes, smoke: bool, seed: int,
                  failures: list) -> None:
    """PARS over the full serving path on the first device."""
    from repro.core.scheduler.policies import make_policy
    from repro.serving import report

    t0 = time.perf_counter()
    predictor = train_scorer(smoke, seed)
    log(f"predictor: {predictor.cfg.num_layers}L d{predictor.cfg.d_model} "
        f"trained in {time.perf_counter() - t0:.1f} s (compile included)")
    policy = make_policy("pars", predictor)
    eng = build_engine(cfg, params, policy, sizes)
    compiles = Compiles()
    warm = eng.warmup()
    log(f"warmup: {warm:.1f} s, {compiles.n} compiles "
        f"({compiles.seconds:.1f} s compiling)")

    reqs = make_trace(N_REQUESTS, seed)
    eng.submit(reqs)
    n0 = compiles.n
    t0 = time.perf_counter()
    finished = eng.run()
    wall = time.perf_counter() - t0
    be, core = eng.backend, eng.core
    rep = report(policy.name, finished)
    tokens = sum(r.tokens_done for r in finished)
    log(f"served {len(finished)}/{len(reqs)} requests, {tokens} tokens in "
        f"{wall:.2f} s wall ({compiles.n - n0} compiles while serving)")
    log(f"  prefill_dispatches={be.prefill_dispatches} "
        f"extend_dispatches={be.extend_dispatches} "
        f"prefix_hits={be.prefix_installs} "
        f"prefix_hit_rate={rep.prefix_hit_rate:.3f} "
        f"reranks={core.rerank_count}")
    scores = [round(r.score, 4) for r in reqs]
    log(f"  PARS scores: {len(set(scores))} distinct, first four {scores[:4]}")
    stats = be.device.memory_stats() or {}
    log(f"  peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"
        f" on {be.device.device_kind}")
    check(len(finished) == len(reqs) and not core.dropped,
          "every request finished, none dropped", failures)
    check(policy.scorer_failures == 0 and policy.degradations == 0,
          "scorer_failures == 0 and degradations == 0", failures)
    check(be.extend_dispatches >= 1, "at least one continuation chunk",
          failures)
    check(be.prefix_installs >= 1 and rep.prefix_hit_rate > 0,
          "at least one prefix hit", failures)
    check(served_tokens_ok(finished, cfg.vocab_size),
          "every sampled token lies in [0, vocab)", failures)


def kernel_phase(cfg, sizes, smoke: bool, seed: int, failures: list) -> None:
    """The paged decode kernel, compiled, against its oracle at the served
    widths (batch, heads, KV heads, head dim, cache length, block size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_decode.ops import paged_decode_attention_pallas
    from repro.kernels.flash_decode.ref import flash_decode_paged_ref

    b, w, _ = sizes
    h, kh, dh, bs = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 16
    mb = w // bs
    n_blocks = b * mb + 1
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, kh, bs, dh), dt)
    v_pool = jax.random.normal(ks[1], (n_blocks, kh, bs, dh), dt)
    q = jax.random.normal(ks[2], (b, h, dh), dt)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(np.stack([rng.permutation(n_blocks)[:mb]
                                   for _ in range(b)]), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, w + 1, b), jnp.int32)
    out = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=smoke)
    with jax.default_matmul_precision("highest"):
        ref = flash_decode_paged_ref(q.reshape(b, kh, h // kh, dh), k_pool,
                                     v_pool, tables, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.reshape(b, h, dh).astype(jnp.float32))))
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    log(f"paged decode kernel ({'interpreted' if smoke else 'compiled'}): "
        f"B={b} H={h} KH={kh} dh={dh} W={w} max|err| vs oracle={err:.3e}")
    check(bool(np.isfinite(err)) and err <= tol,
          f"paged kernel matches flash_decode_paged_ref within {tol}",
          failures)


def replicas_phase(cfg, params, sizes, devices, seed: int,
                   failures: list) -> None:
    """Four one-chip replicas behind ``ReplicaRouter``, each holding its own
    params, KV pool and slot tokens, against the same trace on one replica.
    Programs compile as shapes first appear (no warm-up grid)."""
    import jax

    from repro.core.scheduler.policies import make_policy
    from repro.serving.router import ReplicaRouter

    def serve(n_replicas):
        engines = [build_engine(cfg, params, make_policy("oracle"), sizes,
                                device=d) for d in devices[:n_replicas]]
        router = ReplicaRouter([e.core for e in engines],
                               policy="round_robin", seed=seed)
        reqs = make_trace(N_REQUESTS // 2, seed)
        router.submit(reqs)
        t0 = time.perf_counter()
        finished = router.run()
        wall = time.perf_counter() - t0
        dropped = router.all_dropped
        log(f"{n_replicas} replica(s): {len(finished)} finished + "
            f"{len(dropped)} dropped of {len(reqs)} submitted, "
            f"{sum(r.tokens_done for r in finished)} tokens in {wall:.2f} s "
            f"wall (compile included)")
        check(len(finished) + len(dropped) == len(reqs) and not dropped,
              f"{n_replicas} replica(s): conservation, nothing dropped",
              failures)
        check(served_tokens_ok(finished, cfg.vocab_size),
              f"{n_replicas} replica(s): every sampled token in [0, vocab)",
              failures)
        return engines, router, finished

    base = serve(1)[2]          # frees the one-replica engine and its pool
    engines, router, routed = serve(len(devices))
    homes = []
    for i, e in enumerate(engines):
        be = e.backend
        placed = {d for a in (be.k_pool, be.v_pool, be.slot_tokens,
                              *jax.tree.leaves(be.params))
                  for d in a.devices()}
        homes.append(placed)
        stats = devices[i].memory_stats() or {}
        routed_here = sum(1 for _, r in router.assignment_log if r == i)
        log(f"  replica {i}: {routed_here} requests, arrays on "
            f"{sorted(str(d) for d in placed)}, "
            f"bytes_in_use={stats.get('bytes_in_use', 'not reported')}")
    check(all(h == {devices[i]} for i, h in enumerate(homes)),
          "each replica's params, pool and slot tokens sit on its own device",
          failures)
    same = sum(a.generated_tokens == b.generated_tokens
               for a, b in zip(base, routed))
    log(f"  greedy streams equal to the one-replica run: {same}/{len(base)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-replica router phase")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal at reduced width; prints no result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.smoke and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing served",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import (CACHE_LEN, MAX_BATCH, PROMPT_LEN,
                                    build_model)

    log(f"compile cache: {use_compile_cache()}")
    limit = (dev.memory_stats() or {}).get("bytes_limit", "not reported")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"bytes_limit={limit}")
    sizes = SMOKE_SIZES if args.smoke else (MAX_BATCH, CACHE_LEN, PROMPT_LEN)
    t0 = time.perf_counter()
    cfg, params = build_model("llama3_2_3b", smoke=args.smoke, seed=args.seed)
    jax.block_until_ready(params)
    log(f"model: {cfg.arch_id} {cfg.num_layers}L d{cfg.d_model} "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads vocab {cfg.vocab_size} "
        f"{cfg.dtype}, max_batch={sizes[0]} cache_len={sizes[1]} "
        f"prompt_len={sizes[2]}; weights in "
        f"{time.perf_counter() - t0:.1f} s")

    failures: list = []
    if args.chips == 4:
        replicas_phase(cfg, params, sizes, devices[:4], args.seed, failures)
    else:
        serving_phase(cfg, params, sizes, args.smoke, args.seed, failures)
        kernel_phase(cfg, sizes, args.smoke, args.seed, failures)
    if failures:
        print(f"chip_smoke: FAILED: {failures}", file=sys.stderr)
        return 1
    if args.smoke:
        log("rehearsal passed (no result line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
