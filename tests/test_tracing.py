"""The program's own spans and counters (``repro.tracing``, ``RealBackend``
counters, ``Request.first_admit_time``)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_smoke_config
from repro.core.scheduler.policies import make_policy, oracle_sjf
from repro.core.scheduler.request import Request
from repro.core.scheduler.scheduler import Scheduler
from repro.models import transformer as tfm
from repro.serving import ServingConfig
from repro.serving.engine import Engine
from repro.serving.simulator import CostModel, simulate


def _prompt(words: int) -> str:
    """A prompt the engine encodes to ``words + 1`` ids (CLS + one per word)."""
    return " ".join(f"w{i}" for i in range(words))


def _reqs(words, base: int = 0):
    return [Request(base + i, _prompt(w), 0.0, w + 1, 6 + 3 * i)
            for i, w in enumerate(words)]


@pytest.fixture(scope="module")
def served():
    """One small engine serving a burst with tracing off, then another
    burst with tracing on, counting from outside the calls the spans
    should match."""
    cfg = get_smoke_config("llama3_2_3b").replace(dtype="float32",
                                                  vocab_size=2048)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    policy = make_policy(
        "pars", lambda ps: np.asarray([len(p) for p in ps], np.float32))
    eng = Engine(cfg, params, Scheduler(policy=policy, max_batch=4),
                 cache_len=64, prompt_len=16,
                 config=ServingConfig(prefill_chunk_tokens=8,
                                      rerank_every_steps=2))
    tracing.reset()
    eng.submit(_reqs([3, 9]))
    eng.run()
    off = tracing.snapshot()

    core, be = eng.core, eng.backend
    seen = types.SimpleNamespace(steps=0, decodes=0, active=0, real=0)
    step, decode, prefill = core.step, be.decode, be.prefill

    def step_w(now):
        seen.steps += 1
        return step(now)

    def decode_w(now):
        seen.decodes += 1
        seen.active += sum(r is not None and core.decode_ready(r)
                           for r in be.slot_req)
        return decode(now)

    def prefill_w(chunks, now):
        seen.real += sum(len(be._prompt_ids(r)[s:e]) for r, s, e in chunks)
        return prefill(chunks, now)

    core.step, be.decode, be.prefill = step_w, decode_w, prefill_w
    before = {k: getattr(be, k) for k in ("decode_rows", "decode_rows_padded",
                                          "prefill_tokens",
                                          "prefill_pad_tokens")}
    with tracing.recording():
        eng.submit(_reqs([3, 9, 12, 2, 5, 14], base=100))
        eng.run()
    core.step, be.decode, be.prefill = step, decode, prefill
    grew = {k: getattr(be, k) - v for k, v in before.items()}
    return types.SimpleNamespace(eng=eng, off=off, on=tracing.snapshot(),
                                 seen=seen, grew=grew)


def test_off_span_is_one_shared_object_and_serving_records_nothing(served):
    assert not tracing._TRACER.on
    assert tracing.span("a") is tracing.span("b")
    assert served.off == {}


def test_a_serve_gives_one_span_per_call(served):
    on, seen = served.on, served.seen
    assert on["serve.step"]["n"] == seen.steps > 0
    assert on["engine.decode"]["n"] == seen.decodes > 0
    assert {"serve.deliver", "sched.schedule", "sched.rerank", "pred.score",
            "engine.prefill", "engine.prefill.sync",
            "engine.decode.sync"} <= set(on)
    assert on["engine.decode.sync"]["n"] <= on["engine.decode"]["n"]
    children = sum(on[k]["s"] for k in ("sched.schedule", "sched.rerank",
                                        "engine.prefill", "engine.decode"))
    step = on["serve.step"]
    assert 0.0 <= step["self_s"] <= step["s"]
    assert step["self_s"] == pytest.approx(step["s"] - children, abs=1e-6)


def test_decode_and_prefill_counters_add_up(served):
    grew, seen = served.grew, served.seen
    assert grew["decode_rows"] - grew["decode_rows_padded"] == seen.active
    assert grew["decode_rows_padded"] > 0
    assert grew["prefill_tokens"] - grew["prefill_pad_tokens"] == seen.real


@pytest.mark.parametrize("words, positions, pad", [
    # ids 3, 5, 7 in bucket 8: one dispatch of 4 lanes x 8
    ([2, 4, 6], 32, 32 - 15),
    # ids 10, 13 in bucket 16: one dispatch of 2 lanes x 16
    ([9, 12], 32, 32 - 23),
])
def test_prefill_pad_counters_match_a_hand_count(served, words, positions,
                                                 pad):
    be = served.eng.backend
    reqs = _reqs(words, base=1000)
    t0, p0 = be.prefill_tokens, be.prefill_pad_tokens
    be.prefill([(r, 0, be.prefill_total(r)) for r in reqs], now=0.0)
    for r in reqs:
        be.release(r)
    assert (be.prefill_tokens - t0, be.prefill_pad_tokens - p0) == \
        (positions, pad)


def _fake_clock(monkeypatch):
    clock = types.SimpleNamespace(t=0.0)
    monkeypatch.setattr(tracing, "time",
                        types.SimpleNamespace(perf_counter=lambda: clock.t))

    def tick(dt):
        clock.t += dt
    return tick


def _flat(tick):
    with tracing.span("a"):
        tick(2.0)


def _nested(tick):
    with tracing.span("a"):
        tick(1.0)
        with tracing.span("b"):
            tick(2.0)
        tick(1.0)


def _repeated(tick):
    with tracing.span("a"):
        for dt in (1.0, 2.0):
            with tracing.span("b"):
                tick(dt)
        with tracing.span("c"):
            tick(0.5)
            with tracing.span("b"):
                tick(3.0)


@pytest.mark.parametrize("body, want", [
    (_flat, {"a": (1, 2.0, 2.0)}),
    (_nested, {"a": (1, 4.0, 2.0), "b": (1, 2.0, 2.0)}),
    (_repeated, {"a": (1, 6.5, 0.0), "b": (3, 6.0, 6.0),
                 "c": (1, 3.5, 0.5)}),
])
def test_spans_count_total_and_self_seconds(monkeypatch, body, want):
    tick = _fake_clock(monkeypatch)
    with tracing.recording():
        body(tick)
    got = {k: (v["n"], v["s"], v["self_s"])
           for k, v in tracing.snapshot().items()}
    assert got == {k: (n, pytest.approx(s), pytest.approx(self_s))
                   for k, (n, s, self_s) in want.items()}


def test_a_compile_is_counted_under_its_span():
    with tracing.recording():
        with tracing.span("compiles"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
        with tracing.span("warm"):
            jnp.ones(7).block_until_ready()
    snap = tracing.snapshot()
    assert snap["compiles"]["compiles"] >= 1
    assert snap["warm"]["compiles"] == 0


def test_first_admit_time_survives_preemption():
    """A long job alone at t=0 is admitted, preempted by short jobs that
    arrive at t=1, and admitted again: its first admission stays t=0."""
    reqs = [Request(0, "p0", 0.0, 8, 1000)] + \
        [Request(i, f"p{i}", 1.0, 8, 5) for i in range(1, 6)]
    cost = CostModel(iter_base_s=0.01, per_seq_s=0.0, prefill_per_token_s=0.0)
    sched = Scheduler(policy=oracle_sjf(), max_batch=1, preemption=True)
    fin = {r.req_id: r for r in simulate(reqs, sched, cost=cost)}
    long = fin[0]
    assert long.preempt_count >= 1
    assert long.first_admit_time == 0.0
    assert long.start_time > long.first_admit_time
    assert all(fin[i].first_admit_time >= 1.0 for i in range(1, 6))
