"""The one traffic generator: every mix is a JSON file of parameters beside it.

A run's traffic is a pure function of (mix parameters, rate, window, seed).
The *population* of a run (every prompt length, output length, task and
arrival gap) is drawn from the mix's fixed ``population_seed``; ``--seed``
only permutes it (which task lands in which arrival slot, the order of the
gaps) and draws the filler words. So every seed offers the same work in
another order, and the spread between seeds measures the system, not the
draw. Arrival gaps are scaled to sum to the window exactly, so every seed
offers the same number of requests in the window.

Two kinds of mix:

* ``tasks``    -- independent requests: a synthetic task prompt (verb +
  topics, the length signal) followed by seeded filler words up to a
  lognormal prompt length; the output length comes from the length oracle,
  driven by the task's features.
* ``sessions`` -- tenants with a shared document prompt; conversations of a
  few turns, each turn extending the previous turn's prompt (so its KV blocks
  are a prefix-cache hit) after a think time; near-uniform output lengths.

Lengths are in the serving engine's prompt-token unit: 1 ([CLS]) + words.
Nothing here imports the program: requests come out as :class:`Spec` rows.

The task corpus and the length oracle are copied from the program's
synthetic corpus, with one change: the oracle's latent difficulty is drawn
from a stream derived from the seed, not from ``hash()``, whose value changes
with ``PYTHONHASHSEED`` between processes.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent

# Task verbs with complexity weights (reasoning-heavy verbs -> long outputs).
VERBS = [
    ("what is", -1.2), ("define", -1.0), ("name", -1.3), ("count", -0.8),
    ("translate", -0.5), ("classify", -0.6), ("summarize", 0.1),
    ("list", 0.2), ("describe", 0.4), ("explain", 0.8), ("compare", 0.9),
    ("analyze", 1.1), ("write an essay about", 1.4), ("write code for", 1.2),
    ("prove", 1.6), ("derive", 1.7), ("design a plan for", 1.3),
    ("walk me through", 1.0), ("debate", 1.2), ("brainstorm ideas about", 0.9),
]
FILLER = ("please could you kindly just quickly briefly the a an of in on "
          "for with about regarding concerning my our this that").split()
N_TOPICS = 240
#: Filler and document words are drawn from this many distinct words.
WORDS = 8192


def stream(*key) -> np.random.Generator:
    """A numpy generator keyed by any mix of ints and strings, identical in
    every process (no ``hash()``)."""
    h = hashlib.blake2b(repr(key).encode(), digest_size=16).digest()
    return np.random.default_rng(np.frombuffer(h, np.uint32))


@dataclass
class Spec:
    """One request of a run, before it becomes the program's ``Request``."""
    req_id: int
    due: float              # seconds after the window opens
    prompt: str
    prompt_tokens: int      # 1 + words
    true_length: int        # output tokens the request is served
    session: Optional[int] = None
    turn: int = 0


def load_mix(name: str) -> dict:
    return json.loads((HERE / f"{name}.json").read_text())


# --------------------------------------------------------------- tasks
def _topic_weights() -> np.ndarray:
    return stream("topic_weights").normal(0.0, 1.0, N_TOPICS)


def task(rng: np.random.Generator, tw: np.ndarray):
    """One synthetic task prompt and its latent complexity z (the corpus
    generator's clean-instruction branch)."""
    verb, wv = VERBS[rng.integers(len(VERBS))]
    ti = int(rng.integers(N_TOPICS))
    words = [f"topic{ti}"]
    z = 1.0 * wv + 0.6 * tw[ti]
    if rng.random() < 0.45:                              # secondary topic
        t2 = int(rng.integers(N_TOPICS))
        words.append(f"topic{t2}")
        z += 0.35 * tw[t2]
    words += list(rng.choice(FILLER, rng.integers(0, 5)))
    rng.shuffle(words)
    text = f"{verb} {' '.join(words)}"
    z += 0.04 * len(text.split()) + rng.normal(0.0, 0.2)  # irreducible
    return text, float(z)


def oracle_lengths(z: np.ndarray, oracle: dict, key) -> np.ndarray:
    """Output lengths: round(exp(base + slope*z + hidden + noise)), clipped.
    ``hidden`` is the per-prompt latent difficulty no predictor can see."""
    rng = stream("oracle", *key)
    hidden = rng.normal(0.0, oracle["hidden_sigma"], len(z))
    noise = rng.normal(0.0, oracle["noise_sigma"], len(z))
    n = np.round(np.exp(oracle["base"] + oracle["slope"] * z + hidden + noise))
    return np.clip(n, oracle["min"], oracle["max"]).astype(np.int64)


def task_population(mix: dict, n: int, key) -> dict:
    """``n`` tasks with prompt lengths and output lengths, from ``key``."""
    rng = stream("tasks", *key)
    tw = _topic_weights()
    texts, zs = zip(*(task(rng, tw) for _ in range(n))) if n else ((), ())
    p = mix["prompt_tokens"]
    plen = np.exp(rng.normal(np.log(p["median"]), p["sigma"], n))
    plen = np.clip(np.round(plen), p["min"], p["max"]).astype(np.int64)
    plen = np.maximum(plen, [len(t.split()) + 1 for t in texts]).astype(np.int64)
    out = oracle_lengths(np.asarray(zs), mix["output_tokens"], key)
    return {"texts": list(texts), "prompt_tokens": plen, "out": out}


def filler(rng: np.random.Generator, n_words: int) -> str:
    return " ".join(f"w{i}" for i in rng.integers(0, WORDS, n_words))


def _gaps(n: int, seconds: float, key) -> np.ndarray:
    """``n`` exponential gaps scaled so the last arrival lands at the end of
    the window (Poisson arrivals conditioned on the count)."""
    g = stream("gaps", *key).exponential(1.0, n)
    return g * (seconds / g.sum()) if n else g


def _arrivals(gaps: np.ndarray) -> np.ndarray:
    """Due times from gaps: the first at 0, the last one gap before the
    window closes."""
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) if len(gaps) else gaps


def tasks_trace(mix: dict, rate: float, seconds: float, seed: int) -> List[Spec]:
    n = int(round(rate * seconds))
    key = (mix["population_seed"], n)
    pop = task_population(mix, n, key)
    gaps = _gaps(n, seconds, key)
    rng = stream("permute", seed)
    order = rng.permutation(n)
    due = _arrivals(gaps[rng.permutation(n)])
    specs = []
    for i, j in enumerate(order):
        text = pop["texts"][j]
        extra = int(pop["prompt_tokens"][j]) - 1 - len(text.split())
        prompt = f"{text} {filler(rng, extra)}" if extra > 0 else text
        specs.append(Spec(req_id=i, due=float(due[i]),
                          prompt=prompt,
                          prompt_tokens=1 + len(prompt.split()),
                          true_length=int(pop["out"][j])))
    return specs


def training_set(mix: dict, n: int, seed: int, max_words: int):
    """Prompts and true lengths to train the ranker on: the mix's own task
    distribution (truncated to what the ranker reads), from a fixed seed."""
    pop = task_population(mix, n, ("train", seed))
    rng = stream("train_filler", seed)
    prompts = []
    for text, plen in zip(pop["texts"], pop["prompt_tokens"]):
        extra = min(int(plen) - 1, max_words) - len(text.split())
        prompts.append(f"{text} {filler(rng, extra)}" if extra > 0 else text)
    return prompts, pop["out"]


# ------------------------------------------------------------ sessions
def sessions_trace(mix: dict, rate: float, seconds: float,
                   seed: int) -> List[Spec]:
    """Conversations start as a Poisson stream at ``rate`` conversations/s
    (early enough that their last turn is due in the window); turn k+1 is
    due a think time after turn k and extends its prompt with the answer's
    echo and a new user message."""
    s = mix["sessions"]
    n_conv = int(round(rate * seconds))
    key = (mix["population_seed"], n_conv)
    prng = stream("sessions", *key)
    turns = prng.integers(s["turns"][0], s["turns"][1] + 1, n_conv)
    user = [prng.integers(s["user_tokens"][0], s["user_tokens"][1] + 1, t)
            for t in turns]
    think = [prng.uniform(s["think_s"][0], s["think_s"][1], t) for t in turns]
    outs = [prng.integers(s["output_tokens"][0], s["output_tokens"][1] + 1, t)
            for t in turns]
    tenant = prng.integers(0, s["tenants"], n_conv)
    # conversations start early enough that every turn is due in the window
    span = (s["turns"][1] - 1) * s["think_s"][1]
    gaps = _gaps(n_conv, max(seconds - span, seconds / 2), key)

    rng = stream("permute", seed)
    docs = [filler(rng, s["doc_tokens"] - 1) for _ in range(s["tenants"])]
    order = rng.permutation(n_conv)
    starts = _arrivals(gaps[rng.permutation(n_conv)])
    specs: List[Spec] = []
    for c, j in enumerate(order):
        prompt, due = docs[tenant[j]], float(starts[c])
        for k in range(turns[j]):
            if k:
                due += float(think[j][k])
                prompt += " " + filler(rng, int(outs[j][k - 1]))  # answer echo
            prompt += " " + filler(rng, int(user[j][k]))
            specs.append(Spec(req_id=0, due=due, prompt=prompt,
                              prompt_tokens=1 + len(prompt.split()),
                              true_length=int(outs[j][k]), session=c,
                              turn=k))
    specs.sort(key=lambda r: (r.due, r.session, r.turn))
    for i, r in enumerate(specs):
        r.req_id = i
    return specs


KINDS = {"tasks": tasks_trace, "sessions": sessions_trace}


def trace(mix: dict, rate: float, seconds: float, seed: int) -> List[Spec]:
    """The run's requests, sorted by due time."""
    return KINDS[mix["kind"]](mix, rate, seconds, seed)


def summary(specs: Sequence[Spec]) -> Dict[str, float]:
    p = np.asarray([r.prompt_tokens for r in specs])
    o = np.asarray([r.true_length for r in specs])
    return {"requests": len(specs), "prompt_median": float(np.median(p)),
            "prompt_max": int(p.max()), "output_median": float(np.median(o)),
            "output_max": int(o.max()), "output_tokens": int(o.sum())}
