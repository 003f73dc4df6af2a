"""The traffic generator: reproducible across processes, lengths within
each mix's stated ranges, and prefix sharing only where the mix has it."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.reference import encode
from bench.traffic import generator

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 12345          # seeds run past 32 signed bits

DIGEST = ("import hashlib, json, sys; sys.path.insert(0, {root!r}); "
          "from bench.traffic import generator as g; "
          "t = g.trace(g.load_mix({mix!r}), 6.0, 20.0, {seed}); "
          "print(hashlib.sha256(json.dumps([vars(r) for r in t]).encode())"
          ".hexdigest())")


@pytest.mark.parametrize("mix", ["pars_mixed", "doc_sessions"])
def test_same_seed_same_trace_in_two_processes(mix):
    code = DIGEST.format(root=str(ROOT), mix=mix, seed=SEED)
    out = []
    for hashseed in ("1", "2"):
        env = {**os.environ, "PYTHONHASHSEED": hashseed}
        out.append(subprocess.run([sys.executable, "-c", code], env=env,
                                  capture_output=True, text=True,
                                  check=True).stdout.strip())
    assert out[0] == out[1] and len(out[0]) == 64


@pytest.mark.parametrize("mix", ["pars_mixed", "doc_sessions"])
def test_every_seed_offers_the_same_work(mix):
    m = generator.load_mix(mix)
    a, b = (generator.trace(m, 5.0, 30.0, s) for s in (1, SEED))
    assert [r.due for r in a] != [r.due for r in b]
    key = lambda t: sorted((r.prompt_tokens, r.true_length) for r in t)
    if mix == "pars_mixed":     # sessions draw document words per seed too
        assert key(a) == key(b)
    assert len(a) == len(b)
    assert max(r.due for r in a) < 30.0 and min(r.due for r in a) >= 0.0


def test_pars_mixed_lengths_follow_the_mix():
    m = generator.load_mix("pars_mixed")
    t = generator.trace(m, 16.0, 30.0, 3)
    p = np.asarray([r.prompt_tokens for r in t])
    o = np.asarray([r.true_length for r in t])
    pt, ot = m["prompt_tokens"], m["output_tokens"]
    assert p.min() >= pt["min"] and p.max() <= pt["max"]
    assert o.min() >= ot["min"] and o.max() <= ot["max"]
    assert 0.7 * pt["median"] <= np.median(p) <= 1.3 * pt["median"]
    assert 32 <= np.median(o) <= 72            # "median near 48"
    assert (o == ot["max"]).any()              # the heavy tail reaches the clip
    for r in t:                                # the engine's token unit
        assert len(encode(r.prompt, 256000, 4096)) == r.prompt_tokens


def test_doc_sessions_lengths_follow_the_mix():
    m = generator.load_mix("doc_sessions")
    s = m["sessions"]
    t = generator.trace(m, 2.0, 30.0, 3)
    o = np.asarray([r.true_length for r in t])
    assert o.min() >= s["output_tokens"][0] and o.max() <= s["output_tokens"][1]
    turns = {}
    for r in t:
        turns.setdefault(r.session, []).append(r)
    for conv in turns.values():
        assert s["turns"][0] <= len(conv) <= s["turns"][1]
        first = conv[0].prompt_tokens - s["doc_tokens"]
        assert s["user_tokens"][0] <= first <= s["user_tokens"][1]
        for a, b in zip(conv, conv[1:]):
            assert b.prompt.startswith(a.prompt)
            assert s["think_s"][0] <= b.due - a.due <= s["think_s"][1]


def _chains(t, block=16):
    from repro.serving.kv_cache import prefix_chunk_hashes
    return [prefix_chunk_hashes(encode(r.prompt, 256000, 4096), block)
            for r in t]


def test_sessions_share_prefix_blocks_and_tasks_do_not():
    tasks = generator.trace(generator.load_mix("pars_mixed"), 16.0, 30.0, 5)
    heads = [c[0] for c in _chains(tasks) if c]
    assert len(heads) == len(set(heads)) == len(tasks)

    sess = generator.trace(generator.load_mix("doc_sessions"), 2.0, 30.0, 5)
    chains = _chains(sess)
    seen, shared = set(), 0
    for ch in chains:
        shared += sum(1 for h in ch if h in seen)
        seen.update(ch)
    total = sum(len(ch) for ch in chains)
    assert shared / total > 0.5      # most blocks repeat an earlier prompt's
