"""The check that decides ``correct`` fails when it should: the float8
control fails the logit-gap limit, and a run whose timed path is broken
underneath comes out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.arch.dense_gqa import Reference, _embed
from bench.reference import served_sequence
from bench.traffic import generator
from repro.serving.engine import RealBackend

CELL = "nemo4-15b.pars_mixed"
SEED = 2 ** 31 + 99


def _served_sample(cell, seed):
    specs = generator.trace(cell.mix, cell.cell["rate_per_s"], 4.0, seed)
    for s in specs:
        s.true_length = min(s.true_length,
                            cell.config["serving"]["max_output_tokens"])
    sess = harness.Session(cell, seed, specs, smoke=True)
    reqs, _ = sess.serve(specs, 4.0)
    done = [r for r in reqs if r.finish_time is not None]
    sample = [(r.prompt, list(r.generated_tokens))
              for r in harness._sample(done, seed)]
    sess.close()
    return sample, sess.prompt_len


def test_reference_draws_the_programs_weights():
    cell = harness.smoke_cell(harness.load_cell(CELL))
    c = cell.config
    w = weights.make(c, SEED)
    ref = Reference(c, SEED)
    seq, first = served_sequence("explain topic3 w1 w2", [5, 6, 7],
                                 c["vocab_size"], 128)
    emb = np.asarray(w["embed"], np.float32)[seq]
    got = np.asarray(_embed(jnp.asarray(seq), ref.salts, dm=ref.dm,
                            lay=ref.lay))
    assert np.array_equal(emb, got)
    assert first == 7 and len(seq) == 8 + 2


def test_float8_control_fails_and_the_program_passes():
    cell = harness.smoke_cell(harness.load_cell(CELL))
    sample, prompt_len = _served_sample(cell, SEED)
    limit = cell.cell["checks"]["logit_gap"]
    prog = harness.check_served(cell.config, SEED, sample, prompt_len)
    ctl = harness.check_served(cell.config, SEED, sample, prompt_len,
                               quant=True)
    assert prog["tokens"] >= cell.cell["checks"]["sample_tokens_min"]
    assert prog["gap"] <= limit < ctl["gap"]
    assert ctl["gap"] >= 3 * prog["gap"]


def _broken_decode(monkeypatch, fault):
    orig = RealBackend._paged_programs

    def programs(self, bs):
        orig(self, bs)
        decode, vocab = self._decode_paged, self.cfg.vocab_size

        def broken(params, k_pool, v_pool, *args):
            if fault == "token":           # a token altered where produced
                tokens, k_new, v_new = decode(params, k_pool, v_pool, *args)
                return (tokens + 1) % vocab, k_new, v_new
            # the step leaves its state as it was: the pools are copied
            # first, since a program that donates them deletes its inputs
            k_old, v_old = jnp.copy(k_pool), jnp.copy(v_pool)
            tokens, _, _ = decode(params, k_pool, v_pool, *args)
            return tokens, k_old, v_old
        self._decode_paged = broken
    monkeypatch.setattr(RealBackend, "_paged_programs", programs)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, tmp_path, fault):
    _broken_decode(monkeypatch, fault)
    r = harness.run(CELL, SEED, 4.0, False, smoke=True, work_dir=tmp_path)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]
