"""The architecture seam: a configuration reaches its block only through the
module its ``"arch"`` names, so a new architecture needs no edit to a file
the benchmark already has."""
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, harness, weights
from bench.reference import served_sequence

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 4242

#: A module that wraps dense GQA and names one backend counter; its work
#: counts record the ``counts`` every decode call is given.
COUNTED = '''
from bench.arch.dense_gqa import Reference, layout, program_config, shapes as _shapes

COUNTERS = ("decode_rows",)
SEEN = []


class Counted:
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def decode(self, contexts, counts):
        SEEN.append((list(contexts), counts))
        return self.inner.decode(contexts, counts)


def shapes(c):
    return Counted(_shapes(c))
'''

#: A module whose layout draws the embedding at twice dense GQA's scale.
SCALED = '''
from bench.arch import dense_gqa
from bench.arch.dense_gqa import COUNTERS, Reference, program_config, shapes


def layout(c):
    lay = dense_gqa.layout(c)
    shape, std = lay["embed"]
    return {**lay, "embed": (shape, 2 * std)}
'''


def _tree(tmp_path: Path, module: str) -> Path:
    """A checkout of its own: BENCHMARK.json with one cell of a
    configuration whose architecture module exists only here."""
    root = tmp_path / "tree"
    (root / "bench" / "cells").mkdir(parents=True)
    (root / "arch").mkdir()
    (root / "arch" / "counted.py").write_text(module)
    c = json.loads((ROOT / "bench/configs/nemo4-15b.json").read_text())
    c["arch"] = "arch/counted.py"
    (root / "counted.json").write_text(json.dumps(c))
    shutil.copy(ROOT / "bench/cells/nemo4-15b.pars_mixed.json",
                root / "bench/cells/counted.pars_mixed.json")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{**bench["configs"][0], "name": "counted",
                         "file": "counted.json"}]
    bench["workloads"] = [{**bench["workloads"][0], "name": "counted.pars_mixed",
                           "config": "counted"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_module_in_new_files_runs_a_cell_and_gets_its_counts(
        tmp_path, monkeypatch):
    root = _tree(tmp_path, COUNTED)
    runs = []
    read = harness.read_metric

    def keep(name, run):
        runs.append(run)
        return read(name, run)
    monkeypatch.setattr(harness, "read_metric", keep)
    r = harness.run("counted.pars_mixed", SEED, 4.0, True, smoke=True,
                    work_dir=tmp_path, root=root)
    assert r["correct"], r["checks"]
    module = arch.load({"arch": str(root / "arch" / "counted.py")})
    run = runs[0]
    calls = run.probe.decode_calls
    assert any(c for c, _ in calls)
    # the probe's calls, then the trace reduction's, each with its counts:
    # the rows of one dispatch, padded to a power of two (none: no rows)
    assert module.SEEN == calls + [(c, n) for c, n in calls if c]
    for c, n in calls:
        assert n == {"decode_rows": 1 << (len(c) - 1).bit_length() if c else 0}
    # every integer counter of the backend, as changed over the window
    assert run.counters["decode_rows"] >= sum(n["decode_rows"]
                                              for _, n in calls)
    assert run.counters["max_batch"] == 0 and run.counters["prefill_tokens"] > 0


def test_weights_and_reference_draw_the_layout_the_module_gives(tmp_path):
    root = _tree(tmp_path, SCALED)
    cell = harness.smoke_cell(harness.load_cell("counted.pars_mixed", root))
    scaled = cell.config
    dense = {**scaled, "arch": "bench/arch/dense_gqa.py"}
    w2, w1 = weights.make(scaled, SEED), weights.make(dense, SEED)
    e2, e1 = (np.asarray(w["embed"], np.float32) for w in (w2, w1))
    assert np.array_equal(e2, 2 * e1)
    assert np.array_equal(np.asarray(w2["layers"]["attn"]["wq"]),
                          np.asarray(w1["layers"]["attn"]["wq"]))
    module = arch.load(scaled)
    ref = module.Reference(scaled, SEED)
    assert dict((n, (s, i)) for n, s, i in ref.lay) == {
        n: (tuple(s), i) for n, (s, i) in module.layout(scaled).items()}
    seq, _ = served_sequence("explain topic3 w1 w2", [5, 6, 7],
                             scaled["vocab_size"], 128)
    from bench.arch.dense_gqa import _embed
    got = np.asarray(_embed(jnp.asarray(seq), ref.salts, dm=ref.dm,
                            lay=ref.lay))
    assert np.array_equal(got, e2[seq])


# Work counts of dense GQA at the configurations' full sizes, as they were
# before the seam: weight bytes, KV bytes per token, decode over contexts
# (1, 100, 2047) and over none, and prefill of three chunks.
PINNED = {
    "nemo4-15b": ({"layers": 3120758784, "embedding_and_head": 6291480576,
                   "total": 9412239360}, 16384,
                  {"flops": 19010027520.0, "bytes": 6301741056},
                  {"flops": 0, "bytes": 6266511360},
                  802097725440.0, 811685904384.0, 832623869952.0),
    "cmdr-32b": ({"layers": 7046758400, "embedding_and_head": 4194336768,
                  "total": 11241095168}, 20480,
                 {"flops": 34074132480.0, "bytes": 11285135360},
                 {"flops": 0, "bytes": 11241095168},
                 1809275944960.0, 1824207667200.0, 1859104276480.0),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_counts_are_pinned(name):
    c = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    s = arch.load(c).shapes(c)
    got = (s.weight_bytes(), s.kv_bytes_per_token, s.decode([1, 100, 2047], {}),
           s.decode([], {}), s.prefill(0, 256, False), s.prefill(256, 512, True),
           s.prefill(1088, 1344, True))
    assert got == PINNED[name]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_kv_row_is_the_programs_pool_row(name):
    """The pool that ``rehearse_memory.py`` sizes from ``kv_row`` is the
    program's, and the KV bytes per token are its two pools' rows."""
    import jax
    from repro.models import transformer as tfm
    c = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    module = arch.load(c)
    cfg = module.program_config(c)
    row = jax.eval_shape(lambda: tfm.init_cache(cfg, 1, 64))["k"]
    s = module.shapes(c)
    assert (row.shape[0],) + tuple(row.shape[3:]) == s.kv_row
    assert s.kv_bytes_per_token == 2 * int(np.prod(s.kv_row)) \
        * row.dtype.itemsize
