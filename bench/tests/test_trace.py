"""The reduction from a profiler trace to busy time, idle gaps and shares."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr


def test_union_and_gaps_of_a_hand_built_list():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 7.0)]
    assert tr.union(ivs) == [(0.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert tr.busy_seconds(ivs) == pytest.approx(4.0)
    assert tr.gaps(tr.union(ivs), 0.0, 8.0) == [(2.0, 3.0), (4.0, 6.0),
                                                (7.0, 8.0)]
    spans = [("bench.step", 1.5, 5.0), ("bench.scorer", 4.2, 5.5)]
    assert tr.name_gap((4.0, 6.0), spans) == "bench.scorer"
    assert tr.name_gap((2.0, 3.0), spans) == "bench.step"
    assert tr.name_gap((7.0, 8.0), spans) == "no span"


def test_reduce_events_of_a_known_device_timeline():
    ev = {"devices": {"/device:TPU:0": {
        "ops": [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("a", 3.0, 4.0)],
        "modules": [("jit__decode_paged(1)", 0.0, 2.0),
                    ("jit__prefill_bucket(2)", 3.0, 4.0)]}},
        "spans": [("bench.step", 0.0, 5.0), ("bench.scorer", 2.1, 2.9)]}
    red = tr.reduce_events(ev)
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["window_s"] == pytest.approx(5.0)
    assert red["idle_share"] == pytest.approx(40.0)
    assert tr.module_seconds(red["modules"], "decode_paged") == pytest.approx(2.0)
    assert red["breakdown"]["device_ops"][0] == ("a", pytest.approx(2.0))
    names = [n for n, _ in red["breakdown"]["idle_gaps"]]
    assert names == ["bench.scorer", "bench.step"]
    cut = tr.reduce_events(ev, clip_s=2.5)
    assert cut["busy_s"] == pytest.approx(2.0)
    assert cut["window_s"] == pytest.approx(2.5)
    assert cut["idle_share"] == pytest.approx(20.0)
    assert tr.module_seconds(cut["modules"], "prefill_bucket") == 0.0
    assert tr.reduce_events({"devices": {}, "spans": []}) is None


def test_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.decode"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.scorer"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert files
    ev = tr.events(files[0])
    assert {n for n, _, _ in ev["spans"]} >= {"bench.decode", "bench.scorer"}
    red = tr.reduce_events(ev)
    assert red is not None
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["idle_share"] < 100
    assert any(n == "bench.scorer" for n, _ in red["breakdown"]["idle_gaps"])


@pytest.mark.parametrize("cell,seconds,start", [
    ("nemo4-15b.pars_mixed", 36.0, 33.0),    # the window's last 3 s
    ("nemo4-15b.pars_mixed", 6.0, 3.0),
    ("cmdr-32b.doc_sessions", 36.0, 16.2),   # the cell file's share
])
def test_trace_start(cell, seconds, start):
    from bench import harness
    assert harness.trace_start(harness.load_cell(cell), seconds) == \
        pytest.approx(start)
