"""The benchmark end to end on the CPU at the presets' smoke size, through
the harness's internal entry; the command itself refuses a non-TPU device."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses_on_the_cpu(tmp_path, trace):
    cell = "nemo4-15b.pars_mixed"
    r = harness.run(cell, 2 ** 31 + 7, 4.0, trace, smoke=True,
                    work_dir=tmp_path)
    assert list(r)[-1] == "checks"
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    spec = harness.load_cell(cell)
    names = {m["name"] for m in (spec.per_layer if trace else spec.end_to_end)}
    assert set(r["metrics"]) <= names
    if trace:
        assert {"sched.queue_wait_p95_s", "engine.decode_step_ms",
                "device.idle_share"} <= set(r["metrics"])
        assert 0 < r["device"]["busy_s"] < r["device"]["window_s"]
        assert r["breakdown"]["device_ops"]
    else:
        assert set(r["metrics"]) == names
        assert all(m["value"] > 0 for m in r["metrics"].values())
    json.dumps(r)


def test_command_refuses_a_cpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nemo4-15b.pars_mixed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_warm_bursts_reach_every_group_and_decode_batch():
    bursts = harness.warm_bursts({64, 128, 256, 1024}, 256, 32, 2048)
    groups = [(b[0][0], len(b)) for b in bursts[:-1]]
    assert groups == [(64, 1), (64, 2), (64, 3), (64, 4), (128, 1), (128, 2),
                      (256, 1), (1024, 1)]
    stair = bursts[-1]
    assert len(stair) == 32 and {s for s, _ in stair} == {64}
    outs = sorted(o for _, o in stair)
    assert outs == list(range(outs[0], outs[0] + 32))
    assert outs[0] > 32 * 64 // 256    # every prompt is in before one ends


def test_warm_staircase_stays_inside_the_cache():
    # doc_sessions: every prompt pads to 2048 in a 2176-token cache
    stair = harness.warm_bursts({2048}, 256, 16, 2176)[-1]
    assert len(stair) == 16 and {s for s, _ in stair} == {2048}
    assert max(o for _, o in stair) == 2176 - 2048 + 1


def test_hit_bursts_reach_every_continuation_shape():
    doc, *hits = harness.hit_bursts({2048}, 256, 16, 16)
    assert doc == [(2046, 0, 2)]
    assert all(len(set(b)) == 1 and b[0][0] + b[0][1] + 1 == 2048
               for b in hits)
    # (remainder, lanes padded to a power of two) of every dispatch that a
    # step's 256-token budget can pack from prefix-hit remainders
    pow2 = lambda n: 1 << (n - 1).bit_length()
    want = {(r, pow2(n)) for r in range(16, 257, 16)
            for n in range(1, min(256 // r, 16) + 1)}
    assert {(b[0][1], pow2(len(b))) for b in hits} == want
