"""Shape-based work and bytes, and the peak table."""
import json
from pathlib import Path

import pytest

from bench import roofline
from bench.arch import dense_gqa

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
GB = 1e9


def shapes(name):
    return dense_gqa.shapes(
        json.loads((CONFIGS / f"{name}.json").read_text()))


@pytest.mark.parametrize("name,layers,table,total,decode,kv", [
    ("nemo4-15b", 3.12, 6.29, 9.41, 6.27, 16384),
    ("cmdr-32b", 7.05, 4.19, 11.24, 11.24, 20480),
])
def test_weights_decode_bytes_and_kv(name, layers, table, total, decode, kv):
    s = shapes(name)
    w = s.weight_bytes()
    assert w["layers"] / GB == pytest.approx(layers, abs=0.006)
    assert w["embedding_and_head"] / GB == pytest.approx(table, abs=0.006)
    assert w["total"] / GB == pytest.approx(total, abs=0.006)
    assert s.decode([])["bytes"] / GB == pytest.approx(decode, abs=0.006)
    assert s.kv_bytes_per_token == kv
    # each live KV token of a row adds its bytes to a decode step
    assert s.decode([100])["bytes"] - s.decode([0])["bytes"] == 100 * kv


@pytest.mark.parametrize("name,gflop", [("nemo4-15b", 3.1), ("cmdr-32b", 7.0)])
def test_prefill_flops_per_token(name, gflop):
    s = shapes(name)
    per_token = s.prefill(0, 1, emits=False)
    assert per_token / 1e9 == pytest.approx(gflop, rel=0.02)
    # causal attention: the second half of a chunk sees more keys
    assert s.prefill(0, 256, False) < s.prefill(256, 512, False)
    assert s.prefill(0, 8, True) - s.prefill(0, 8, False) == s.head_flops()


def test_peaks_known_and_unknown():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    t, bound = roofline.roofline_seconds(1e12, 8.19e9, p)
    assert bound == "bytes" and t == pytest.approx(0.01)
