"""The benchmark's work counts and peak table against hand-computed numbers."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import work  # noqa: E402
from bench.peaks import least_time, peaks  # noqa: E402


def _cfg(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        c = json.load(f)
    c["head_dim"] = c["hidden_size"] // c["num_attention_heads"]
    c.setdefault("sliding_window", 0)
    return c


def test_internlm2_parameters_and_kv_bytes():
    cfg = _cfg("internlm2-1.8b")
    # embed and head 92544 x 2048 each; a layer: q/o 2048x2048, k/v 2048x1024,
    # three 2048x8192 MLP matrices, two norms of 2048; a final norm
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 2 * 2048
    assert layer == 62_918_656
    assert work.param_count(cfg) == 2 * 92544 * 2048 + 24 * layer + 2048 == 1_889_110_016
    # K and V, 8 heads of 128, bf16, 24 layers
    assert work.kv_bytes_per_token(cfg) == 2 * 24 * 8 * 128 * 2 == 98_304


def test_decode_bytes_count_live_kv_not_capacity():
    cfg = _cfg("internlm2-1.8b")
    body = 24 * 62_918_656 + 92544 * 2048  # matrices read once, LM head included
    w = work.decode_step(cfg, [100, 2000])
    assert w["bytes"] == 2 * body + 2 * 2048 * 2 + (100 + 2000) * 98_304 + 2 * 98_304
    assert w["flops"] == 2 * body * 2 + 4 * 16 * 128 * 24 * (100 + 2000)
    # twice the live context reads twice the KV, whatever the pool holds
    more = work.decode_step(cfg, [200, 4000])
    assert more["bytes"] - w["bytes"] == 2100 * 98_304


def test_sliding_window_caps_what_decode_and_prefill_attend():
    cfg = _cfg("h2o-danube-1.8b")
    assert cfg["sliding_window"] == 4096
    kv = work.kv_bytes_per_token(cfg)
    assert kv == 2 * 24 * 8 * 80 * 2
    a, b = work.decode_step(cfg, [5000]), work.decode_step(cfg, [9000])
    assert a == b  # both rows see a full window
    pair = 4 * 32 * 80 * 24
    body = 24 * (2560 * 80 * (2 * 32 + 2 * 8) + 3 * 2560 * 6912 + 2 * 2560)
    n = 5000
    attended = 4096 * 4097 // 2 + (n - 4096) * 4096
    assert work.prefill(cfg, n)["flops"] == 2 * body * n + 2 * 2560 * 32000 + pair * attended


def test_roofline_picks_the_binding_bound():
    p = peaks("TPU v5 lite")
    t, bound = least_time({"flops": 197e12, "bytes": 819e9 / 2}, p)
    assert (t, bound) == (1.0, "flops")
    t, bound = least_time({"flops": 1e12, "bytes": 819e9}, p)
    assert (t, bound) == (1.0, "bytes")


def test_peaks_are_v5e_and_unknown_kind_raises():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
