"""The FLOP and byte functions against counts made by hand for the two
cells' shapes."""

import json

import flops
from conftest import ROOT


def config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def test_model_flops_per_token_stablelm():
    m = config("stablelm-3b.l4")
    # per layer: q, k, v, o 4 x 2560^2 = 26,214,400; SwiGLU 3 x 2560 x 6912 = 53,084,160
    per_layer = 26_214_400 + 53_084_160
    proj = 50_304 * 2560  # untied head; the embedding is a gather
    assert flops.matmul_params(m) == 4 * per_layer + proj == 445_972_480
    attn = 6 * 4 * 2048 * 32 * 80  # causal: S/2 keys, 2 products fwd, 4 bwd
    assert flops.model_flops_per_token(m, 2048) == 6 * 445_972_480 + attn


def test_model_flops_per_token_phi4():
    m = config("phi4-mini.vocab4-share.l5")
    # q 3072x3072, k and v 3072x1024 each, o 3072x3072; SwiGLU 3 x 3072 x 8192
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
    assert per_layer == 100_663_296
    assert flops.matmul_params(m) == 5 * per_layer + 50_016 * 3072
    assert flops.model_flops_per_token(m, 2048) == (
        6 * (5 * per_layer + 50_016 * 3072) + 6 * 5 * 2048 * 24 * 128)


def test_flash_counts_cell1():
    B, H, KV, S, D = 2, 32, 32, 2048, 80
    pairs = 2048 * 2049 // 2
    f = flops.flash_fwd(B, H, KV, S, D)
    assert f["flops"] == 4 * B * H * D * pairs
    # q, k, v and o in bf16, lse in f32
    assert f["bytes"] == 2 * B * S * D * (32 + 64) + 2 * B * H * S * D + 4 * B * H * S
    b = flops.flash_bwd(B, H, KV, S, D)
    assert b["flops"] == 10 * B * H * D * pairs
    assert b["bytes"] == 2 * B * H * S * D * 7 + 8 * B * H * S


def test_flash_counts_cell2_gqa():
    # GQA: the forward reads 8 kv heads, the backward takes them expanded to 24
    f = flops.flash_fwd(2, 24, 8, 2048, 128)
    assert f["bytes"] == 2 * 2 * 2048 * 128 * (24 + 16) + 2 * 2 * 24 * 2048 * 128 + 4 * 2 * 24 * 2048
    b = flops.flash_bwd(2, 24, 8, 2048, 128)
    assert b["flops"] == 10 * 2 * 24 * 128 * (2048 * 2049 // 2)


def test_least_seconds_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds({"flops": 197e12, "bytes": 0.0}, peak) == 1.0
    assert flops.least_seconds({"flops": 0.0, "bytes": 2 * 819e9}, peak) == 2.0
