"""The FLOP and byte functions against hand counts at the shapes of both
benchmark configurations."""
import pytest

import _tiny  # noqa: F401  (puts bench/ on the path)
from harness import flops

STABLELM = dict(L=24, d=2048, nq=32, nkv=32, dh=64, f=5632, v=100352)
YI = dict(L=6, d=7168, nq=56, nkv=8, dh=128, f=20480, v=64000)


def test_layer_params():
    s, y = STABLELM, YI
    assert flops.layer_matmul_params(s["d"], s["nq"], s["nkv"], s["dh"],
                                     s["f"]) == 51_380_224
    # 557.8M per Yi-34B layer
    assert flops.layer_matmul_params(y["d"], y["nq"], y["nkv"], y["dh"],
                                     y["f"]) == 557_842_432


def test_decode_attention_stablelm():
    # two rows with 100 and 200 keys: 4 * 32 heads * 64 * 300 FLOPs;
    # K and V of 300 keys * 32 heads * 64 * 2 bytes, + q and out
    f, b = flops.decode_attention([100, 200], 32, 32, 64)
    assert f == 4 * 32 * 64 * 300 == 2_457_600
    assert b == 2 * 300 * 32 * 64 * 2 + 2 * 2 * 32 * 64 * 2 == 2_473_984


def test_decode_attention_yi():
    # one row, 9,000 keys: GQA reads 8 KV heads, scores 56 query heads
    f, b = flops.decode_attention([9000], 56, 8, 128)
    assert f == 258_048_000
    assert b == 36_864_000 + 28_672


def test_masked_argmax():
    f, b = flops.masked_argmax(16, 16387)
    assert f == 524_384
    assert b == 16 * (4 * 16387 + 4 * 513 + 8) == 1_081_728


@pytest.mark.parametrize("c", [STABLELM, YI], ids=["stablelm", "yi"])
def test_decode_step(c):
    keys = [500, 1500]
    per_tok = 2 * (c["L"] * flops.layer_matmul_params(
        c["d"], c["nq"], c["nkv"], c["dh"], c["f"]) + c["d"] * c["v"])
    attn = c["L"] * 4 * c["nq"] * c["dh"] * 2000
    assert flops.decode_step(keys, c["L"], c["d"], c["nq"], c["nkv"],
                             c["dh"], c["f"], c["v"]) == 2 * per_tok + attn


def test_roofline_bounds():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = flops.min_time(*flops.decode_attention([9000], 56, 8, 128),
                              peak)
    assert bound == "memory" and t == pytest.approx(36_892_672 / 819e9)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
