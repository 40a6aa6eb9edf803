"""Model families found by name: the dense family builds what the harness
built before it, an unknown family fails at set-up naming the module it
lacks, a new family is one new file, and the weights of the dense
configurations are bit for bit what they were."""
import dataclasses
import hashlib
import json
import math
import os
import sys
import textwrap

import numpy as np
import pytest

import _tiny
import families
from harness import flops, server, weights

SEED = 3000000019
# sha256 of the tiny config's program parameters at SEED, as the harness
# drew them when fan-in was ``shape[0]`` for every leaf
TINY_PARAMS_SHA256 = ("c932dddf8d7f25d1bf7dabbfa025496415b3bae74efbaa560bdabc"
                      "10160dcb21")


def _tiny_cfg():
    with open(os.path.join(_tiny.DATA, "tiny.json")) as f:
        return json.load(f)


def test_dense_builds_the_tiny_model_as_before():
    from repro.configs.base import ModelConfig
    cfg = _tiny_cfg()
    assert families.of(cfg) is families.dense
    assert families.dense.model_config(cfg) == ModelConfig(
        arch_id="tiny", family="dense", n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=2, d_ff=256, vocab_size=16640, group=("attn",),
        rope_theta=10000.0, rms_eps=1e-05, max_seq_len=1024,
        dtype="bfloat16", use_pallas_kernels=True)
    cfg["model"]["partial_rotary_factor"] = 0.25
    with pytest.raises(ValueError, match="whole head"):
        families.dense.model_config(cfg)


def test_unknown_family_fails_at_set_up_naming_the_file():
    cfg = dict(_tiny_cfg(), family="no_such_family")
    with pytest.raises(ValueError, match=r"families/no_such_family\.py"):
        server.Server(cfg, [], None, SEED)


def test_a_new_family_is_one_new_file(tmp_path, monkeypatch):
    """A family module written to a directory of its own, on the
    package's search path, is what the server builds from and what the
    readers count with; no file of the harness changes."""
    import test_bench_metrics as tm
    from metrics import decode_attention_roofline, decode_mfu
    name = "toy_family_for_test"
    (tmp_path / f"{name}.py").write_text(textwrap.dedent('''
        import dataclasses
        from families import dense

        def model_config(cfg):
            return dataclasses.replace(dense.model_config(cfg),
                                       arch_id="toy")

        def step_flops(cfg, keys):
            return 1e9 * len(keys)

        def attention_cost(cfg, keys):
            return 0, 1e6 * len(keys)
    '''))
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    cfg = dict(_tiny_cfg(), family=name)
    try:
        assert families.of(cfg).model_config(cfg).arch_id == "toy"
        r = tm._run()
        r = dataclasses.replace(r, cell=dataclasses.replace(r.cell,
                                                            config=cfg))
        peak = tm.PEAK
        # two traced steps of two rows; two decode calls in 60 ms
        assert decode_mfu.read(r) == pytest.approx(
            100 * 2e9 * 2 / peak["bf16_flops_per_s"] / 0.060)
        # 48 kernel calls of 0.2 ms, each moving 2 MB
        assert decode_attention_roofline.read(r) == pytest.approx(
            100 * (2e6 / peak["hbm_bytes_per_s"]) / 0.0002)
    finally:
        sys.modules.pop(f"families.{name}", None)


def _sha256(params) -> str:
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves,
                             key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf.astype(np.float32))
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_dense_weights_bit_for_bit_as_before():
    from repro.models import build_model
    cfg = _tiny_cfg()
    model = build_model(families.of(cfg).model_config(cfg))
    assert _sha256(weights.program_params(model, SEED)) == TINY_PARAMS_SHA256


@pytest.mark.parametrize("shape,fan_in", [
    ((2048, 5632), 2048),          # a layer's 2-D leaf: its rows
    ((8, 2048, 1408), 2048),       # an expert leaf (E, d, f): d
    ((8, 1408, 2048), 1408),       # its down projection (E, f, d): f
    ((2048,), 2048),               # a 1-D leaf
])
def test_spread_by_contraction_size(shape, fan_in):
    scale = weights._scale("mlp/w", shape)
    want = 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in) / weights.HALF))
    assert scale == want
    # the draw's spread is within a factor sqrt(2) of 1/sqrt(fan_in)
    std = scale * weights.HALF / math.sqrt(3.0)
    assert 2 ** -0.5 <= std * math.sqrt(fan_in) <= 2 ** 0.5
    assert weights._scale("embed", shape) == 2.0 ** round(
        math.log2(math.sqrt(3.0) / weights.HALF))


@pytest.mark.parametrize("name", ["stablelm-1.6b", "yi-34b-6l"])
def test_dense_counts_are_the_shared_helpers(name):
    cfg = server.load_config(name)
    m = cfg["model"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    dh = m["hidden_size"] // nq
    keys = [500, 1500, 9000]
    fam = families.of(cfg)
    assert fam.step_flops(cfg, keys) == flops.decode_step(
        keys, m["num_hidden_layers"], m["hidden_size"], nq, nkv, dh,
        m["intermediate_size"], m["vocab_size"])
    assert fam.attention_cost(cfg, keys) == flops.decode_attention(
        keys, nq, nkv, dh)
