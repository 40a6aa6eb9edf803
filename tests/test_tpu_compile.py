"""The main-path Pallas kernels, and the scheduler's paged programs
around them, compile for a TPU v5e at stablelm-1.6b widths.

Interpret mode (every other kernel test) cannot see what only the TPU
compiler refuses: block shapes off its (8, 128) tiling, VMEM overruns.
These tests hand the kernels shapes placed on a *described* v5e chip
(``jax.experimental.topologies``) and compile them; nothing runs.  The
topology is described inside a module fixture, never at import time, so
every test worker collects the same tests.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.masked_sample.kernel import (masked_argmax_pallas,
                                                masked_argmax_pallas_packed)
from repro.models import build_model, kvcache
from repro.serving.scheduler import _scatter_row_paged

# stablelm-1.6b: 32 KV heads of 64, vocabulary 100,352; serving with 8
# slots, 64-token pages and max_len 1024
B, G, D, PAGE, MAX_LEN, V = 8, 32, 64, 64, 1024, 100352
N_PAGES = B * (MAX_LEN // PAGE) + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so write none
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("s_win", [1, 5])
def test_paged_decode_attention_compiles(one_chip, s_win):
    """Plain decode (S=1) and a speculative verify window (S=5) over the
    paged pool, BLOCK_T == page_size."""
    fn = functools.partial(decode_attention_pallas, interpret=False)
    _compile(lambda q, k, v, ln, tbl: fn(q, k, v, ln, block_tables=tbl),
             _spec(one_chip, (B, s_win, G, 1, D), jnp.bfloat16),
             _spec(one_chip, (N_PAGES, PAGE, G, D), jnp.bfloat16),
             _spec(one_chip, (N_PAGES, PAGE, G, D), jnp.bfloat16),
             _spec(one_chip, (B,), jnp.int32),
             _spec(one_chip, (B, MAX_LEN // PAGE), jnp.int32))


def test_dense_decode_attention_compiles(one_chip):
    """Contiguous per-row stripes: the default BLOCK_T shrinks until one
    all-heads K/V block fits the VMEM budget."""
    fn = functools.partial(decode_attention_pallas, interpret=False)
    _compile(fn,
             _spec(one_chip, (B, 1, G, 1, D), jnp.bfloat16),
             _spec(one_chip, (B, MAX_LEN, G, D), jnp.bfloat16),
             _spec(one_chip, (B, MAX_LEN, G, D), jnp.bfloat16),
             _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("v", [403, V])
def test_packed_masked_argmax_compiles(one_chip, v):
    """A vocabulary inside one tile (403: the in-repo BPE's size class)
    and the published 100,352 with a padded tail tile."""
    fn = functools.partial(masked_argmax_pallas_packed, interpret=False)
    _compile(fn, _spec(one_chip, (B, v), jnp.float32),
             _spec(one_chip, (B, -(-v // 32)), jnp.uint32))


def test_dense_mask_argmax_compiles(one_chip):
    """An int8 (B, V) mask is packed on device and takes the same
    kernel."""
    fn = functools.partial(masked_argmax_pallas, interpret=False)
    _compile(fn, _spec(one_chip, (B, V), jnp.float32),
             _spec(one_chip, (B, V), jnp.int8))


# -- the scheduler's paged programs -------------------------------------------

# the benchmark's deployment: 16 slots, 280 pages of 64 tokens, max_len
# 2048; a few layers (the stack is one scanned group); (d_model, heads,
# KV heads, d_ff, vocabulary) as published
SLOTS, POOL_PAGES, SERVE_LEN = 16, 280, 2048
WIDTHS = {"stablelm-1.6b": (2048, 32, 32, 5632, 100352),
          "yi-34b": (7168, 56, 8, 20480, 64000)}
POOL_OPS = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* ([\w-]+)\(")


@pytest.fixture
def tpu_programs(monkeypatch):
    """The program picks the kernels' interpret mode from
    ``jax.default_backend()``, which sees this host's CPU: compile the
    chip's branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _serving(sharding, widths, n_layers=4):
    d, nq, nkv, f, v = widths
    cfg = ModelConfig(arch_id="compile", family="dense", n_layers=n_layers,
                      d_model=d, n_heads=nq, n_kv_heads=nkv, d_ff=f,
                      vocab_size=v, group=("attn",), dtype="bfloat16",
                      max_seq_len=2 * SERVE_LEN, use_pallas_kernels=True)
    m = build_model(cfg)

    def place(tree):
        return jax.tree.map(
            lambda a: _spec(sharding, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    cache = m.cache_spec(SLOTS, SERVE_LEN, page_size=PAGE,
                         n_pages=POOL_PAGES)
    cache["len"] = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    return m, params, place(cache)


def _pool_traffic(compiled, cache):
    """Instructions that copy, slice or write back a whole pool leaf or
    one layer of it, whatever its layout (the per-layer scatter of the
    new tokens is not among them)."""
    leaf = cache["group"]["b0"]["k"].shape
    shapes = {leaf, leaf[1:], (leaf[0] * leaf[1],) + leaf[2:]}
    found = []
    for line in compiled.as_text().splitlines():
        m = HLO_OP.match(line)
        if m and m.group(2) in POOL_OPS and tuple(
                int(x) for x in m.group(1).split(",") if x) in shapes:
            found.append(line.strip()[:160])
    return found


def _pool_bytes(cache):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache["group"]))


@pytest.mark.parametrize("widths,s_win", [("stablelm-1.6b", 1),
                                          ("stablelm-1.6b", 5),
                                          ("yi-34b", 1)])
def test_paged_decode_copies_no_pool(one_chip, tpu_programs, widths, s_win):
    """The scheduler's decode step (a verify window too) scatters into
    the donated pool in place and hands it to the kernel as is: no copy,
    slice or write-back of a pool, the pool aliased in and out, and
    every layer counted in place."""
    m, params, cache = _serving(one_chip, WIDTHS[widths])
    decode = jax.jit(m.decode_step, donate_argnums=(1,))
    compiled = decode.lower(
        params, cache, _spec(one_chip, (SLOTS, s_win), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _pool_traffic(compiled, cache) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        _pool_bytes(cache)
    assert m.kv_inplace_layers == 4


def test_paged_scatter_copies_no_pool(one_chip, tpu_programs):
    """Admission writes a prefilled row's pages into the donated pool in
    place."""
    m, _, cache = _serving(one_chip, WIDTHS["stablelm-1.6b"])
    row = m.cache_spec(1, SERVE_LEN)
    row = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), row)
    scatter = jax.jit(functools.partial(_scatter_row_paged, page_size=PAGE),
                      donate_argnums=(0,))
    compiled = scatter.lower(
        cache, row, _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (SERVE_LEN // PAGE,), jnp.int32)).compile()
    assert _pool_traffic(compiled, cache) == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        _pool_bytes(cache)


@pytest.mark.parametrize("widths", ["stablelm-1.6b", "yi-34b"])
def test_inplace_pool_is_row_major_on_the_chip(one_chip, widths):
    """The chip lays a pool out by its shape.  With d_head 64 as its last
    axis it puts the page axis minor, to spare lane padding, and the
    kernel would need a relayout; the in-place pool's last axis is
    d_head in whole lanes, which the chip keeps row-major, the kernel's
    order, at d_head 64 and 128 alike."""
    m, _, cache = _serving(one_chip, WIDTHS[widths], n_layers=2)
    leaf = cache["group"]["b0"]["k"]
    assert leaf.shape[-1] == kvcache.pool_width(m.cfg) == 128

    def chip_order(shape):
        compiled = jax.jit(lambda a: a * 2).lower(
            _spec(one_chip, shape, leaf.dtype)).compile()
        return compiled.input_formats[0][0].layout.major_to_minor

    row_major = tuple(range(leaf.ndim))
    assert chip_order(leaf.shape) == row_major
    unpadded = leaf.shape[:-1] + (m.cfg.d_head,)
    assert (chip_order(unpadded) == row_major) == (m.cfg.d_head == 128)
