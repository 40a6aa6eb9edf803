"""The main-path Pallas kernels compile for a TPU v5e at stablelm-1.6b
widths.

Interpret mode (every other kernel test) cannot see what only the TPU
compiler refuses: block shapes off its (8, 128) tiling, VMEM overruns.
These tests hand the kernels shapes placed on a *described* v5e chip
(``jax.experimental.topologies``) and compile them; nothing runs.  The
topology is described inside a module fixture, never at import time, so
every test worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.masked_sample.kernel import (masked_argmax_pallas,
                                                masked_argmax_pallas_packed)

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
