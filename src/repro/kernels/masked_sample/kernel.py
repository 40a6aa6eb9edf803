"""Fused grammar-mask + argmax over the vocabulary (Pallas TPU).

This is where DOMINO touches the accelerator: Algorithm 1 line 7-8
(``v' = m . v; t = decode(v')``).  The naive implementation materializes
the masked logits (B, V) in HBM — 2 extra |V|-sized HBM round trips per
step per sequence (1 MiB at gemma3's V=262144 fp32).  The fused kernel
streams logits tiles HBM->VMEM once, applies the mask in-register and
keeps a running (max, argmax) in VMEM scratch across vocabulary tiles.

The mask operand is the packed uint32 ``(B, ceil(V/32))`` bitset of
``core/bitmask.py``: each vocab tile loads only ``BLOCK_V/32`` words and
unpacks them in-register (each word repeated across the 32 lanes it
governs, shifted by the lane's bit position, AND 1), fused with the
running argmax, so the host ships 8x fewer mask bytes and the unpack
never touches HBM.  A dense int8/bool mask is packed on device first
(``masked_argmax_pallas``) and takes the same kernel.

Tiling (the TPU compiler wants the last two block dims (8, 128)-divisible
or whole): a block holds ROWS rows (8, or the whole batch when B is not a
multiple of 8) by BLOCK_V tokens, so its word block is BLOCK_V/32 — a
multiple of 128 words when BLOCK_V is a multiple of ``TILE_V`` = 4096.
Compiled calls round BLOCK_V up to that; a vocabulary smaller than one
tile is a single whole-row block.  Outputs and scratch are (ROWS, 1)
columns.

Tail tiles: when ``v % block_v != 0`` the operands are padded up to the
next tile boundary (logits to NEG, mask to 0) instead of collapsing to a
single whole-vocabulary tile — ``block_v = v`` at real vocab sizes
(V=262144 -> a 1 MiB+ logits tile plus mask) blows the VMEM budget.

Grid: (B / ROWS, V / BLOCK_V), sequential over the vocab axis (TPU grid
order is minor-first), so the scratch carries state between vocab tiles
of the same rows.  The masked-out value is -1e30; ties resolve to the
lowest index (matching jnp.argmax on the reference path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
WORD_BITS = 32
TILE_V = WORD_BITS * 128      # vocab tile whose word block is 128 lanes


def _pad_tail(logits: jnp.ndarray, bits: jnp.ndarray, block_v: int):
    """Pad the vocab axis up to a tile boundary: logits with NEG (never
    wins the argmax), mask words with 0 (nothing becomes legal)."""
    v = logits.shape[1]
    v_pad = -(-v // block_v) * block_v
    if v_pad != v:
        logits = jnp.pad(logits, ((0, 0), (0, v_pad - v)),
                         constant_values=NEG)
    pad_words = v_pad // WORD_BITS - bits.shape[1]
    if pad_words:
        bits = jnp.pad(bits, ((0, 0), (0, pad_words)))
    return logits, bits, v_pad


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """Dense (B, V) int8/bool mask -> packed (B, ceil(V/32)) uint32 in the
    ``core/bitmask`` layout (bit b of word w, LSB first, is token
    w*32+b; tail bits past V zero)."""
    b, v = mask.shape
    w = -(-v // WORD_BITS)
    bits = jnp.pad(mask != 0, ((0, 0), (0, w * WORD_BITS - v)))
    bits = bits.reshape(b, w, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


def _kernel_packed(logits_ref, bits_ref, idx_ref, val_ref, m_scr, i_scr, *,
                   block_v: int, n_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        i_scr[...] = jnp.zeros_like(i_scr)

    # in-register unpack: token (w, b) of this tile is bit b (LSB first)
    # of word w — repeat each word across the 32 lanes it governs, shift
    # by the lane's bit position, AND 1
    lane = jax.lax.broadcasted_iota(jnp.int32, logits_ref.shape, 1)
    words = jnp.repeat(bits_ref[...], WORD_BITS, axis=1)  # (R, BV) u32
    bit = (words >> (lane % WORD_BITS).astype(jnp.uint32)) & jnp.uint32(1)
    logits = logits_ref[...].astype(jnp.float32)           # (R, BV)
    masked = jnp.where(bit != 0, logits, NEG)
    local_max = jnp.max(masked, axis=1, keepdims=True)     # (R, 1)
    # ties to the LOWEST flat index == first argmax occurrence
    local_arg = jnp.min(jnp.where(masked == local_max, lane, block_v),
                        axis=1, keepdims=True) + j * block_v

    best = m_scr[...]
    take = local_max > best
    m_scr[...] = jnp.where(take, local_max, best)
    i_scr[...] = jnp.where(take, local_arg, i_scr[...])

    @pl.when(j == n_blocks - 1)
    def _done():
        idx_ref[...] = i_scr[...]
        val_ref[...] = m_scr[...]


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def masked_argmax_pallas_packed(logits: jnp.ndarray, bits: jnp.ndarray,
                                block_v: int = TILE_V,
                                interpret: bool = True):
    """logits (B, V) float, bits (B, ceil(V/32)) uint32 (bitmask layout,
    tail bits past V zero) -> (idx (B,), val (B,))."""
    b, v = logits.shape
    assert block_v % WORD_BITS == 0, block_v
    if not interpret:                  # word block: a multiple of 128 lanes
        block_v = -(-block_v // TILE_V) * TILE_V
    block_v = min(block_v, -(-v // WORD_BITS) * WORD_BITS)
    logits, bits, v_pad = _pad_tail(logits, bits, block_v)
    n_blocks = v_pad // block_v
    rows = 8 if b % 8 == 0 else b
    kernel = functools.partial(_kernel_packed, block_v=block_v,
                               n_blocks=n_blocks)
    idx, val = pl.pallas_call(
        kernel,
        grid=(b // rows, n_blocks),
        in_specs=[
            pl.BlockSpec((rows, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((rows, block_v // WORD_BITS), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.int32),
        ],
        interpret=interpret,
    )(logits, bits)
    return idx[:, 0], val[:, 0]


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def masked_argmax_pallas(logits: jnp.ndarray, mask: jnp.ndarray,
                         block_v: int = TILE_V,
                         interpret: bool = True):
    """logits (B, V) float, mask (B, V) int8/bool -> (idx (B,), val (B,)):
    the mask is packed on device and served by the packed kernel."""
    return masked_argmax_pallas_packed(logits, pack_mask(mask),
                                       block_v=block_v, interpret=interpret)
