"""Ragged flash-decode GQA attention against a KV cache (Pallas TPU).

The dominant bytes-consumer of ``decode_32k`` / ``long_500k`` *and* of the
continuous-batching serving hot path: a short query window attends a
T-long cache.  Arithmetic intensity is O(1) FLOP/byte, so the kernel's job
is to stream K/V through VMEM exactly once with an online-softmax
accumulator — no (T,) score vector in HBM, no second pass — and, in a
ragged batch, to stream only the tiles each row actually owns.

Layout: q (B, S, G, Qh, Dk) where S = query window (1 for plain decode,
1+spec_s for a speculative verify window), G = n_kv heads and Qh = n_q/G
query heads per group; k (B, T, G, Dk); v (B, T, G, Dv) — Dv may differ
from Dk, and an optional second (q2, k2) operand pair adds a split score
term (absorbed-MLA scores q_lat.c_kv^T + q_rope.k_rope^T against Dv = r
latent values, streaming both caches exactly as stored).  ``lengths`` is
a per-row (B,) int32 vector (a scalar
broadcasts): query position s of row b attends keys t < lengths[b] + s,
i.e. ``lengths`` counts the keys visible to the *first* window position
and later positions extend causally one key at a time.

Paged caches: with ``block_tables`` (B, max_pages) int32, K/V are POOLS
(n_pages, page_size, G, D) shared across rows and BLOCK_T == page_size —
cache tile j of row b lives at pool row ``block_tables[b, j]``, so each
grid step gathers one page from a (generally non-contiguous) pool row
instead of slicing a contiguous stripe.  The tile's *logical* positions
are still j*BLOCK_T.., so the in-tile validity mask and the per-row
frontier early-exit are unchanged; only the HBM addresses move.  Both
scalar operands ride the scalar-prefetch channel, which is what lets the
pipeline compute the next DMA's source address from the table before the
tile is needed.

Grid (B, T/BLOCK_T) — the T axis is minor, so VMEM scratch (m, l, acc)
carries across cache tiles of one row.  Each grid step loads ALL G heads
of its tile, block (1, BLOCK_T, G, D): the TPU compiler requires the
last two block dims to be (8, 128)-divisible or whole, and a one-head
block (1, BLOCK_T, 1, D) is neither once G > 1.  The heads are then
walked by a static in-kernel loop, each with its own scratch slice, so
every head's arithmetic is exactly the one-head computation.  Raggedness
is handled twice over:
  * ``pl.when(j * BLOCK_T < lengths[b] + S - 1)`` skips compute on tiles
    fully beyond the row's frontier, and
  * the K/V index maps clamp the tile index to the row's last live tile
    (then translate it through the block table when paged), so the
    pipeline re-addresses the same block and elides the HBM copy —
    row b moves ceil((lengths[b]+S-1)/BLOCK_T) tiles, not T/BLOCK_T.

VMEM working set per step: BLOCK_T*G*(Dk+Dv) halves of K/V, double
buffered, + G*S*Qh*(Dv+2) f32 accumulators.  Dense caches cap BLOCK_T so
one K/V block stays within ``KV_BLOCK_BYTES``; paged caches use
BLOCK_T == page_size (stablelm-1.6b, ps=64, G=32, D=64 in bf16: 256 KiB
per block, lane-padded to 512 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# VMEM budget for one dense K/V block (BLOCK_T tokens x all G heads); the
# pipeline double-buffers K and V, so four such blocks are resident
KV_BLOCK_BYTES = 1 << 20


def _kernel(len_ref, *rest,
            block_t: int, n_blocks: int, s_win: int, g: int, qh: int,
            scale: float, split_k: bool, paged: bool):
    if paged:                               # block table rides the scalar
        rest = rest[1:]                     # channel; index maps consume it
    q_ref, k_ref, v_ref, *rest = rest
    if split_k:                             # second (q2, k2) score operand
        q2_ref, k2_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    base = len_ref[i]                       # keys visible to window pos 0
    frontier = base + s_win - 1             # keys visible to the last pos

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_t < frontier)        # early-exit past the frontier
    def _accumulate():
        t_idx = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1)
        # row r of the flattened (S*Qh) axis sits at window pos r // Qh
        w_pos = jax.lax.broadcasted_iota(
            jnp.int32, (s_win * qh, 1), 0) // qh
        valid = t_idx < base + w_pos                     # (S*Qh, BT)
        for h in range(g):                  # static: one head per pass
            q = q_ref[0, :, h].reshape(s_win * qh, q_ref.shape[-1])
            q = q.astype(jnp.float32)                    # (S*Qh, Dk)
            k = k_ref[0, :, h].astype(jnp.float32)       # (BT, Dk)
            v = v_ref[0, :, h].astype(jnp.float32)       # (BT, Dv)

            s = jnp.dot(q, k.T)                          # (S*Qh, BT)
            if split_k:
                q2 = q2_ref[0, :, h].reshape(s_win * qh, q2_ref.shape[-1])
                k2 = k2_ref[0, :, h].astype(jnp.float32)  # (BT, D2)
                s = s + jnp.dot(q2.astype(jnp.float32), k2.T)
            s = s * scale
            s = jnp.where(valid, s, NEG)

            m_prev = m_scr[h]                            # (S*Qh, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # explicit re-mask: on an all-masked row s - m_new == 0, and
            # the exp would count dead keys into l (divergence vs ref on
            # empty rows / skipped tiles)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (S*Qh, BT)
            corr = jnp.exp(m_prev - m_new)               # (S*Qh, 1)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(p, v)
            m_scr[h] = m_new

    @pl.when(j == n_blocks - 1)
    def _done():
        for h in range(g):
            out = acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, :, h] = out.reshape(
                s_win, qh, o_ref.shape[-1]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "interpret", "scale"))
def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            lengths: jnp.ndarray, block_t: int = 512,
                            interpret: bool = True,
                            scale: float | None = None,
                            q2: jnp.ndarray | None = None,
                            k2: jnp.ndarray | None = None,
                            block_tables: jnp.ndarray | None = None
                            ) -> jnp.ndarray:
    """q (B,S,G,Qh,Dk); k (B,T,G,Dk); v (B,T,G,Dv); lengths (B,) int32
    (scalar broadcasts) -> (B,S,G,Qh,Dv).  Window pos s of row b attends
    keys t < lengths[b] + s.

    Optional split scores: with q2 (B,S,G,Qh,D2) / k2 (B,T,G,D2) the tile
    score is (q.k^T + q2.k2^T) * scale.  Absorbed MLA uses this to run
    the latent (c_kv) and rope (k_rope) caches as-is — no per-step O(T)
    key concatenation on the host side.

    Paged: with ``block_tables`` (B, max_pages) int32, k/v (and k2) are
    pools (n_pages, page_size, G, D); BLOCK_T is forced to page_size and
    tile j of row b streams pool row block_tables[b, j].  Negative /
    vacant table entries are clamped to pool row 0 (the reserved trash
    page) — such tiles are always beyond the row's frontier, so their
    contents never reach the accumulator.
    """
    b, s_win, g, qh, dk = q.shape
    dv = v.shape[-1]
    paged = block_tables is not None
    if paged:
        block_t = k.shape[1]               # BLOCK_T == page_size
        n_blocks = block_tables.shape[1]
        block_tables = jnp.asarray(block_tables, jnp.int32)
    else:
        t = k.shape[1]
        # one token of a K block is G lane-padded rows in VMEM
        tok_bytes = g * -(-max(dk, dv) // 128) * 128 * k.dtype.itemsize
        while block_t > 8 and block_t * tok_bytes > KV_BLOCK_BYTES:
            block_t //= 2
        if t % block_t != 0:
            block_t = t
        n_blocks = t // block_t
    if scale is None:
        scale = 1.0 / (dk ** 0.5)
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    split_k = q2 is not None
    kernel = functools.partial(_kernel, block_t=block_t, n_blocks=n_blocks,
                               s_win=s_win, g=g, qh=qh, scale=scale,
                               split_k=split_k, paged=paged)

    def last_live(i, len_ref):
        # clamp to the row's last live tile: once past the frontier the
        # block index stops changing and the pipeline skips the HBM copy
        return jnp.maximum(len_ref[i] + s_win - 2, 0) // block_t

    if paged:
        def kv_map(i, j, len_ref, tbl_ref):
            page = jnp.minimum(j, last_live(i, len_ref))
            return (jnp.maximum(tbl_ref[i, page], 0), 0, 0, 0)
    else:
        def kv_map(i, j, len_ref):
            return (i, jnp.minimum(j, last_live(i, len_ref)), 0, 0)

    def q_map(i, j, *_):
        return (i, 0, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, s_win, g, qh, dk), q_map),
        pl.BlockSpec((1, block_t, g, dk), kv_map),
        pl.BlockSpec((1, block_t, g, dv), kv_map),
    ]
    operands = [lengths] + ([block_tables] if paged else []) + [q, k, v]
    if split_k:
        d2 = q2.shape[-1]
        in_specs += [pl.BlockSpec((1, s_win, g, qh, d2), q_map),
                     pl.BlockSpec((1, block_t, g, d2), kv_map)]
        operands += [q2, k2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if paged else 1,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s_win, g, qh, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, s_win * qh, 1), jnp.float32),
            pltpu.VMEM((g, s_win * qh, 1), jnp.float32),
            pltpu.VMEM((g, s_win * qh, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_win, g, qh, dv), q.dtype),
        interpret=interpret,
    )(*operands)
    return out
