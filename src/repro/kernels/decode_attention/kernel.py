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

Grid (B * n_q, T/BLOCK_T) — the T axis is minor, so VMEM scratch (m, l,
acc) carries across cache tiles of one (row, query tile).  A long query
window (a prefix-cache hit prefilling its tail) is cut into n_q tiles of
``S_TILE`` positions, so the VMEM working set is bounded by one tile
whatever S is; the tile is the largest power of two whose working set
fits ``VMEM_BUDGET`` (``window_tile``), and a window no longer than it is
one tile, the same program as before the axis existed.  Each grid step
loads ALL G heads
of its tile, block (1, BLOCK_T, G, D): the TPU compiler requires the
last two block dims to be (8, 128)-divisible or whole, and a one-head
block (1, BLOCK_T, 1, D) is neither once G > 1.  The heads are then
walked by a static in-kernel loop, each with its own scratch slice, so
every head's arithmetic is exactly the one-head computation.  Raggedness
is handled twice over:
  * ``pl.when(j * BLOCK_T < frontier)`` skips compute on tiles fully
    beyond the query tile's frontier (lengths[b] + its last position), and
  * the K/V index maps clamp the tile index to that frontier's last live
    tile (then translate it through the block table when paged), so the
    pipeline re-addresses the same block and elides the HBM copy —
    row b moves ceil((lengths[b]+S-1)/BLOCK_T) tiles, not T/BLOCK_T.

VMEM working set per step (``_vmem_bytes``): the K/V blocks, double
buffered, plus, per window position, the q and output blocks (double
buffered, Qh padded to the sublane tile) and the f32 m, l and acc
scratch (m and l lane-padded to 128).  Dense caches cap BLOCK_T so one
K/V block stays within ``KV_BLOCK_BYTES``; paged caches use BLOCK_T ==
page_size (stablelm-1.6b, ps=64, G=32, D=64 in bf16: 256 KiB per block,
lane-padded to 512 KiB).

A device trace names plain decode (S=1) ``decode_attention_pallas`` and
a query window (S > 1: speculative verify windows, prefix-cache hit
tails) ``window_attention_pallas``.
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
# VMEM the working set of one grid step may take (v5e's scoped default is
# 16 MiB; the rest is left to the compiler's own temporaries)
VMEM_BUDGET = 8 << 20
LANES = 128
# the device trace's name for a query window of several positions
WINDOW_NAME = "window_attention_pallas"


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(s_tile: int, block_t: int, g: int, qh: int, dks, dv: int,
                itemsize: int) -> int:
    """Bytes of VMEM one grid step holds for a query tile of ``s_tile``
    positions: double-buffered K/V (and k2) blocks, double-buffered q
    (and q2) and output blocks with Qh padded to the sublane tile, and
    the f32 scratch (m and l each take a whole lane row)."""
    sub = 8 * 4 // itemsize                  # sublane tile: 16 for bf16
    kv = 2 * block_t * g * (sum(_pad(d, LANES) for d in dks)
                            + _pad(dv, LANES)) * itemsize
    qo = 2 * s_tile * g * _pad(qh, sub) * (
        sum(_pad(d, LANES) for d in dks) + _pad(dv, LANES)) * itemsize
    scratch = g * _pad(s_tile * qh, 8) * (2 * LANES + _pad(dv, LANES)) * 4
    return kv + qo + scratch


def window_tile(s_win: int, block_t: int, g: int, qh: int, dks, dv: int,
                itemsize: int) -> int:
    """Query positions per grid step: the whole window when its working
    set fits ``VMEM_BUDGET``, else the largest power of two that does."""
    size = functools.partial(_vmem_bytes, block_t=block_t, g=g, qh=qh,
                             dks=dks, dv=dv, itemsize=itemsize)
    if size(s_win) <= VMEM_BUDGET:
        return s_win
    tile = 1
    while tile * 2 < s_win and size(tile * 2) <= VMEM_BUDGET:
        tile *= 2
    return tile


def _kernel(len_ref, *rest,
            block_t: int, n_blocks: int, s_win: int, s_tile: int, n_q: int,
            g: int, qh: int, scale: float, split_k: bool, paged: bool):
    if paged:                               # block table rides the scalar
        rest = rest[1:]                     # channel; index maps consume it
    q_ref, k_ref, v_ref, *rest = rest
    if split_k:                             # second (q2, k2) score operand
        q2_ref, k2_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    i, qt = _row_and_tile(pl.program_id(0), n_q)
    j = pl.program_id(1)
    base = len_ref[i]                       # keys visible to window pos 0
    frontier = base + _tile_end(qt, s_tile, s_win) - 1  # ... to its last

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_t < frontier)        # early-exit past the frontier
    def _accumulate():
        t_idx = j * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_t), 1)
        # row r of the flattened (S_TILE*Qh) axis sits at window pos
        # qt * S_TILE + r // Qh
        first = base if n_q == 1 else base + qt * s_tile
        w_pos = jax.lax.broadcasted_iota(
            jnp.int32, (s_tile * qh, 1), 0) // qh
        valid = t_idx < first + w_pos                    # (S_TILE*Qh, BT)
        for h in range(g):                  # static: one head per pass
            q = q_ref[0, :, h].reshape(s_tile * qh, q_ref.shape[-1])
            q = q.astype(jnp.float32)                    # (S_TILE*Qh, Dk)
            k = k_ref[0, :, h].astype(jnp.float32)       # (BT, Dk)
            v = v_ref[0, :, h].astype(jnp.float32)       # (BT, Dv)

            s = jnp.dot(q, k.T)                          # (S_TILE*Qh, BT)
            if split_k:
                q2 = q2_ref[0, :, h].reshape(s_tile * qh, q2_ref.shape[-1])
                k2 = k2_ref[0, :, h].astype(jnp.float32)  # (BT, D2)
                s = s + jnp.dot(q2.astype(jnp.float32), k2.T)
            s = s * scale
            s = jnp.where(valid, s, NEG)

            m_prev = m_scr[h]                            # (S_TILE*Qh, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # explicit re-mask: on an all-masked row s - m_new == 0, and
            # the exp would count dead keys into l (divergence vs ref on
            # empty rows / skipped tiles)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)               # (S_TILE*Qh, 1)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(p, v)
            m_scr[h] = m_new

    @pl.when(j == n_blocks - 1)
    def _done():
        for h in range(g):
            out = acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, :, h] = out.reshape(
                s_tile, qh, o_ref.shape[-1]).astype(o_ref.dtype)


def _row_and_tile(i, n_q: int):
    """(row, query tile) of the first grid index; a one-tile window keeps
    the row index as it is."""
    return (i, 0) if n_q == 1 else (i // n_q, i % n_q)


def _tile_end(qt, s_tile: int, s_win: int):
    """Window positions up to the end of query tile ``qt``."""
    return s_win if s_tile == s_win else jnp.minimum((qt + 1) * s_tile,
                                                     s_win)


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

    One query per row keeps this function's name in a device trace; a
    window of several is named ``window_attention_pallas`` there.
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
        tok_bytes = g * _pad(max(dk, dv), LANES) * k.dtype.itemsize
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
    dks = (dk,) + ((q2.shape[-1],) if split_k else ())
    s_tile = window_tile(s_win, block_t, g, qh, dks, dv, k.dtype.itemsize)
    n_q = -(-s_win // s_tile)
    if n_q * s_tile != s_win:              # pad the window to whole tiles;
        pad = ((0, 0), (0, n_q * s_tile - s_win)) + ((0, 0),) * 3
        q = jnp.pad(q, pad)                # pad rows' outputs are dropped
        q2 = None if q2 is None else jnp.pad(q2, pad)
    kernel = functools.partial(_kernel, block_t=block_t, n_blocks=n_blocks,
                               s_win=s_win, s_tile=s_tile, n_q=n_q, g=g,
                               qh=qh, scale=scale, split_k=split_k,
                               paged=paged)

    def last_live(i, len_ref):
        # clamp to the query tile's last live cache tile: once past its
        # frontier the block index stops changing and the pipeline skips
        # the HBM copy
        row, qt = _row_and_tile(i, n_q)
        end = _tile_end(qt, s_tile, s_win)
        return row, jnp.maximum(len_ref[row] + end - 2, 0) // block_t

    if paged:
        def kv_map(i, j, len_ref, tbl_ref):
            row, live = last_live(i, len_ref)
            page = jnp.minimum(j, live)
            return (jnp.maximum(tbl_ref[row, page], 0), 0, 0, 0)
    else:
        def kv_map(i, j, len_ref):
            row, live = last_live(i, len_ref)
            return (row, jnp.minimum(j, live), 0, 0)

    def q_map(i, j, *_):
        row, qt = _row_and_tile(i, n_q)
        return (row, qt, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, s_tile, g, qh, dk), q_map),
        pl.BlockSpec((1, block_t, g, dk), kv_map),
        pl.BlockSpec((1, block_t, g, dv), kv_map),
    ]
    operands = [lengths] + ([block_tables] if paged else []) + [q, k, v]
    if split_k:
        d2 = q2.shape[-1]
        in_specs += [pl.BlockSpec((1, s_tile, g, qh, d2), q_map),
                     pl.BlockSpec((1, block_t, g, d2), kv_map)]
        operands += [q2, k2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if paged else 1,
        grid=(b * n_q, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s_tile, g, qh, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, s_tile * qh, 1), jnp.float32),
            pltpu.VMEM((g, s_tile * qh, 1), jnp.float32),
            pltpu.VMEM((g, s_tile * qh, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_q * s_tile, g, qh, dv),
                                       q.dtype),
        interpret=interpret,
        name=None if s_win == 1 else WINDOW_NAME,
    )(*operands)
    return out[:, :s_win] if n_q * s_tile != s_win else out
