"""Decode caches for every block kind — dense per-row stripes or a paged
pool with per-slot block tables.

Dense layout (all static shapes — TPU/XLA friendly):
 - full attention: k/v (B, T_max, n_kv, d_head); validity = pos < len
 - sliding window: ring buffers (B, W, n_kv, d_head) + per-row
   slot->position map (B, W)
 - MLA: the compressed latent (B, T_max, r_kv) + rope key (B, T_max, 1, dr)
 - SSM: conv state (B, K-1, C) + recurrent state (fp32)
 - cross-attention (whisper): encoder k/v, written once at prefill

Paged layout (``init_cache(..., page_size=ps)``; serving hot path): the
full-attention / MLA stripes above are replaced by a POOL shared across
all slots plus a per-slot block table:
 - full attention: k/v (n_pages, ps, n_kv, d_head) pool pages
 - MLA: ckv (n_pages, ps, r_kv) + krope (n_pages, ps, 1, dr) pool pages
 - ``cache["pages"]``: (B, max_pages) int32 block table, max_pages =
   T_max / ps.  Logical position p of row b lives at pool row
   ``pages[b, p // ps]``, offset ``p % ps``.  Every layer indexes its own
   pool arrays through the SAME table (one allocation covers the whole
   stack; scanned groups carry a leading ``reps`` axis on the pool).
 - page 0 is reserved as the trash page: unallocated table entries point
   at it, so batched decode writes from vacant slots (which feed pads and
   advance ``len`` like every row) land somewhere harmless instead of in
   a live row's storage.  Allocators hand out pages 1..n_pages-1.
Row state that is already O(W)/O(1) per row — SWA rings, SSM states,
cross-attention encoder K/V — stays dense; ``pageable(cfg)`` says whether
every cache-bearing block of an architecture can take the paged layout.

The cache for a scanned group of layers is the same pytree with a leading
``reps`` axis, so it can be fed through ``jax.lax.scan`` together with the
stacked layer params.  ``len`` is a single int32 scalar for the whole model
(batch-synchronous decoding) or an (B,) int32 vector for ragged /
continuous-batching serving.

Validity invariant: entries at positions >= len are garbage by contract —
speculative rollback rewinds ``len`` past rejected tokens, bucketed
admission prefills leave pad K/V beyond the true prompt length, and freed
serving slots keep their stale rows until the next admission scatters over
them.  Every reader masks by ``pos < len`` (the dense paths via
``k_valid``; ``kernels/decode_attention`` via its per-row length vector,
which also bounds how many cache tiles each row streams), and writers
append at ``len``, overwriting garbage first.  The paged layout extends
the invariant through the block table: position p of row b is valid iff
p < len[b] AND ``pages[b, p // ps]`` is a page currently allocated to b —
the scheduler's allocator guarantees every position below the frontier
has a live table entry, so readers still only need ``pos < len``; a page
freed by rollback/eviction may hold stale K/V, but no surviving row's
table points at it, and its next owner overwrites positions below its own
frontier before they become visible.

In-place pools (``pool_inplace``): a GQA pool that the Pallas decode
kernel reads is handed to the decode step whole.  Each layer's new
tokens are scattered into the stacked pool and the kernel reads it
through a free reshape (``models/transformer.py``), so no pool is copied,
sliced or written back.  That needs the pool in the kernel's row-major
order in HBM.  The TPU lays a pool out by its shape, and for a d_head of
64 it puts the page axis minor (``{1,4,3,2,0}``) to spare padding D to 128
lanes; every kernel call would then relayout the pool.  So an in-place
pool's last axis is d_head rounded up to whole lanes (``pool_width``):
the chip's own layout is then row-major, with the bytes the kernel's
lane-padded blocks hold anyway.  Writers zero-pad the new K/V to that
width; readers see zeros there.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

LANES = 128            # the TPU's vector lanes: a row's minor-axis tile

# block kinds whose cache can take the paged pool layout (everything the
# paged serving scheduler needs; ring/recurrent/encoder state stays dense)
PAGEABLE_KINDS = ("attn", "shared_attn", "mla", "moe")


def pageable(cfg: ModelConfig) -> bool:
    """True iff every cache-bearing block of ``cfg`` can be paged — i.e.
    the whole stack is full-attention / MLA (incl. MoE blocks, whose
    attention is one of the two).  SWA rings and SSM states are already
    O(W)/O(1) per row, and whisper's encoder K/V is written once — those
    architectures keep the dense per-row layout."""
    head, reps, group, tail = cfg.layer_program
    kinds = list(head) + list(group) + list(tail)
    return (not cfg.is_encoder_decoder
            and all(k in PAGEABLE_KINDS for k in kinds))


def pool_inplace(cfg: ModelConfig, kind: str) -> bool:
    """True iff a paged pool of block ``kind`` is updated in place by the
    decode step and read whole by the Pallas kernel: GQA attention (incl.
    a GQA MoE block's), unquantized, with the kernels on.  MLA latent
    pools, int8 pools and the jnp read keep the per-layer slice."""
    gqa = kind in ("attn", "shared_attn") or (kind == "moe"
                                              and cfg.mla is None)
    return (gqa and cfg.use_pallas_kernels
            and cfg.kv_cache_dtype != "int8")


def pool_width(cfg: ModelConfig) -> int:
    """Last axis of an in-place pool: d_head in whole lanes."""
    return -(-cfg.d_head // LANES) * LANES


def pad_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x`` zero-padded along its last axis to ``width``: K/V written to,
    or a query read against, a pool ``pool_width`` wide."""
    n = width - x.shape[-1]
    if n == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n)])


def inplace_layers(cfg: ModelConfig, cache) -> int:
    """Layers of a decode step over ``cache`` that take the in-place
    path: every layer of an in-place pool (0 for a dense cache)."""
    if "pages" not in cache:
        return 0
    head, reps, group, tail = cfg.layer_program
    return (sum(pool_inplace(cfg, k) for k in head + tail)
            + reps * sum(pool_inplace(cfg, k) for k in group))


def block_cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype, page_size: Optional[int] = None,
                     n_pages: Optional[int] = None
                     ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs for one block's cache (used by init and dry-run).

    With ``page_size`` set (pageable kinds only), K/V stripes become
    shared pool pages: leading axis ``n_pages`` instead of ``batch``,
    second axis ``page_size`` instead of ``max_len``.
    """
    nkv, dh = cfg.n_kv_heads, cfg.d_head
    quant = cfg.kv_cache_dtype == "int8"
    kv_dt = jnp.int8 if quant else dtype
    paged = page_size is not None
    lead, t_axis = (n_pages, page_size) if paged else (batch, max_len)

    def _kv(t, lead=lead):
        d = pool_width(cfg) if paged and pool_inplace(cfg, kind) else dh
        spec = {
            "k": jax.ShapeDtypeStruct((lead, t, nkv, d), kv_dt),
            "v": jax.ShapeDtypeStruct((lead, t, nkv, d), kv_dt),
        }
        if quant:
            spec["k_scale"] = jax.ShapeDtypeStruct((lead, t, nkv),
                                                   jnp.bfloat16)
            spec["v_scale"] = jax.ShapeDtypeStruct((lead, t, nkv),
                                                   jnp.bfloat16)
        return spec

    if kind in ("attn", "shared_attn"):
        return _kv(t_axis)
    if kind == "swa":
        assert not paged, "SWA ring caches stay dense (O(W) per row)"
        w = min(cfg.sliding_window or max_len, max_len)
        spec = _kv(w, lead=batch)
        # per-row slot->position map: rows of a continuous batch sit at
        # different sequence positions, so each carries its own ring state
        spec["pos"] = jax.ShapeDtypeStruct((batch, w), jnp.int32)
        return spec
    if kind == "mla":
        m = cfg.mla
        return {
            "ckv": jax.ShapeDtypeStruct((lead, t_axis, m.kv_lora_rank),
                                        dtype),
            "krope": jax.ShapeDtypeStruct(
                (lead, t_axis, 1, m.qk_rope_head_dim), dtype),
        }
    if kind == "moe":
        base = "mla" if cfg.mla is not None else "attn"
        return block_cache_spec(cfg, base, batch, max_len, dtype,
                                page_size=page_size, n_pages=n_pages)
    if kind in ("mamba1", "mamba2"):
        assert not paged, "SSM states stay dense (O(1) per row)"
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        if s.version == 1 and kind == "mamba1":
            conv_c = d_in
            state_shape = (batch, d_in, s.d_state)
        else:
            conv_c = d_in + 2 * s.n_groups * s.d_state
            nh = d_in // s.head_dim
            state_shape = (batch, nh, s.head_dim, s.d_state)
        return {
            "conv": jax.ShapeDtypeStruct((batch, s.d_conv - 1, conv_c),
                                         dtype),
            "ssm": jax.ShapeDtypeStruct(state_shape, jnp.float32),
        }
    if kind == "xattn":
        assert not paged, "encoder-decoder caches stay dense"
        spec = block_cache_spec(cfg, "attn", batch, max_len, dtype)
        spec["xk"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq_len, nkv, dh), dtype)
        spec["xv"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq_len, nkv, dh), dtype)
        return spec
    raise ValueError(kind)


def _zeros_like_spec(spec):
    def mk(s):
        if s.dtype == jnp.int32:  # slot->position maps start invalid
            return jnp.full(s.shape, -1, jnp.int32)
        return jnp.zeros(s.shape, s.dtype)
    return jax.tree.map(mk, spec)


def default_n_pages(batch: int, max_len: int, page_size: int) -> int:
    """Capacity-equivalent pool: as many tokens as ``batch`` contiguous
    stripes would hold, plus the reserved trash page.  Serving pools are
    usually sized SMALLER than this — that is the paged win."""
    return batch * (max_len // page_size) + 1


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               page_size: Optional[int] = None,
               n_pages: Optional[int] = None):
    """Concrete zero cache matching cache_spec()."""
    return jax.tree.map(lambda s: s, _cache_build(
        cfg, batch, max_len, concrete=True, page_size=page_size,
        n_pages=n_pages))


def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               page_size: Optional[int] = None,
               n_pages: Optional[int] = None):
    """ShapeDtypeStruct pytree (for .lower() in the dry-run)."""
    return _cache_build(cfg, batch, max_len, concrete=False,
                        page_size=page_size, n_pages=n_pages)


def _cache_build(cfg: ModelConfig, batch: int, max_len: int, concrete: bool,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None):
    dtype = jnp.dtype(cfg.dtype)
    head, reps, group, tail = cfg.layer_program
    if page_size is not None:
        assert pageable(cfg), \
            f"{cfg.arch_id}: not every cache-bearing block is pageable"
        assert max_len % page_size == 0, \
            f"max_len {max_len} must be a multiple of page_size {page_size}"
        if n_pages is None:
            n_pages = default_n_pages(batch, max_len, page_size)
        assert n_pages >= 2, "pool needs the trash page plus >= 1 usable"

    def one(kind):
        spec = block_cache_spec(cfg, kind, batch, max_len, dtype,
                                page_size=page_size, n_pages=n_pages)
        return _zeros_like_spec(spec) if concrete else spec

    def stacked(kind):
        spec = block_cache_spec(cfg, kind, batch, max_len, dtype,
                                page_size=page_size, n_pages=n_pages)
        spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((reps,) + s.shape, s.dtype), spec)
        return _zeros_like_spec(spec) if concrete else spec

    cache = {
        "len": (jnp.zeros((), jnp.int32) if concrete
                else jax.ShapeDtypeStruct((), jnp.int32)),
        "head": [one(k) for k in head],
        "group": {f"b{i}": stacked(k) for i, k in enumerate(group)},
        "tail": [one(k) for k in tail],
    }
    if page_size is not None:
        mp = max_len // page_size
        # table entries start at 0 = the reserved trash page, so vacant /
        # unallocated positions always resolve to a harmless pool row
        cache["pages"] = (jnp.zeros((batch, mp), jnp.int32) if concrete
                          else jax.ShapeDtypeStruct((batch, mp), jnp.int32))
    return cache


def page_size_of(cache) -> Optional[int]:
    """Static page size of a paged cache (None for dense layouts): the
    second axis of any pool leaf."""
    if "pages" not in cache:
        return None
    for part in (cache["head"], cache["tail"]):
        for blk in part:
            for v in blk.values():
                return v.shape[1]
    for blk in cache["group"].values():
        for v in blk.values():
            return v.shape[2]          # (reps, n_pages, ps, ...)
    return None
