"""Block assembly: every architecture family as a composition of typed
blocks, executed as head-blocks + lax.scan over a repeated (possibly
heterogeneous) group + tail-blocks.

Scanning over stacked layer params keeps compile time and HLO size flat in
depth (62-layer gemma3 compiles as one 6-block group x 10 reps), which is
what makes the 40-combination dry-run tractable; it is also the layout the
sharding rules expect (leading ``reps`` axis unsharded).

Block kinds:
  attn | swa          GQA transformer block (full / sliding-window)
  mla                 DeepSeek multi-head latent attention block
  moe                 MoE-FFN block (attention = mla if cfg.mla else GQA)
  mamba1 | mamba2     SSM blocks
  shared_attn         zamba2 shared-weight attention block (params shared
                      across invocations; per-invocation KV cache)
  xattn               encoder-decoder decoder block (self + cross attn)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import act_sharding, kvcache, ssm
from repro.models.flash import attention_any
from repro.models.layers import (attention_init, mla_apply,
                                 mla_apply_absorbed, mla_compress,
                                 mla_init, mlp_apply, mlp_init, moe_apply,
                                 moe_init, rmsnorm, rmsnorm_init, rope,
                                 _split_heads, dense_init)

Params = Dict[str, Any]


@dataclasses.dataclass
class Ctx:
    mode: str                      # 'train' | 'prefill' | 'decode'
    q_pos: jnp.ndarray             # (B, S)
    cache_len: Optional[jnp.ndarray] = None   # scalar int32, or (B,) for
    max_len: int = 0                          # per-request batched serving
    enc_out: Optional[jnp.ndarray] = None     # (B, T_enc, D) for xattn
    # paged KV serving: (B, max_pages) int32 block table — position p of
    # row b lives at pool row pages[b, p // ps], offset p % ps, where ps
    # is the (static) second axis of the pool leaves
    pages: Optional[jnp.ndarray] = None
    # in-place paged decode (kvcache.pool_inplace): the block's cache is
    # the whole stacked (reps, n_pages, ps, G, D) pool and this traced
    # scalar is its layer's index in it
    layer: Optional[jnp.ndarray] = None

    @property
    def ragged(self) -> bool:
        return self.cache_len is not None and self.cache_len.ndim == 1

    @property
    def paged(self) -> bool:
        return self.pages is not None


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def block_init(rng, cfg: ModelConfig, kind: str) -> Params:
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 6)
    if kind in ("attn", "swa", "shared_attn"):
        return {
            "norm1": rmsnorm_init(d, dt),
            "attn": attention_init(ks[0], cfg),
            "norm2": rmsnorm_init(d, dt),
            "mlp": mlp_init(ks[1], d, cfg.d_ff, dt),
        }
    if kind == "mla":
        return {
            "norm1": rmsnorm_init(d, dt),
            "mla": mla_init(ks[0], cfg),
            "norm2": rmsnorm_init(d, dt),
            "mlp": mlp_init(ks[1], d, cfg.d_ff, dt),
        }
    if kind == "moe":
        p: Params = {"norm1": rmsnorm_init(d, dt),
                     "norm2": rmsnorm_init(d, dt),
                     "moe": moe_init(ks[1], cfg)}
        if cfg.mla is not None:
            p["mla"] = mla_init(ks[0], cfg)
        else:
            p["attn"] = attention_init(ks[0], cfg)
        return p
    if kind == "mamba1":
        return {"norm": rmsnorm_init(d, dt), "mamba": ssm.mamba1_init(ks[0], cfg)}
    if kind == "mamba2":
        return {"norm": rmsnorm_init(d, dt), "mamba": ssm.mamba2_init(ks[0], cfg)}
    if kind == "xattn":
        return {
            "norm1": rmsnorm_init(d, dt),
            "attn": attention_init(ks[0], cfg),
            "norm_x": rmsnorm_init(d, dt),
            "xattn": attention_init(ks[1], cfg),
            "norm2": rmsnorm_init(d, dt),
            "mlp": mlp_init(ks[2], d, cfg.d_ff, dt),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# attention with cache plumbing
# ---------------------------------------------------------------------------


def _self_attention(p: Params, cfg: ModelConfig, xn: jnp.ndarray, ctx: Ctx,
                    cache: Optional[Params], window: Optional[int]
                    ) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Returns (attn_out (B,S,D), updated cache)."""
    nq, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b, s, _ = xn.shape
    q = _split_heads(xn @ p["wq"], nq, dh)
    q = rope(q, ctx.q_pos, cfg.rope_theta)
    k_new = _split_heads(xn @ p["wk"], nkv, dh)
    k_new = rope(k_new, ctx.q_pos, cfg.rope_theta)
    v_new = _split_heads(xn @ p["wv"], nkv, dh)
    qg = q.reshape(b, s, nkv, nq // nkv, dh)

    new_cache = cache
    if ctx.mode == "train" or cache is None:
        out = attention_any(qg, k_new, v_new, ctx.q_pos, ctx.q_pos, window)
    elif ctx.mode == "prefill":
        out = attention_any(qg, k_new, v_new, ctx.q_pos, ctx.q_pos, window)
        new_cache = _write_kv(cache, cfg, k_new, v_new, ctx, window)
    else:  # decode
        if window is None:
            new_cache = _write_kv(cache, cfg, k_new, v_new, ctx, window)
            k_all, v_all = _read_kv(new_cache, xn.dtype)
            if cfg.use_pallas_kernels:
                # fused ragged flash-decode: q (B,S,G,Qh,D) vs cache
                # (B,T,G,D) — or the (n_pages,ps,G,D) pool streamed
                # through the block table when paged; per-row lengths and
                # the S>1 speculative verify window (causal offsets) are
                # handled in-kernel, so the batched serving path never
                # takes the dense read
                from repro.kernels.decode_attention.ops import \
                    decode_attention
                tables = ctx.pages
                if ctx.layer is not None:
                    # the stacked pool read whole, a free reshape of the
                    # row-major pool: layer l's page p is row l*n_pages+p.
                    # Clamp vacant entries BEFORE the offset, or -1 would
                    # name the previous layer's last page
                    n_pages = k_all.shape[1]
                    k_all = k_all.reshape((-1,) + k_all.shape[2:])
                    v_all = v_all.reshape((-1,) + v_all.shape[2:])
                    tables = jnp.maximum(ctx.pages, 0) + ctx.layer * n_pages
                # a lane-padded pool (kvcache.pool_width): zero lanes in q
                # add nothing to a score, and the output's are dropped
                qp = kvcache.pad_lanes(qg, k_all.shape[-1])
                out = decode_attention(qp, k_all, v_all, ctx.cache_len + 1,
                                       scale=1.0 / (dh ** 0.5),
                                       block_tables=tables)[..., :dh]
            else:
                if ctx.paged:
                    # (a pool laid out for the kernel is read here too,
                    # as by chip_smoke.py's reference: drop its zero lanes)
                    from repro.kernels.decode_attention.ref import \
                        gather_pages
                    k_all = gather_pages(k_all, ctx.pages)[..., :dh]
                    v_all = gather_pages(v_all, ctx.pages)[..., :dh]
                t = k_all.shape[1]
                k_pos = jnp.broadcast_to(
                    jnp.arange(t, dtype=jnp.int32), (b, t))
                lim = (ctx.cache_len[:, None] if ctx.ragged
                       else ctx.cache_len) + s
                k_valid = k_pos < lim
                out = attention_any(qg, k_all, v_all,
                                    ctx.q_pos, k_pos, window, k_valid)
        else:
            # Ring buffer: with S_new > 1 (speculative verification) the new
            # writes may evict entries that earlier queries of this very step
            # still see, so attend over [old ring || new keys] THEN write.
            k_old, v_old = _read_kv(cache, xn.dtype)
            k_all = jnp.concatenate([k_old, k_new], axis=1)
            v_all = jnp.concatenate([v_old, v_new], axis=1)
            k_pos = jnp.concatenate([cache["pos"], ctx.q_pos], axis=1)
            k_valid = k_pos >= 0
            out = attention_any(qg, k_all, v_all, ctx.q_pos, k_pos,
                                window, k_valid)
            new_cache = _write_kv(cache, cfg, k_new, v_new, ctx, window)
    out = out.reshape(b, s, nq * dh) @ p["wo"]
    return out, new_cache


def _quantize_kv(x: jnp.ndarray):
    """(B,S,H,D) -> int8 values + (B,S,H) bf16 scales (per token, head)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def _page_translate(ctx: Ctx, b: int, s: int, page_size: int):
    """(pool row, in-page offset) index pair, both (B, S), for the S new
    tokens each row writes at positions cache_len[b]..cache_len[b]+S-1.
    Vacant table entries (<= 0) AND positions past the table's capacity
    resolve to pool row 0, the reserved trash page: padded rows of a
    batched decode and writes beyond max_len land somewhere harmless
    (the dense layout's equivalent is the scatter dropping OOB indices —
    clamping to the last table column would corrupt the row's newest
    live page instead)."""
    ln = ctx.cache_len
    ln_b = ln[:, None] if ctx.ragged else jnp.full((b, 1), ln, jnp.int32)
    pos = ln_b + jnp.arange(s, dtype=jnp.int32)[None, :]        # (B, S)
    tbl = jnp.maximum(ctx.pages, 0)                             # (B, MP)
    pidx = pos // page_size
    prow = jnp.take_along_axis(
        tbl, jnp.minimum(pidx, tbl.shape[1] - 1), axis=1)
    prow = jnp.where(pidx >= tbl.shape[1], 0, prow)
    return prow, pos % page_size


def _write_kv(cache: Params, cfg: ModelConfig, k: jnp.ndarray,
              v: jnp.ndarray, ctx: Ctx, window: Optional[int]) -> Params:
    b, s = k.shape[:2]
    ln = ctx.cache_len
    quant = "k_scale" in cache
    if quant:
        k, k_sc = _quantize_kv(k)
        v, v_sc = _quantize_kv(v)
    if ctx.paged and window is None:
        # paged pool: scatter each row's S new tokens through its block
        # table (rows own disjoint pages, so index pairs never collide
        # across live rows), into the layer's part of a stacked pool
        # when the block updates it in place
        lead = () if ctx.layer is None else (ctx.layer,)
        prow, poff = _page_translate(ctx, b, s,
                                     cache["k"].shape[len(lead) + 1])
        at = lead + (prow, poff)
        width = cache["k"].shape[-1]            # a lane-padded pool's
        out = dict(cache)
        out["k"] = cache["k"].at[at].set(kvcache.pad_lanes(k, width))
        out["v"] = cache["v"].at[at].set(kvcache.pad_lanes(v, width))
        if quant:
            out["k_scale"] = cache["k_scale"].at[at].set(k_sc)
            out["v_scale"] = cache["v_scale"].at[at].set(v_sc)
        return out
    if window is None or "pos" not in cache:
        out = dict(cache)
        if ctx.ragged:
            # per-request write offsets (batched serving): scatter rows
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            idx = ln[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            out["k"] = cache["k"].at[rows, idx].set(k)
            out["v"] = cache["v"].at[rows, idx].set(v)
            if quant:
                out["k_scale"] = cache["k_scale"].at[rows, idx].set(k_sc)
                out["v_scale"] = cache["v_scale"].at[rows, idx].set(v_sc)
            return out
        out["k"] = jax.lax.dynamic_update_slice(cache["k"], k, (0, ln, 0, 0))
        out["v"] = jax.lax.dynamic_update_slice(cache["v"], v, (0, ln, 0, 0))
        if quant:
            out["k_scale"] = jax.lax.dynamic_update_slice(
                cache["k_scale"], k_sc, (0, ln, 0))
            out["v_scale"] = jax.lax.dynamic_update_slice(
                cache["v_scale"], v_sc, (0, ln, 0))
        return out
    # Ring write, per-row: rows of a ragged batch sit at different sequence
    # positions, so write offsets and the slot->position map are (B, ...).
    w = cache["k"].shape[1]
    n = min(s, w)                      # only the last w tokens can survive
    ln_b = ln[:, None] if ctx.ragged else jnp.full((b, 1), ln, jnp.int32)
    pos_val = ln_b + (s - n) + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = pos_val % w                  # (B, n) per-row ring slots
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    out = dict(cache)
    out["k"] = cache["k"].at[rows, idx].set(k[:, -n:])
    out["v"] = cache["v"].at[rows, idx].set(v[:, -n:])
    if quant:
        out["k_scale"] = cache["k_scale"].at[rows, idx].set(k_sc[:, -n:])
        out["v_scale"] = cache["v_scale"].at[rows, idx].set(v_sc[:, -n:])
    out["pos"] = cache["pos"].at[rows, idx].set(pos_val)
    return out


def _read_kv(cache: Params, dtype):
    """Cache k/v in compute dtype (dequantizing int8 caches inline)."""
    if "k_scale" in cache:
        return (_dequantize_kv(cache["k"], cache["k_scale"], dtype),
                _dequantize_kv(cache["v"], cache["v_scale"], dtype))
    return cache["k"], cache["v"]


def _cross_attention(p: Params, cfg: ModelConfig, xn: jnp.ndarray, ctx: Ctx,
                     cache: Optional[Params]) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Decoder->encoder attention.  No rope, no causal mask."""
    nq, nkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b, s, _ = xn.shape
    q = _split_heads(xn @ p["wq"], nq, dh).reshape(b, s, nkv, nq // nkv, dh)
    new_cache = cache
    if ctx.mode in ("train", "prefill") and ctx.enc_out is not None:
        xk = _split_heads(ctx.enc_out @ p["wk"], nkv, dh)
        xv = _split_heads(ctx.enc_out @ p["wv"], nkv, dh)
        if cache is not None:
            new_cache = dict(cache)
            new_cache.update(xk=xk, xv=xv)
    else:
        xk, xv = cache["xk"], cache["xv"]
    t = xk.shape[1]
    ones_q = jnp.zeros((b, s), jnp.int32)
    ones_k = jnp.zeros((b, t), jnp.int32)  # pos 0 everywhere = no masking
    out = attention_any(q, xk, xv, ones_q, ones_k, None, None)
    return out.reshape(b, s, nq * dh) @ p["wo"], new_cache


def _mla_attention(p: Params, cfg: ModelConfig, xn: jnp.ndarray, ctx: Ctx,
                   cache: Optional[Params]) -> Tuple[jnp.ndarray, Optional[Params]]:
    b, s, _ = xn.shape
    c_kv, k_rope = mla_compress(p, cfg, xn, ctx.q_pos)
    new_cache = cache
    if ctx.mode == "train" or cache is None:
        out = mla_apply(p, cfg, xn, ctx.q_pos, (c_kv, k_rope), ctx.q_pos)
    elif ctx.mode == "prefill":
        out = mla_apply(p, cfg, xn, ctx.q_pos, (c_kv, k_rope), ctx.q_pos)
        new_cache = dict(cache)
        new_cache["ckv"] = jax.lax.dynamic_update_slice(
            cache["ckv"], c_kv, (0, ctx.cache_len, 0))
        new_cache["krope"] = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope, (0, ctx.cache_len, 0, 0))
    else:
        new_cache = dict(cache)
        if ctx.paged:
            # paged latent pool: scatter through the block table
            prow, poff = _page_translate(ctx, b, s, cache["ckv"].shape[1])
            new_cache["ckv"] = cache["ckv"].at[prow, poff].set(c_kv)
            new_cache["krope"] = cache["krope"].at[prow, poff].set(k_rope)
        elif ctx.ragged:
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            idx = ctx.cache_len[:, None] + \
                jnp.arange(s, dtype=jnp.int32)[None, :]
            new_cache["ckv"] = cache["ckv"].at[rows, idx].set(c_kv)
            new_cache["krope"] = cache["krope"].at[rows, idx].set(k_rope)
        else:
            new_cache["ckv"] = jax.lax.dynamic_update_slice(
                cache["ckv"], c_kv, (0, ctx.cache_len, 0))
            new_cache["krope"] = jax.lax.dynamic_update_slice(
                cache["krope"], k_rope, (0, ctx.cache_len, 0, 0))
        if cfg.use_pallas_kernels:
            # fused ragged latent read (per-row lengths, causal window;
            # paged pools stream through the block table)
            out = mla_apply_absorbed(p, cfg, xn, ctx.q_pos,
                                     (new_cache["ckv"], new_cache["krope"]),
                                     None, None,
                                     lengths=ctx.cache_len + 1,
                                     block_tables=ctx.pages)
        else:
            ckv_r, krope_r = new_cache["ckv"], new_cache["krope"]
            if ctx.paged:
                from repro.kernels.decode_attention.ref import gather_pages
                ckv_r = gather_pages(ckv_r, ctx.pages)
                krope_r = gather_pages(krope_r, ctx.pages)
            t = ckv_r.shape[1]
            k_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
            lim = (ctx.cache_len[:, None] if ctx.ragged
                   else ctx.cache_len) + s
            k_valid = k_pos < lim
            out = mla_apply_absorbed(p, cfg, xn, ctx.q_pos,
                                     (ckv_r, krope_r),
                                     k_pos, k_valid)
    return out, new_cache


# ---------------------------------------------------------------------------
# block apply
# ---------------------------------------------------------------------------


def block_apply(p: Params, cfg: ModelConfig, kind: str, x: jnp.ndarray,
                ctx: Ctx, cache: Optional[Params]
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Params]]:
    """Returns (x, aux_loss, new_cache)."""
    # NB: no with_sharding_constraint here — inside the remat'd scan body a
    # constraint becomes a save-point and doubles activation memory (saved
    # f32 copies).  Batch sharding is pinned at the embedding/head
    # boundaries instead (model.py) and propagates through the scan.
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "swa", "shared_attn"):
        window = cfg.sliding_window if kind == "swa" else None
        a, cache = _self_attention(p["attn"], cfg,
                                   rmsnorm(p["norm1"], x, cfg.rms_eps),
                                   ctx, cache, window)
        x = x + a
        x = x + mlp_apply(p["mlp"], rmsnorm(p["norm2"], x, cfg.rms_eps))
        return x, aux, cache
    if kind == "mla":
        a, cache = _mla_attention(p["mla"], cfg,
                                  rmsnorm(p["norm1"], x, cfg.rms_eps),
                                  ctx, cache)
        x = x + a
        x = x + mlp_apply(p["mlp"], rmsnorm(p["norm2"], x, cfg.rms_eps))
        return x, aux, cache
    if kind == "moe":
        xn = rmsnorm(p["norm1"], x, cfg.rms_eps)
        if cfg.mla is not None:
            a, cache = _mla_attention(p["mla"], cfg, xn, ctx, cache)
        else:
            a, cache = _self_attention(p["attn"], cfg, xn, ctx, cache, None)
        x = x + a
        h, aux = moe_apply(p["moe"], cfg, rmsnorm(p["norm2"], x, cfg.rms_eps))
        return x + h, aux, cache
    if kind in ("mamba1", "mamba2"):
        xn = rmsnorm(p["norm"], x, cfg.rms_eps)
        conv_st = cache["conv"] if cache is not None else None
        ssm_st = cache["ssm"] if cache is not None else None
        if ctx.mode == "train":
            conv_st = ssm_st = None
        fn = ssm.mamba1_apply if kind == "mamba1" else ssm.mamba2_apply
        y, (new_conv, new_ssm) = fn(p["mamba"], cfg, xn, conv_st, ssm_st)
        new_cache = None if cache is None else {"conv": new_conv,
                                                "ssm": new_ssm}
        return x + y, aux, new_cache
    if kind == "xattn":
        a, cache = _self_attention(p["attn"], cfg,
                                   rmsnorm(p["norm1"], x, cfg.rms_eps),
                                   ctx, cache, None)
        x = x + a
        c, cache = _cross_attention(p["xattn"], cfg,
                                    rmsnorm(p["norm_x"], x, cfg.rms_eps),
                                    ctx, cache)
        x = x + c
        x = x + mlp_apply(p["mlp"], rmsnorm(p["norm2"], x, cfg.rms_eps))
        return x, aux, cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack: head blocks + scanned group + tail blocks
# ---------------------------------------------------------------------------


def stack_init(rng, cfg: ModelConfig) -> Params:
    head, reps, group, tail = cfg.layer_program
    ks = iter(jax.random.split(rng, len(head) + len(tail) + len(group) + 2))
    params: Params = {
        "head": [block_init(next(ks), cfg, k) for k in head],
        "tail": [block_init(next(ks), cfg, k) for k in tail],
    }
    if "shared_attn" in group + head + tail:
        params["shared_attn"] = block_init(next(ks), cfg, "shared_attn")

    def stacked_block(rng_b, kind):
        if kind == "shared_attn":
            return {}  # weights live in params['shared_attn']
        inits = [block_init(r, cfg, kind)
                 for r in jax.random.split(rng_b, reps)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *inits)

    params["group"] = {f"b{i}": stacked_block(next(ks), k)
                      for i, k in enumerate(group)}
    return params


def stack_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray, ctx: Ctx,
                cache: Optional[Params]
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Params]]:
    head, reps, group, tail = cfg.layer_program
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Optional[Params] = None if cache is None else dict(cache)

    for i, kind in enumerate(head):
        c = cache["head"][i] if cache is not None else None
        x, aux, nc = block_apply(params["head"][i], cfg, kind, x, ctx, c)
        aux_total += aux
        if cache is not None:
            new_cache["head"] = list(new_cache["head"])
            new_cache["head"][i] = nc

    shared = params.get("shared_attn")

    if reps > 0:
        if cache is not None:
            # The stacked group cache rides in the scan CARRY.  An in-place
            # paged pool goes to its block whole, which scatters the new
            # tokens at [layer, page, offset] and lets the kernel read the
            # stacked pool; every other cache is cut out per layer and
            # written back with dynamic_update_index_in_dim.
            def body(carry, xs):
                h, aux, gcache = carry
                p_i, idx = xs
                gcache = dict(gcache)
                for j, kind in enumerate(group):
                    pj = shared if kind == "shared_attn" else p_i[f"b{j}"]
                    key = f"b{j}"
                    if ctx.paged and kvcache.pool_inplace(cfg, kind):
                        h, a, gcache[key] = block_apply(
                            pj, cfg, kind, h,
                            dataclasses.replace(ctx, layer=idx),
                            gcache[key])
                    else:
                        cj = jax.tree.map(
                            lambda a: jax.lax.dynamic_index_in_dim(
                                a, idx, 0, keepdims=False), gcache[key])
                        h, a, nc = block_apply(pj, cfg, kind, h, ctx, cj)
                        gcache[key] = jax.tree.map(
                            lambda full, new:
                            jax.lax.dynamic_update_index_in_dim(
                                full, new.astype(full.dtype), idx, 0),
                            gcache[key], nc)
                    aux = aux + a
                return (h, aux, gcache), None

            (x, aux_total, group_cache), _ = jax.lax.scan(
                body, (x, aux_total, cache["group"]),
                (params["group"], jnp.arange(reps, dtype=jnp.int32)))
            new_cache["group"] = group_cache
        else:
            def body_nc(carry, p_i):
                h, aux = carry
                for j, kind in enumerate(group):
                    pj = shared if kind == "shared_attn" else p_i[f"b{j}"]
                    h, a, _ = block_apply(pj, cfg, kind, h, ctx, None)
                    aux = aux + a
                return (h, aux), None

            # prevent_cse=False: scan's loop structure already prevents CSE;
            # the default barrier makes XLA keep an extra f32 copy of the
            # carried activation per layer (~2x saved-activation memory).
            remat_body = jax.checkpoint(
                body_nc, prevent_cse=False,
                policy=jax.checkpoint_policies.nothing_saveable)
            (x, aux_total), _ = jax.lax.scan(
                remat_body, (x, aux_total), params["group"])

    for i, kind in enumerate(tail):
        c = cache["tail"][i] if cache is not None else None
        x, aux, nc = block_apply(params["tail"][i], cfg, kind, x, ctx, c)
        aux_total += aux
        if cache is not None:
            new_cache["tail"] = list(new_cache["tail"])
            new_cache["tail"][i] = nc

    return x, aux_total, new_cache
