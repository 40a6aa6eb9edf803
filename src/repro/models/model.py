"""Public model API: build_model(config) -> Model with

  init(rng)                          -> params
  train_logits(params, batch)        -> (logits, aux)
  loss(params, batch)                -> (scalar, metrics)
  prefill(params, inputs, cache)     -> (last logits, cache)
  decode_step(params, cache, tokens) -> (logits (B,S_new,V), cache)

``tokens`` in decode_step may carry S_new > 1 — that is the speculative
verification path of the paper (§3.6): one forward pass scores all
proposed tokens.  Inputs are dicts so the modality stubs (VLM patch
embeddings, whisper frame embeddings) ride along; see input layout per
family in ``example_batch``/``launch.dryrun.input_specs``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import act_sharding, kvcache
from repro.models.layers import dense_init, rmsnorm, rmsnorm_init
from repro.models.transformer import Ctx, block_init, stack_apply, stack_init

Params = Dict[str, Any]


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    # layers of the last traced paged decode step that update their pool
    # in place (kvcache.inplace_layers); set once per trace
    kv_inplace_layers: int = dataclasses.field(default=0, compare=False)

    # -- init -----------------------------------------------------------------

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so the embedding/lm_head (and
        the fat (B,S,V) logits) shard over the 16-way model axis even for
        awkward sizes (whisper's 51865 -> 51968).  Standard production
        practice; pad logits are masked to -1e30 in _head."""
        return ((self.cfg.vocab_size + 255) // 256) * 256

    def init(self, rng) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        ks = jax.random.split(rng, 6)
        params: Params = {
            "embed": dense_init(ks[0], (self.padded_vocab, cfg.d_model),
                                scale=1.0, dtype=dt),
            "final_norm": rmsnorm_init(cfg.d_model, dt),
            "stack": stack_init(ks[1], cfg),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(
                ks[2], (cfg.d_model, self.padded_vocab), dtype=dt)
        if cfg.is_encoder_decoder:
            enc_blocks = [block_init(r, cfg, "attn") for r in
                          jax.random.split(ks[3], cfg.n_encoder_layers)]
            params["encoder"] = {
                "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_blocks),
                "norm": rmsnorm_init(cfg.d_model, dt),
            }
        return params

    # -- embedding / head -------------------------------------------------------

    def _embed(self, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
        return act_sharding.constrain_batch(params["embed"][tokens])

    def _head(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        x = act_sharding.constrain_batch(
            rmsnorm(params["final_norm"], x, self.cfg.rms_eps))
        if self.cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        if self.padded_vocab != self.cfg.vocab_size:
            pad = jnp.arange(self.padded_vocab) >= self.cfg.vocab_size
            logits = jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)
        return act_sharding.constrain_logits(logits)

    # -- encoder (whisper): bidirectional stack over stub frame embeddings ------

    def _encode(self, params: Params, frames: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        b, t, _ = frames.shape
        # sinusoidal positions (whisper-style) over the stub embeddings
        pos = jnp.arange(t)[:, None]
        dim = jnp.arange(cfg.d_model // 2)[None, :]
        ang = pos / jnp.power(10000.0, 2 * dim / cfg.d_model)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = frames + pe[None].astype(frames.dtype)
        # bidirectional: all positions 0 -> causal bias never masks
        ctx = Ctx(mode="train", q_pos=jnp.zeros((b, t), jnp.int32))

        from repro.models.transformer import block_apply

        def body(h, p_i):
            h, _, _ = block_apply(p_i, cfg, "attn", h, ctx, None)
            return h, None

        # remat like the decoder stack: grad-of-scan must not save the
        # encoder's per-layer attention intermediates
        body = jax.checkpoint(body, prevent_cse=False,
                              policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return rmsnorm(params["encoder"]["norm"], x, cfg.rms_eps)

    # -- sequence assembly (modality stubs) ---------------------------------------

    def _assemble(self, params: Params, inputs: Dict[str, jnp.ndarray]
                  ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """Returns (x (B,S,D), enc_out or None)."""
        cfg = self.cfg
        x = self._embed(params, inputs["tokens"])
        enc_out = None
        if cfg.family == "vlm" and "prefix" in inputs:
            x = jnp.concatenate([inputs["prefix"].astype(x.dtype), x], axis=1)
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, inputs["frames"])
        return x, enc_out

    # -- train ---------------------------------------------------------------------

    def train_logits(self, params: Params, inputs: Dict[str, jnp.ndarray]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        x, enc_out = self._assemble(params, inputs)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        ctx = Ctx(mode="train", q_pos=pos, enc_out=enc_out)
        x, aux, _ = stack_apply(params["stack"], self.cfg, x, ctx, None)
        return self._head(params, x), aux

    def loss(self, params: Params, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """batch['tokens']: (B, S+1); model trains on next-token prediction.
        Extra keys (prefix/frames) pass through.  labels < 0 are masked."""
        tokens = batch["tokens"]
        inputs = dict(batch)
        inputs["tokens"] = tokens[:, :-1]
        labels = tokens[:, 1:]
        logits, aux = self.train_logits(params, inputs)
        # VLM: prefix positions predict nothing; trim to text tail
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, -labels.shape[1]:]
        nll = cross_entropy(logits, labels)
        total = nll + aux
        return total, {"nll": nll, "aux": aux,
                       "ppl": jnp.exp(jnp.minimum(nll, 20.0))}

    # -- serve ----------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, page_size=None,
                   n_pages=None):
        """Dense per-row cache by default; with ``page_size`` (pageable
        architectures only) the full-attention/MLA stripes become a
        shared page pool + (B, max_pages) block table (``pages``).
        Paged caches are decode-only: admission prefills a dense B=1 row
        and scatters it into the row's allocated pages."""
        return kvcache.init_cache(self.cfg, batch, max_len,
                                  page_size=page_size, n_pages=n_pages)

    def cache_spec(self, batch: int, max_len: int, page_size=None,
                   n_pages=None):
        return kvcache.cache_spec(self.cfg, batch, max_len,
                                  page_size=page_size, n_pages=n_pages)

    def prefill(self, params: Params, inputs: Dict[str, jnp.ndarray],
                cache) -> Tuple[jnp.ndarray, Any]:
        """Run the prompt, fill the cache.  Returns (last-position logits,
        cache).  Batched serving prefills each request (B=1) and scatters
        the row into its slot, so only the last position's logits are ever
        needed.

        ``inputs`` may carry a scalar int32 ``length``: the prompt is then
        right-padded to a bucket size (serving admission pads to powers of
        two so compile count stays O(log max_len)) and only the first
        ``length`` tokens are real.  The head is read at the true last
        token and ``cache['len']`` advances by ``length``, so pad KV
        entries sit beyond the valid frontier — masked by the pos < len
        validity rule and overwritten as decode proceeds.  Full-attention
        / MLA caches only: ring (SWA) and recurrent (SSM) state would
        absorb the pads (callers gate on the refeed predicate).
        """
        x, enc_out = self._assemble(params, inputs)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        ctx = Ctx(mode="prefill", q_pos=pos, cache_len=cache["len"],
                  max_len=0, enc_out=enc_out)
        x, _, new_cache = stack_apply(params["stack"], self.cfg, x, ctx, cache)
        length = inputs.get("length")
        if length is None:
            new_cache["len"] = cache["len"] + s
            return self._head(params, x[:, -1:]), new_cache
        new_cache["len"] = cache["len"] + length
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        return self._head(params, last), new_cache

    def decode_step(self, params: Params, cache,
                    tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Any]:
        """tokens: (B, S_new).  S_new=1 for plain decode; >1 verifies a
        speculative chain in one pass.  Returns logits (B,S_new,V)."""
        x = self._embed(params, tokens)
        b, s, _ = x.shape
        ln = cache["len"]
        base = ln[:, None] if ln.ndim == 1 else ln   # (B,) ragged batch
        pos = base + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s))
        ctx = Ctx(mode="decode", q_pos=pos, cache_len=ln,
                  pages=cache.get("pages"))
        if ctx.paged:
            self.kv_inplace_layers = kvcache.inplace_layers(self.cfg, cache)
        x, _, new_cache = stack_apply(params["stack"], self.cfg, x, ctx, cache)
        new_cache["len"] = cache["len"] + s
        return self._head(params, x), new_cache

    def rollback(self, cache, n_tokens: int):
        """Speculative rollback: rewind ``len`` (KV entries beyond len are
        masked by validity, so no copying).  SSM states cannot be rewound —
        the serving engine snapshots them before speculation instead."""
        out = dict(cache)
        out["len"] = cache["len"] - n_tokens
        return out

    # -- misc -----------------------------------------------------------------------

    def example_batch(self, batch: int, seq: int, rng=None) -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = {"tokens": jax.random.randint(
            rng, (batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32)}
        if cfg.family == "vlm":
            p = cfg.n_prefix_tokens
            out["tokens"] = out["tokens"][:, :max(2, seq + 1 - p)]
            out["prefix"] = jnp.zeros((batch, p, cfg.d_model),
                                      jnp.dtype(cfg.dtype))
        if cfg.is_encoder_decoder:
            out["frames"] = jnp.zeros(
                (batch, cfg.encoder_seq_len, cfg.d_model),
                jnp.dtype(cfg.dtype))
        return out


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Masked CE via one-hot contraction — vocab-sharding friendly: the
    contraction over V composes with a model-axis-sharded vocab (partial
    sums + one small all-reduce) instead of the gather formulation, which
    makes XLA SPMD all-gather the full (B,S,V) logits."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), logits.shape[-1],
                            dtype=jnp.float32)
    gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    mask = (labels >= 0).astype(jnp.float32)
    return ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def build_model(cfg: ModelConfig) -> Model:
    cfg.check()
    return Model(cfg)
