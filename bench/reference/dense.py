"""Plain reference of the dense decoder the benchmark's configurations
run: pre-norm blocks of grouped-query attention with rotary positions
and a SwiGLU MLP, RMS norms, a final norm and an untied head.

Written from the architecture, not from the program: it imports nothing
of it.  Its weights are remade from the run's seed, layer by layer, by
``harness.weights`` (integer draws, so bit for bit the program's), and
each layer is freed before the next, so the whole model never has to fit
at once.  Rotary embedding rotates the two halves of each head
(``x1*cos - x2*sin``, ``x2*cos + x1*sin``) with frequencies
``theta^(-i/half)``; attention scales by ``1/sqrt(d_head)``.

``mode`` picks the arithmetic:

- ``"fp32"``: float32 activations and weights, every product at
  ``Precision.HIGHEST``; the reference that decides ``correct``;
- ``"fp8"``: the control, the step below the configuration's bfloat16:
  weights quantized per output channel (absmax scaling) to
  float8_e4m3fn and back, activations in bfloat16, products at the
  default precision with float32 accumulation.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 256            # sequence lengths are padded to this, to bound compiles
Q_CHUNK = 512        # queries per attention block, where they divide


def _fp8(w: jnp.ndarray) -> jnp.ndarray:
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 448.0
    q = (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * s).astype(jnp.bfloat16)


def _weight(w: jnp.ndarray, mode: str) -> jnp.ndarray:
    if mode == "fp32":
        return w.astype(jnp.float32)
    if mode == "fp8":
        return _fp8(w)
    raise ValueError(mode)


def _act(mode):
    return jnp.float32 if mode == "fp32" else jnp.bfloat16


def _mm(a, b, mode):
    if mode == "fp32":
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32
                      ).astype(jnp.bfloat16)


def _rms(x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (n, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _block(w, x, pos, kc, vc, pc, okc, *, cfg, mode):
    """One layer over queries ``x`` (n, d) at ``pos``, attending to the
    context keys (kc, vc at pc, valid okc) and to themselves, causally.
    Returns the new x and this chunk's keys and values."""
    nq, nkv, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    n = x.shape[0]
    xn = _rms(x, cfg["rms_eps"])
    q = _rope(_mm(xn, w["attn/wq"], mode).reshape(n, nq, dh), pos,
              cfg["rope_theta"])
    k = _rope(_mm(xn, w["attn/wk"], mode).reshape(n, nkv, dh), pos,
              cfg["rope_theta"])
    v = _mm(xn, w["attn/wv"], mode).reshape(n, nkv, dh)
    K = jnp.concatenate([kc, k], 0)
    V = jnp.concatenate([vc, v], 0)
    P = jnp.concatenate([pc, pos], 0)
    OK = jnp.concatenate([okc, jnp.ones((n,), bool)], 0)
    g = nq // nkv
    prec = HIGHEST if mode == "fp32" else None

    def attend(qp):
        qc, pc_ = qp                                         # (C,nq,dh),(C,)
        qg = qc.reshape(-1, nkv, g, dh)
        s = jnp.einsum("cgqd,tgd->gqct", qg, K, precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        mask = OK[None, :] & (P[None, :] <= pc_[:, None])    # (C, T)
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(V.dtype)
        o = jnp.einsum("gqct,tgd->cgqd", p, V, precision=prec,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, nq * dh).astype(x.dtype)

    c = Q_CHUNK if n % Q_CHUNK == 0 else PAD
    out = jax.lax.map(attend, (q.reshape(n // c, c, nq, dh),
                               pos.reshape(n // c, c)))
    x = x + _mm(out.reshape(n, nq * dh), w["attn/wo"], mode)
    xn = _rms(x, cfg["rms_eps"])
    h = jax.nn.silu(_mm(xn, w["mlp/w_gate"], mode).astype(jnp.float32)) \
        * _mm(xn, w["mlp/w_up"], mode).astype(jnp.float32)
    x = x + _mm(h.astype(x.dtype), w["mlp/w_down"], mode)
    return x, k, v


_block_jit = jax.jit(_block, static_argnames=("cfg", "mode"))


def _layer_weights(key, cfg, layer: int, mode: str) -> Dict[str, jnp.ndarray]:
    d, nq, nkv, dh, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                         cfg["d_head"], cfg["d_ff"])
    shapes = {"attn/wq": (d, nq * dh), "attn/wk": (d, nkv * dh),
              "attn/wv": (d, nkv * dh), "attn/wo": (nq * dh, d),
              "mlp/w_gate": (d, f), "mlp/w_up": (d, f),
              "mlp/w_down": (f, d)}
    out = {}
    for name, shape in shapes.items():
        out[name] = _weight(W.draw(key, name, shape, jnp.bfloat16, layer),
                            mode)
    return out


_draw_layer = jax.jit(_layer_weights, static_argnames=("cfg", "mode"))


def _pad(n: int, pow2: bool) -> int:
    """A multiple of PAD, or with ``pow2`` the next power of two from
    PAD up: remainders vary from run to run, and few shapes keep their
    compiled programs in the cache."""
    if not pow2:
        return -(-n // PAD) * PAD
    p = PAD
    while p < n:
        p *= 2
    return p


class _Cfg(dict):
    """A hashable view of the config, for static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def model_cfg(cfg: dict) -> _Cfg:
    m = cfg["model"]
    return _Cfg(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                n_heads=m["num_attention_heads"],
                n_kv_heads=m["num_key_value_heads"],
                d_head=m["hidden_size"] // m["num_attention_heads"],
                d_ff=m["intermediate_size"], rope_theta=m["rope_theta"],
                rms_eps=m["rms_norm_eps"], vocab=m["vocab_size"])


def logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
           score: Sequence[Sequence[int]], n_vocab: int,
           modes: Sequence[str] = ("fp32",)) -> Dict[str, List[np.ndarray]]:
    """For each mode, a list with one (len(score[i]), n_vocab) float32
    array per sequence: the logits over the first ``n_vocab`` ids at
    the positions ``score[i]`` of ``seqs[i]``.

    Sequences that share a prefix have it computed once: the longest
    prefix common to all of them is run first, and every sequence's
    remainder attends to its keys."""
    mc = model_cfg(cfg)
    key = W.run_key(seed)
    d = mc["d_model"]
    pshare = len(seqs[0])
    for s in seqs[1:]:
        n = 0
        while n < min(pshare, len(s)) and s[n] == seqs[0][n]:
            n += 1
        pshare = n
    pshare = min(pshare, min(min(sc) for sc in score))  # scored positions
    pshare = pshare if pshare >= PAD else 0            # are in remainders
    parts = [list(seqs[0][:pshare])] + [list(s[pshare:]) for s in seqs]
    starts = [0] + [pshare] * len(seqs)
    padded = [_pad(max(1, len(p)), i > 0) for i, p in enumerate(parts)]
    vp = -(-mc["vocab"] // 256) * 256
    embed = W.draw(key, "embed", (vp, d), jnp.bfloat16)
    out = {}
    for mode in modes:
        act = _act(mode)
        xs, poss, oks = [], [], []
        for p, st, n in zip(parts, starts, padded):
            ids = np.zeros(n, np.int32)
            ids[:len(p)] = p
            xs.append(embed[jnp.asarray(ids)].astype(act))
            pos = np.arange(st, st + n, dtype=np.int32)
            pos[len(p):] = 1 << 30            # pads come after everything
            poss.append(jnp.asarray(pos))
            ok = np.zeros(n, bool)
            ok[:len(p)] = True
            oks.append(jnp.asarray(ok))
        nkv, dh = mc["n_kv_heads"], mc["d_head"]
        empty = (jnp.zeros((0, nkv, dh), act), jnp.zeros((0, nkv, dh), act),
                 jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool))
        for layer in range(mc["n_layers"]):
            w = _draw_layer(key, mc, jnp.int32(layer), mode)
            ctx = empty
            if pshare:
                xs[0], kp, vp_ = _block_jit(w, xs[0], poss[0], *empty,
                                            cfg=mc, mode=mode)
                ctx = (kp, vp_, poss[0], oks[0])
            for i in range(1, len(xs)):
                xs[i], _, _ = _block_jit(w, xs[i], poss[i], *ctx,
                                         cfg=mc, mode=mode)
            del w
        head = _weight(W.draw(key, "lm_head", (d, vp), jnp.bfloat16)
                       [:, :n_vocab], mode)
        res = []
        for i, sc in enumerate(score):
            rows = xs[i + 1][jnp.asarray(np.asarray(sc) - pshare)]
            h = _rms(rows, mc["rms_eps"])
            res.append(np.asarray(_mm(h, head, mode), np.float32))
        out[mode] = res
        del xs, head
    return out
