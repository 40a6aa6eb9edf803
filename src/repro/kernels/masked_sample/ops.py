"""Jitted public wrappers: fused grammar-masked argmax + masked sampling.

``masked_argmax(logits, mask)`` dispatches to the Pallas kernel on TPU and
to the interpreted kernel (CPU validation) elsewhere; ``use_ref=True``
selects the unfused jnp oracle (the baseline the §Perf analysis compares
against).

The mask operand's layout is read from its dtype: uint32 means a packed
``(B, ceil(V/32))`` bitset row (``core/bitmask.py`` wire format, unpacked
in-register by the kernel); anything else is a dense ``(B, V)`` int8/bool
mask, packed on device before the same kernel.  Both layouts are
bitwise-identical in output — asserted by the parity tests and by
``benchmarks/mask_bench.py``.

``masked_sample_packed(logits, bits, temps, keys)`` is the device-side
temperature>0 selection path (ISSUE 8 satellite): masked softmax sampling
via the Gumbel-max identity, with PER-ROW temperature and per-row
counter-based PRNG keys, so sampled rows stop selecting host-side.  It
matches the host ``select_token`` path in DISTRIBUTION (softmax over
``logits/T`` restricted to the mask — asserted statistically by the
parity test), not bitwise: the host path draws from a per-request
``np.random.Generator`` stream, the device path from a JAX
threefry stream keyed on ``fold_in(PRNGKey(seed), draw_index)``.  Both
streams are pure functions of (request seed, draw index), so either way a
sampled row's output is independent of batch composition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.masked_sample.kernel import (NEG, TILE_V,
                                                masked_argmax_pallas,
                                                masked_argmax_pallas_packed)
from repro.kernels.masked_sample.ref import masked_argmax_ref


def masked_argmax(logits, mask, use_ref: bool = False,
                  block_v: int = TILE_V):
    if use_ref:
        return masked_argmax_ref(logits, mask)
    on_tpu = jax.default_backend() == "tpu"
    if jnp.asarray(mask).dtype == jnp.uint32:
        return masked_argmax_pallas_packed(logits, mask, block_v=block_v,
                                           interpret=not on_tpu)
    return masked_argmax_pallas(logits, mask, block_v=block_v,
                                interpret=not on_tpu)


@jax.jit
def masked_sample_packed(logits, bits, temps, keys):
    """Masked softmax sampling on packed uint32 masks, fully on device.

    ``logits`` (B, V) f32; ``bits`` (B, ceil(V/32)) uint32; ``temps``
    (B,) f32 per-row temperature (rows with t <= 0 still produce the
    masked argmax — Gumbel noise over ``logits/1e-6`` cannot flip a
    strict maximum); ``keys`` (B, 2) uint32 per-row PRNG keys (the caller
    derives them as ``fold_in(PRNGKey(seed), n_draws)`` so the stream
    depends only on the request, never on the batch).  Returns (B,) int32
    token ids.

    Gumbel-max: ``argmax(logits/T + G)`` over the legal set samples
    exactly ``softmax(logits/T)`` restricted to that set — one fused
    argmax instead of a host round-trip per sampled row.  Bit b of word w
    is token ``w*32 + b`` (LSB first), matching core/bitmask.
    """
    b, v = logits.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    legal = ((bits[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1))
    legal = legal.astype(jnp.bool_).reshape(b, -1)[:, :v]
    scaled = logits.astype(jnp.float32) \
        / jnp.maximum(temps, 1e-6)[:, None]
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (v,),
                                                  jnp.float32))(keys)
    score = jnp.where(legal, scaled + gumbel, NEG)
    return jnp.argmax(score, axis=-1).astype(jnp.int32)
