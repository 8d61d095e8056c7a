"""Flash-decode: split-KV attention for serving (FlashDecoding-style).

At decode, Sq = 1: the prefill grid (bh, qi) provides no parallelism along
queries, so occupancy collapses.  Splitting the KV cache across a parallel
grid axis restores it: each (bh, split) grid step reduces its KV span to a
partial (m, l, o); a cheap XLA epilogue merges the partials with the
numerically-stable log-sum-exp combination.

Invariants (core/invariants.build_flash_decode_program): GQA head mapping,
KV-range partition (spans tile the cache exactly once), store-slot honesty
of the partials — all validated before lowering (ops.mha_decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invariants import FlashDecodeConfig

NEG_INF = -1e30
F32 = jnp.float32


def _decode_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref, m_ref, l_ref, *,
                   span: int, scale: float):
    s = pl.program_id(1)
    q = q_ref[0]                                  # (1, D)
    k = k_ref[0]                                  # (span, D)
    v = v_ref[0]                                  # (span, D)
    kv_len = kvlen_ref[0]

    st = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32) * scale  # (1,span)
    pos = s * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    mask = pos < kv_len
    st = jnp.where(mask, st, NEG_INF)
    m = jnp.max(st, axis=1, keepdims=True)        # (1, 1)
    p = jnp.exp(st - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)  # (1, D)
    o_ref[0] = o
    m_ref[0] = m
    l_ref[0] = l


@functools.partial(jax.jit, static_argnames=("cfg", "scale", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len: jnp.ndarray, *,
                 cfg: FlashDecodeConfig = FlashDecodeConfig(),
                 scale=None, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, 1, D); k, v: (B, Hkv, S, D) cache; kv_len: () int32.
    Returns (B, Hq, 1, D)."""
    B, Hq, _, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    ns = cfg.kv_splits
    if S % ns:
        raise ValueError(f"kv_splits {ns} must tile the cache ({S})")
    span = S // ns
    scale = float(scale if scale is not None else D ** -0.5)

    qf = q.reshape(B * Hq, 1, D)
    kf = k.reshape(B * Hkv, S, D)
    vf = v.reshape(B * Hkv, S, D)
    kvl = jnp.broadcast_to(kv_len.astype(jnp.int32), (1,))

    def q_idx(bh, s):
        return (bh, 0, 0)

    def kv_idx(bh, s):
        return ((bh // Hq) * Hkv + (bh % Hq) // G, s, 0)

    o, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, span=span, scale=scale),
        grid=(B * Hq, ns),
        in_specs=[
            pl.BlockSpec((1, 1, D), q_idx),
            pl.BlockSpec((1, span, D), kv_idx),
            pl.BlockSpec((1, span, D), kv_idx),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, s: (bh, s, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, ns, D), F32),
            jax.ShapeDtypeStruct((B * Hq, ns, 1), F32),
            jax.ShapeDtypeStruct((B * Hq, ns, 1), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qf, kf, vf, kvl)

    # log-sum-exp combine across splits (XLA epilogue)
    m_g = jnp.max(m, axis=1, keepdims=True)                  # (BH, 1, 1)
    w = jnp.exp(m - m_g)                                     # (BH, ns, 1)
    l_g = jnp.sum(l * w, axis=1, keepdims=True)              # (BH, 1, 1)
    l_g = jnp.where(l_g == 0.0, 1.0, l_g)
    out = jnp.sum(o * w, axis=1, keepdims=True) / l_g        # (BH, 1, D)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)
