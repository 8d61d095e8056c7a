"""Pallas TPU flash attention — the paper's second kernel family.

Online-softmax streaming over KV blocks (Figure 1 of the paper, adapted to
TPU tiles per DESIGN.md §2):

  * Q block stays resident in VMEM for the whole KV sweep; K/V blocks are
    streamed and double-buffered by the Pallas pipeline (the paper's
    11-stage software pipeline becomes grid-level pipelining).
  * GQA head mapping is folded into the K/V BlockSpec index maps — the
    exact site the ``wrong_kv_head`` invariant guards.
  * Causal block-skip (``@pl.when``) skips fully-masked KV blocks; the
    in-block mask handles the diagonal (OOB-guard analogue).
  * Running (m, l, acc) carried in VMEM scratch across the ``arbitrary``
    KV grid axis — the accumulator-stability invariant's subject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invariants import FlashAttentionConfig

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               nkv: int, bq: int, bkv: int, causal: bool, skip: bool,
               scale: float, kv_len: int):
    qi = pl.program_id(1)
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0]                         # (bq, D)
        k = k_ref[0]                         # (bkv, D)
        v = v_ref[0]                         # (bkv, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bkv)

        kpos = kv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = kpos < kv_len                 # padded-KV guard
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv),
                                                      0)
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)      # exact 1.0 on first visit
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)          # masked lanes contribute zero
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    if causal and skip:
        # visit only blocks intersecting the causal triangle
        pl.when(kv * bkv <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(kv == nkv - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)      # fully-masked rows emit zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pad_seq(x, block, axis):
    pad = (-x.shape[axis]) % block
    if pad:
        cfgs = [(0, 0)] * x.ndim
        cfgs[axis] = (0, pad)
        x = jnp.pad(x, cfgs)
    return x


@functools.partial(
    jax.jit, static_argnames=("cfg", "causal", "scale", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    cfg: FlashAttentionConfig = FlashAttentionConfig(),
                    causal: bool = True, scale=None,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Returns (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = float(scale if scale is not None else D ** -0.5)
    bq = min(cfg.block_q, max(Sq, 8))
    bkv = min(cfg.block_kv, max(Skv, 8))

    q = _pad_seq(q, bq, 2)
    k = _pad_seq(k, bkv, 2)
    v = _pad_seq(v, bkv, 2)
    Sq_p, Skv_p = q.shape[2], k.shape[2]

    qf = q.reshape(B * Hq, Sq_p, D)
    kf = k.reshape(B * Hkv, Skv_p, D)
    vf = v.reshape(B * Hkv, Skv_p, D)

    nq, nkv = Sq_p // bq, Skv_p // bkv
    grid = (B * Hq, nq, nkv)

    def q_idx(bh, qi, kv):
        return (bh, qi, 0)

    def kv_idx(bh, qi, kv):
        # GQA: query head bh -> kv head (the invariant-guarded site)
        return ((bh // Hq) * Hkv + (bh % Hq) // group, kv, 0)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, nkv=nkv, bq=bq, bkv=bkv, causal=causal,
            skip=cfg.causal_block_skip, scale=scale, kv_len=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_idx),
            pl.BlockSpec((1, bkv, D), kv_idx),
            pl.BlockSpec((1, bkv, D), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_idx),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)

    return out.reshape(B, Hq, Sq_p, D)[:, :, :Sq, :]
