"""Pallas TPU ragged-prefill attention — packed variable-length prefill.

Queries and KV both live at *packed* offsets; the per-token metadata
(``seg`` = owning segment, ``pos`` = segment-relative position, derived
from cu_seqlens by :mod:`.packing`) rides in as VMEM blocks alongside
the tiles they describe, in the layout
:func:`repro.core.families.ragged_prefill.kernel_blocks` fixes (a
(block_q, 1) column per query block, a (1, block_kv) row per kv block —
legal TPU blocks for every config the gate admits).  The segment/causal
mask is applied **before** the online softmax:

    admit(q, k)  ⇔  seg_q == seg_k  ∧  pos_k <= pos_q  ∧  both >= 0

so a KV element reaches the accumulator only when it provably belongs
to the query's sequence at a causally-visible position — the runtime
mirror of the family's leakage-gate conformity assertion
(repro.core.families.ragged_prefill).  Padding tokens carry seg == -1
and are masked unconditionally; a fully-masked query row flushes a zero
row (zero-denominator guard), never an average over garbage.

Grid: ``(Hq, TQ/block_q, TK/block_kv)`` — heads and query blocks
parallel, packed KV blocks sequential with the (m, l, acc) online-
softmax carry in VMEM scratch.  Weights stay f32 and V is cast up,
matching the paged-decode kernel's convention (a lossy p->bf16 downcast
visibly perturbs logits vs the dense oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.families.ragged_prefill import (RaggedPrefillConfig,
                                                kernel_blocks)

NEG_INF = -1e30
F32 = jnp.float32


def _ragged_kernel(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, n_steps: int,
                   scale: float):
    kb = pl.program_id(2)
    q = q_ref[0]                                   # (bq, D)
    k = k_ref[0]                                   # (bkv, D)
    v = v_ref[0]                                   # (bkv, D)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (bq, bkv)

    # the leakage mask: same segment, causally visible, not padding —
    # applied BEFORE the online softmax so foreign-sequence and padding
    # scores never touch the (m, l, acc) carry
    sq = sq_ref[...]                               # (bq, 1)
    pq = pq_ref[...]
    sk = sk_ref[0]                                 # (1, bkv)
    pk = pk_ref[0]
    mask = (sq == sk) & (pk <= pq) & (sq >= 0) & (sk >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # NEG_INF is finite: a fully-masked block has s == m_new == NEG_INF,
    # so exp(s - m_new) is 1, not 0 — the explicit mask keeps it honest
    p = jnp.exp(s - m_new) * mask.astype(F32)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    # f32 weights, V cast *up* (exact for bf16) — PR-8 convention
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v.astype(F32), (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(kb == n_steps - 1)
    def _flush():
        l = l_scr[...]
        # fully-masked rows (padding queries) emit zeros, not garbage
        o_ref[0] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("cfg", "scale", "interpret"))
def ragged_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   seg_q: jnp.ndarray, pos_q: jnp.ndarray,
                   seg_k: jnp.ndarray, pos_k: jnp.ndarray, *,
                   cfg: RaggedPrefillConfig = RaggedPrefillConfig(),
                   scale=None, interpret: bool = False) -> jnp.ndarray:
    """q: (Hq, TQ, D) packed queries; k, v: (Hkv, TK, D) packed KV;
    seg/pos: (TQ,) and (TK,) int32 per-token metadata (seg -1 on
    padding).  Returns (Hq, TQ, D) in q's dtype."""
    Hq, TQ, D = q.shape
    Hkv, TK, _ = k.shape
    G = Hq // Hkv
    bq, bkv = cfg.block_q, cfg.block_kv
    if TQ % bq or TK % bkv:
        raise ValueError(
            f"blocks ({bq}, {bkv}) must tile the packed buffers "
            f"(TQ={TQ}, TK={TK}) — pad before packing")
    scale = float(scale if scale is not None else D ** -0.5)
    blk = kernel_blocks(cfg, total_q=TQ, total_k=TK, q_heads=Hq,
                        kv_heads=Hkv, head_dim=D)
    meta = [m.reshape(blk[name][1]).astype(jnp.int32)
            for name, m in (("seg_q", seg_q), ("pos_q", pos_q),
                            ("seg_k", seg_k), ("pos_k", pos_k))]
    nq, nk = TQ // bq, TK // bkv

    def q_idx(h, qb, kb):
        return (h, qb, 0)

    def kv_idx(h, qb, kb):
        # GQA: query head h reads kv head h // G (invariant-guarded site)
        return (h // G, kb, 0)

    out = pl.pallas_call(
        functools.partial(_ragged_kernel, n_steps=nk, scale=scale),
        grid=(Hq, nq, nk),
        in_specs=[
            pl.BlockSpec(blk["Q"][0], q_idx),
            pl.BlockSpec(blk["K"][0], kv_idx),
            pl.BlockSpec(blk["V"][0], kv_idx),
            pl.BlockSpec(blk["seg_q"][0], lambda h, qb, kb: (qb, 0)),
            pl.BlockSpec(blk["pos_q"][0], lambda h, qb, kb: (qb, 0)),
            pl.BlockSpec(blk["seg_k"][0], lambda h, qb, kb: (kb, 0, 0)),
            pl.BlockSpec(blk["pos_k"][0], lambda h, qb, kb: (kb, 0, 0)),
        ],
        out_specs=pl.BlockSpec(blk["O"][0], q_idx),
        out_shape=jax.ShapeDtypeStruct((Hq, TQ, D), F32),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, D), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, *meta)
    return out.astype(q.dtype)
