"""Pallas TPU paged-attention decode — block-table-indexed KV cache.

The KV cache is a pool of fixed-size physical pages ``(P, HK, PS, D)``;
``table[b, lp]`` maps sequence b's logical page lp to a physical page.
The table and the per-sequence logical lengths ride in as scalar-prefetch
operands (:class:`pltpu.PrefetchScalarGridSpec`), so the BlockSpec index
maps can gather K/V pages by table lookup before each grid step's DMA —
the kernel body itself never sees a physical index, only the gathered
tile plus its logical position.

Grid: ``(B·H, NP/block_pages, block_pages)`` — sequences×heads parallel,
logical pages sequential with a running online-softmax (m, l, acc) carry
in VMEM scratch, merged at the final page.

Length masking: score position ``lp·PS + col`` is masked to -inf when it
reaches ``lengths[b]``, and the post-softmax weight is explicitly zeroed
under the same mask (NEG_INF is finite, so a fully-masked page block
would otherwise contribute ``exp(0)`` per lane).  Every null-page
position sits at or beyond the sequence's logical length, so masked
garbage never reaches the accumulator — the runtime mirror of the
family's length-gate conformity assertion.

Invariants (repro.core.families.paged_attention): page-bound indirection,
K/V through the same table entry, GQA head mapping, logical coverage of
the cache, position honesty of the scores, length-gate conformity, carry
stability — all validated before lowering (ops.paged_decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.families.paged_attention import PagedAttentionConfig

NEG_INF = -1e30
F32 = jnp.float32


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, n_steps: int, scale: float,
                   q_heads: int, page_size: int):
    step = pl.program_id(1) * pl.num_programs(2) + pl.program_id(2)
    b = pl.program_id(0) // q_heads
    q = q_ref[0]                                   # (1, D)
    k = k_ref[0, 0]                                # (PS, D)
    v = v_ref[0, 0]                                # (PS, D)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (1, PS)
    # logical positions of this page block's columns vs the sequence's
    # logical length: beyond-length (incl. every null-page) scores die here
    pos = step * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    mask = pos < len_ref[b]
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # NEG_INF is finite: a fully-masked block has s == m_new == NEG_INF,
    # so exp(s - m_new) is 1, not 0 — the explicit mask keeps it honest
    p = jnp.exp(s - m_new) * mask.astype(F32)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    # weights stay f32 and V is cast *up* (exact for bf16 pools): a
    # lossy p->bf16 downcast here visibly perturbs decode logits vs the
    # dense oracle
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v.astype(F32), (((1,), (0,)), ((), ())),
        preferred_element_type=F32)
    m_scr[...] = m_new

    @pl.when(step == n_steps - 1)
    def _flush():
        l = l_scr[...]
        o_ref[0] = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("cfg", "scale", "interpret"))
def paged_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                 v_pages: jnp.ndarray, table: jnp.ndarray,
                 lengths: jnp.ndarray = None, *,
                 cfg: PagedAttentionConfig = PagedAttentionConfig(),
                 scale=None, interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, 1, D); k_pages/v_pages: (P, Hkv, PS, D) pools;
    table: (B, NP) int32 logical→physical page map; lengths: (B,) int32
    logical tokens per sequence (None ⇒ every sequence spans NP·PS).
    Returns (B, Hq, 1, D)."""
    B, Hq, _, D = q.shape
    P, Hkv, PS, _ = k_pages.shape
    _, NP = table.shape
    G = Hq // Hkv
    bp = cfg.block_pages
    if NP % bp:
        raise ValueError(f"block_pages {bp} must divide the {NP} pages "
                         f"per sequence")
    scale = float(scale if scale is not None else D ** -0.5)

    qf = q.reshape(B * Hq, 1, D)
    tflat = table.reshape(B * NP).astype(jnp.int32)
    if lengths is None:
        lengths = jnp.full((B,), NP * PS, jnp.int32)
    lens = lengths.astype(jnp.int32)

    def kv_idx(bh, pg, u, tref, lref):
        return (tref[(bh // Hq) * NP + pg * bp + u],
                (bh % Hq) // G, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hq, NP // bp, bp),
        in_specs=[
            pl.BlockSpec((1, 1, D),
                         lambda bh, pg, u, tref, lref: (bh, 0, 0)),
            pl.BlockSpec((1, 1, PS, D), kv_idx),
            pl.BlockSpec((1, 1, PS, D), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, D),
                               lambda bh, pg, u, tref, lref: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), F32),
            pltpu.VMEM((1, 1), F32),
            pltpu.VMEM((1, D), F32),
        ],
    )

    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_steps=NP, scale=scale,
                          q_heads=Hq, page_size=PS),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, D), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tflat, lens, qf, k_pages, v_pages)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)
