"""Pallas TPU quantized GEMM — int8/fp8 operands, per-group f32 scales.

C = dequant(Aq @ Bq): the narrow-dtype contraction runs on the MXU at the
doubled int8 issue rate with an int32 partial product; each K tile is
dequantized *before* accumulation with the (SA row-slice, SB col-slice)
scale pair of its K-group (``bk`` must divide the scale group, so every
tile has exactly one scale — the precondition the family's
``build_program`` enforces).  Accumulation is f32 VMEM scratch.

Every config is validated against the family's scale-provenance
invariants (repro.core.families.quant_gemm) before lowering — see ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.families.quant_gemm import QuantGemmConfig


def make_kernel(nk: int):
    def kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        prod = jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        # dequant-before-accumulate: this tile's K-group scales apply to
        # this partial product only (the family's stability invariant)
        acc_ref[...] += prod.astype(jnp.float32) * sa_ref[...] * sb_ref[...]

        @pl.when(k == nk - 1)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return kernel


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit,
                   static_argnames=("group", "cfg", "out_dtype", "interpret"))
def quant_gemm(a: jnp.ndarray, b: jnp.ndarray, sa: jnp.ndarray,
               sb: jnp.ndarray, *, group: int,
               cfg: QuantGemmConfig = QuantGemmConfig(),
               out_dtype=jnp.float32, interpret: bool = False
               ) -> jnp.ndarray:
    """a: (M, K) int8; b: (K, N) int8; sa: (M, ceil(K/group)) f32;
    sb: (ceil(K/group), N) f32.  Returns dequantized (M, N)."""
    if group % cfg.bk:
        raise ValueError(f"bk {cfg.bk} must divide the scale group {group}")
    m0, k0 = a.shape
    _, n0 = b.shape
    bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
    a = _pad_to(a, bm, bk)
    b = _pad_to(b, bk, bn)
    sa = _pad_to(sa, bm, 1)
    sb = _pad_to(sb, 1, bn)
    m, k = a.shape
    n = b.shape[1]
    mi, nj, nk = m // bm, n // bn, k // bk
    gk = group // bk                     # K tiles per scale group

    out = pl.pallas_call(
        make_kernel(nk),
        grid=(mi, nj, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, kk // gk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (kk // gk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, sa, sb)
    return out[:m0, :n0]
