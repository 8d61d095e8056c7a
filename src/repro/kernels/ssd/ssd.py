"""Pallas TPU SSD (Mamba-2 state-space-dual) chunk kernel — a
beyond-paper fourth ARGUS kernel family covering the attention-free arch.

Per grid step (bh, c): the intra-chunk dual "attention" (masked C·Bᵀ
matmul — MXU work the GEMM invariants govern) plus the inter-chunk state
contribution, with the (N, P) running state carried in VMEM scratch across
the sequential chunk axis — the same carried-accumulator pattern whose
stability ARGUS asserts for flash attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invariants import SSDConfig


F32 = jnp.float32


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, state_ref, *,
                nc: int, q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(F32)                       # (q, P)
    da = da_ref[0].astype(F32)                     # (q,)
    B = b_ref[0].astype(F32)                       # (q, N)
    C = c_ref[0].astype(F32)                       # (q, N)

    cs = jnp.cumsum(da)                            # (q,)
    diff = cs[:, None] - cs[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32) * L
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)

    # inter-chunk: y += exp(cs) * (C @ state)
    state = state_ref[...]                         # (N, P)
    y = y + jnp.exp(cs)[:, None] * jax.lax.dot_general(
        C, state, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    # state update: state = exp(cs[-1]) * state + Bᵀ (decay_to_end ⊙ x)
    decay_to_end = jnp.exp(cs[-1] - cs)            # (q,)
    bx = jax.lax.dot_general(B, decay_to_end[:, None] * x,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=F32)
    state_ref[...] = jnp.exp(cs[-1]) * state + bx

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def ssd_chunk_scan(x: jnp.ndarray, da: jnp.ndarray, Bm: jnp.ndarray,
                   Cm: jnp.ndarray, *, cfg: SSDConfig = None,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (BH, S, P); da: (BH, S); Bm, Cm: (BH, S, N) -> y (BH, S, P)."""
    cfg = cfg or SSDConfig()
    BH, S, P = x.shape
    N = Bm.shape[-1]
    q = cfg.chunk
    if S % q:
        raise ValueError(f"S={S} must divide chunk {q}")
    nc = S // q
    grid = (BH, nc)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, q), lambda b, c: (b, c)),
            pl.BlockSpec((1, q, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, q, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, da, Bm, Cm)
