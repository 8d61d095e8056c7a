"""Pallas TPU GEMM — the paper's first kernel family, MXU-native.

Implements the Table-1 optimization set as *config policies* (DESIGN.md §2):
  * MXU matmul           — jnp.dot with f32 ``preferred_element_type``
  * software pipelining  — Pallas grid double-buffering (HBM→VMEM)
  * stagger-K            — K-start rotation per (i, j) block to spread HBM
                           controller load (index-map policy)
  * split-K              — K partitioned across a parallel grid axis with a
                           partial-sum epilogue (small-M/N regime)
  * accumulate-in-VMEM   — f32 scratch accumulator (the AGPR analogue)

Every config is validated against the family's data-flow invariants
(:func:`repro.core.invariants.verify_gemm`) before lowering — see ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invariants import GemmConfig


def make_kernel(nk: int, n_axes: int):
    """Build the kernel body for an ``n_axes``-dim grid whose last axis is
    the K reduction."""

    def kernel(a_ref, b_ref, o_ref, acc_ref):
        k = pl.program_id(n_axes - 1)

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

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
                   static_argnames=("cfg", "out_dtype", "interpret"))
def gemm(a: jnp.ndarray, b: jnp.ndarray, *, cfg: GemmConfig = GemmConfig(),
         out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """C = A @ B via the validated Pallas kernel.

    Inputs are zero-padded to block multiples (the TPU analogue of
    HW OOB-guarded loads: padding keeps every lane in-bounds and is exact
    for a contraction).
    """
    m0, k0 = a.shape
    _, n0 = b.shape
    out_dtype = out_dtype or a.dtype
    bm, bn, bk = cfg.bm, cfg.bn, cfg.bk
    a = _pad_to(a, bm, bk)
    b = _pad_to(b, bk, bn)
    m, k = a.shape
    n = b.shape[1]
    mi, nj, nk_total = m // bm, n // bn, k // bk

    if cfg.split_k > 1:
        if nk_total % cfg.split_k:
            raise ValueError("split_k must divide the K block count")
        nk = nk_total // cfg.split_k
        grid = (cfg.split_k, mi, nj, nk)
        sem = ("parallel", "parallel", "parallel", "arbitrary")

        def a_idx(s, i, j, kk):
            return (i, s * nk + kk)

        def b_idx(s, i, j, kk):
            return (s * nk + kk, j)

        def o_idx(s, i, j, kk):
            return (s * mi + i, j)

        # partials stay f32: the split-K epilogue must reduce at accumulator
        # precision or cancellation across partials destroys accuracy
        out_shape = jax.ShapeDtypeStruct((cfg.split_k * m, n), jnp.float32)
    else:
        nk = nk_total
        grid = (mi, nj, nk)
        sem = ("parallel", "parallel", "arbitrary")
        if cfg.stagger_k:
            def a_idx(i, j, kk):
                return (i, (kk + i + j) % nk)

            def b_idx(i, j, kk):
                return ((kk + i + j) % nk, j)
        else:
            def a_idx(i, j, kk):
                return (i, kk)

            def b_idx(i, j, kk):
                return (kk, j)

        def o_idx(i, j, kk):
            return (i, j)

        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)

    out = pl.pallas_call(
        make_kernel(nk, len(grid)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_idx),
            pl.BlockSpec((bk, bn), b_idx),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_idx),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
        interpret=interpret,
    )(a, b)

    if cfg.split_k > 1:
        out = out.reshape(cfg.split_k, m, n).sum(axis=0,
                                                 dtype=jnp.float32)
        out = out.astype(out_dtype)
    return out[:m0, :n0]
