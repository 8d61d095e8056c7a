"""Pallas TPU fused MoE FFN — the paper's third kernel family.

TPU adaptation (DESIGN.md §2, §4): the MI300X kernel's sorted-map dispatch
becomes capacity-based expert-parallel dispatch (the TPU-native formulation:
static shapes, no dynamic gather inside the systolic pipeline):

  1. ``compute_dispatch`` (XLA): top-k routing table -> per-expert slots of
     fixed capacity C, dropping overflow (GShard-style).
  2. the **Pallas grouped-FFN kernel** (this module): for every expert
     block, gate/up projections + SwiGLU + down projection fused in one
     kernel, with the router gate applied in the epilogue (fused combine
     scaling) — d_ff is the sequential reduction axis of the down-proj
     accumulator.
  3. combine (XLA): scatter-add routed rows back to token positions.

The d_ff-blocked accumulation is the site of the ``y_depends_f`` and
``down_f_offset`` invariants; expert-block weight pairing is guarded by the
``w_by_block_index`` invariant (see repro.core.invariants.build_moe_program).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.invariants import MoEConfig


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _moe_kernel(x_ref, wg_ref, wu_ref, wd_ref, g_ref, y_ref, acc_ref, *,
                nf: int, fuse_gate: bool):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bt, DM)
    hg = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    hu = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    act = (_silu(hg) * hu).astype(x.dtype)         # (bt, bf)
    acc_ref[...] += jnp.dot(act, wd_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _flush():
        y = acc_ref[...]
        if fuse_gate:
            y = y * g_ref[0]                       # (bt, 1) gate scaling
        y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def grouped_ffn(x_routed: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
                wd: jnp.ndarray, gates_routed: Optional[jnp.ndarray] = None,
                *, cfg: MoEConfig = MoEConfig(),
                interpret: bool = False) -> jnp.ndarray:
    """x_routed: (E, C, DM) -> (E, C, DM); C % block_t == 0 required."""
    E, C, DM = x_routed.shape
    DF = wg.shape[-1]
    bt, bf = cfg.block_t, cfg.block_f
    if C % bt or DF % bf:
        raise ValueError(f"capacity {C} / d_ff {DF} must divide blocks "
                         f"({bt}, {bf})")
    fuse = cfg.fuse_gate and gates_routed is not None
    if gates_routed is None:
        gates_routed = jnp.ones((E, C, 1), jnp.float32)
    nt, nf = C // bt, DF // bf
    grid = (E, nt, nf)

    out = pl.pallas_call(
        functools.partial(_moe_kernel, nf=nf, fuse_gate=fuse),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, DM), lambda e, t, f: (e, t, 0)),
            pl.BlockSpec((1, DM, bf), lambda e, t, f: (e, 0, f)),
            pl.BlockSpec((1, DM, bf), lambda e, t, f: (e, 0, f)),
            pl.BlockSpec((1, bf, DM), lambda e, t, f: (e, f, 0)),
            pl.BlockSpec((1, bt, 1), lambda e, t, f: (e, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, DM), lambda e, t, f: (e, t, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, DM), x_routed.dtype),
        scratch_shapes=[pltpu.VMEM((bt, DM), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_routed, wg, wu, wd, gates_routed)
    return out


def compute_dispatch(expert_idx: jnp.ndarray, n_experts: int,
                     capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-based routing tables.

    expert_idx: (T, K) int32.  Returns (dest, keep):
      dest (T, K) int32 — flat slot ``e * C + rank`` for kept pairs,
      keep (T, K) bool  — False where the expert overflowed capacity.
    Deterministic: rank is assignment order (token-major), the GShard drop
    policy.
    """
    T, K = expert_idx.shape
    flat = expert_idx.reshape(-1)                                # (T*K,)
    onehot = (flat[:, None] == jnp.arange(n_experts)).astype(jnp.int32)
    ranks = (jnp.cumsum(onehot, axis=0) - 1)                     # (T*K, E)
    rank = jnp.take_along_axis(ranks, flat[:, None], axis=1)[:, 0]
    keep = rank < capacity
    dest = flat * capacity + jnp.minimum(rank, capacity - 1)
    return (dest.reshape(T, K).astype(jnp.int32),
            keep.reshape(T, K))
