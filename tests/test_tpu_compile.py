"""Compile rehearsal for the TPU: the served path's Pallas kernels and
whole steps at qwen3-1.7b's published widths, compiled for a v5e chip
that is described, not attached.

Nothing runs: each test lowers and compiles with the TPU compiler, so a
block shape Mosaic refuses, a kernel that overflows VMEM or a step that
cannot be partitioned fails here, at no chip time.  The topology is
described inside a fixture (never at import), the kernels must appear in
the compiled program as ``tpu_custom_call``, and JAX's persistent
compilation cache is off around the compiles (a TPU executable written
here cannot be read back without a chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.families.ragged_prefill import RaggedPrefillConfig
from repro.kernels.paged_attention.ops import default_config
from repro.kernels.paged_attention.paged_attention import paged_decode
from repro.kernels.ragged_prefill.ops import verified_config
from repro.kernels.ragged_prefill.ragged_prefill import ragged_prefill
from repro.models import build
from repro.serve.pool import KVPool

ARCH = "qwen3-1.7b"
PAGE, ROWS, MAX_LEN, POOL_PAGES = 16, 8, 2048, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return build(configs.get_config(ARCH))


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_decode_kernel(one_chip, model):
    cfg = model.cfg
    D, NP = cfg.resolved_head_dim, MAX_LEN // PAGE
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((POOL_PAGES, cfg.n_kv_heads, PAGE, D), jnp.bfloat16)
    _compile(lambda q, k, v, t, n: paged_decode(
        q, k, v, t, n, cfg=default_config(NP)),
        S((ROWS, cfg.n_heads, 1, D), jnp.bfloat16), pool, pool,
        S((ROWS, NP), jnp.int32), S((ROWS,), jnp.int32))


# packed extents the engine emits (both padded to 64 tokens); the first
# two were refused at 64-wide blocks by the old (1, block) metadata layout
@pytest.mark.parametrize("tq,tk", [(64, 192), (192, 448), (64, 64),
                                   (256, 512), (320, 1216), (1344, 1344)])
def test_ragged_prefill_kernel_at_engine_extents(one_chip, model, tq, tk):
    cfg = model.cfg
    D = cfg.resolved_head_dim
    kcfg = verified_config(tq, tk, 2, q_heads=cfg.n_heads,
                           kv_heads=cfg.n_kv_heads, head_dim=D)
    assert kcfg is not None, "the gate refused an engine geometry"
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv = S((cfg.n_kv_heads, tk, D), jnp.bfloat16)
    _compile(lambda *a: ragged_prefill(*a, cfg=kcfg),
             S((cfg.n_heads, tq, D), jnp.bfloat16), kv, kv,
             S((tq,), jnp.int32), S((tq,), jnp.int32),
             S((tk,), jnp.int32), S((tk,), jnp.int32))


def test_gate_and_compiler_refuse_the_same_blocks(one_chip, model):
    """block_q=4 breaks the (8, 128)-or-full-dim rule: the ARGUS gate
    rejects it and so does the compiler."""
    cfg = model.cfg
    D, tq, tk = cfg.resolved_head_dim, 64, 192
    bad = RaggedPrefillConfig(block_q=4, block_kv=64)
    assert verified_config(tq, tk, 2, q_heads=cfg.n_heads,
                           kv_heads=cfg.n_kv_heads, head_dim=D,
                           cfg=bad) is None
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv = S((cfg.n_kv_heads, tk, D), jnp.bfloat16)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        jax.jit(lambda *a: ragged_prefill(*a, cfg=bad)).lower(
            S((cfg.n_heads, tq, D), jnp.bfloat16), kv, kv,
            S((tq,), jnp.int32), S((tq,), jnp.int32),
            S((tk,), jnp.int32), S((tk,), jnp.int32)).compile()


def _params_and_pool(model, one_chip):
    pool = jax.eval_shape(lambda: KVPool(model, POOL_PAGES, PAGE).storage)
    return _on(one_chip, model.abstract()), _on(one_chip, pool)


def test_whole_decode_step(one_chip, model):
    params, pool = _params_and_pool(model, one_chip)
    NP = MAX_LEN // PAGE
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    kc = default_config(NP)
    _compile(lambda p, pl, t, tok, pos, n: model.decode_step_paged(
        p, pl, t, tok, pos, n, kernel_cfg=kc),
        params, pool, i32(ROWS, NP), i32(ROWS, 1), i32(ROWS), i32(ROWS))


def test_whole_prefill_step(one_chip, model):
    params, pool = _params_and_pool(model, one_chip)
    cfg, tq, tk = model.cfg, 256, 512
    kc = verified_config(tq, tk, 2, q_heads=cfg.n_heads,
                         kv_heads=cfg.n_kv_heads,
                         head_dim=cfg.resolved_head_dim)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    _compile(lambda p, pl, *meta: model.prefill_chunk_packed(
        p, pl, *meta, kernel_cfg=kc),
        params, pool, i32(1, tq), i32(tq), i32(tq), i32(tk), i32(tk),
        i32(tq), i32(tq), i32(tk), i32(tk))
