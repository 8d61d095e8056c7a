"""ragged_prefill family: cross-sequence leakage invariants, the
pre-solver offset-bound catch, fault-menu gating, the interpret-mode
kernel vs the masked dense oracle, and the poisoned-KV leakage canary
(foreign-sequence / padding KV slots full of sentinel garbage must
leave every other sequence's output bit-identical)."""
import numpy as np
import pytest

from repro.core.families import get_family
from repro.core.verify_engine import VerificationEngine

FAM = get_family("ragged_prefill")
CFG = FAM.config_cls(block_q=64, block_kv=64)
# 3 packed sequences in a 512-token buffer, GQA 8:2 heads
PROB = FAM.problem_cls(3, 512, 8, 2, 128)


class TestLeakageInvariants:
    def test_good_config_proves_all_assertions(self):
        res = FAM.verify(CFG, PROB)
        assert res.hard_ok, res.render()

    def test_offset_oob_caught_before_the_solver(self):
        """The acceptance property: a cu_seqlens table whose declared
        range escapes the packed buffer is caught *structurally*
        (interval arithmetic at the analysis stage), before any solver
        search."""
        eng = VerificationEngine()
        res = eng.verify("ragged_prefill", CFG, PROB, inject_bug="cu_oob")
        assert not res.hard_ok
        assert res.violations
        for f in res.violations:
            assert f.stage == "analysis", \
                f"cu_oob leaked to stage {f.stage}"
        assert all("assert_in_range(segment offset" in f.assertion_id
                   for f in res.violations)

    @pytest.mark.parametrize("bug", ["cross_seq_leak", "causal_off_by_one",
                                     "wrong_cu_base"])
    def test_leakage_gate_faults_yield_solver_counterexamples(self, bug):
        """The three leakage-mask faults break the gate conformity
        assertion with a concrete counterexample at the solver stage —
        a cross-boundary read, an off-by-one causal bound and a
        mis-based offset all surface as the same invariant class: the
        weight entering the accumulator does not carry the
        (seg_q, seg_k, pos_q, pos_k) quadruple its gate admitted."""
        eng = VerificationEngine()
        res = eng.verify("ragged_prefill", CFG, PROB, inject_bug=bug)
        assert not res.hard_ok
        bad = [f for f in res.violations if f.stage == "solver"
               and f.counterexample is not None]
        assert bad, [f.assertion_id for f in res.violations]
        ce = bad[0].counterexample
        assert ce.env or ce.detail, "no concrete witness"
        assert bad[0].repair_hint
        # only gate conformity fires — coverage/stability stay proven
        assert all("assert_conform" in f.assertion_id
                   for f in res.violations), \
            [f.assertion_id for f in res.violations]

    def test_segment_skip_and_replay_hit_the_coverage_machinery(self):
        skip = FAM.verify(CFG, PROB, inject_bug="segment_skip")
        assert not skip.hard_ok
        assert any("coverage" in label for label, r
                   in skip.report.violations)
        replay = FAM.verify(CFG, PROB, inject_bug="segment_replay")
        assert not replay.hard_ok
        assert any("disjoint" in label for label, r
                   in replay.report.violations)

    def test_tail_mask_and_carry_faults_are_caught(self):
        assert not FAM.verify(CFG, PROB,
                              inject_bug="mask_dropped_tail").hard_ok
        assert not FAM.verify(CFG, PROB,
                              inject_bug="acc_depends_kv").hard_ok

    def test_fault_menu_gating(self):
        mha = FAM.problem_cls(3, 512, 8, 8, 128)
        assert "wrong_kv_head" not in FAM.bugs_for(CFG, mha)
        assert "wrong_kv_head" in FAM.bugs_for(CFG, PROB)
        # one kv block == the whole packed range: nothing to skip/replay
        whole = FAM.config_cls(block_q=64, block_kv=512)
        menu = FAM.bugs_for(whole, PROB)
        assert "segment_skip" not in menu
        assert "segment_replay" not in menu

    def test_structural_capacity_and_tiling_checks(self):
        overfull = FAM.problem_cls(600, 512, 8, 2, 128)
        assert any(s.kind == "capacity"
                   for s in FAM.structural(CFG, overfull))
        ragged = FAM.problem_cls(3, 500, 8, 2, 128)
        assert any(s.kind == "masking"
                   for s in FAM.structural(CFG, ragged))

    @pytest.mark.parametrize("bq,bkv", [(4, 64), (64, 4), (64, 12)])
    def test_blocks_the_tpu_compiler_refuses_are_hard_issues(self, bq,
                                                             bkv):
        """Every tiled operand, the seg/pos metadata included, must
        have a trailing block pair divisible by (8, 128) or equal to
        the array's: the gate rejects what Mosaic would refuse."""
        from repro.kernels.ragged_prefill import verified_config
        cfg = FAM.config_cls(block_q=bq, block_kv=bkv)
        issues = [s for s in FAM.structural(cfg, FAM.problem_cls(
            2, 192, 16, 8, 128)) if s.hard]
        assert issues and all(s.kind == "block_shape" for s in issues)
        assert verified_config(64, 192, 2, q_heads=16, kv_heads=8,
                               head_dim=128, cfg=cfg) is None

    @pytest.mark.parametrize("tq,tk", [(64, 64), (64, 192), (192, 448),
                                       (256, 512), (320, 1216)])
    def test_engine_extents_get_a_legal_verified_config(self, tq, tk):
        """The serving engine pads both packed extents to 64 tokens;
        the config the gate picks for each has only legal blocks (the
        compile side of this is tests/test_tpu_compile.py)."""
        from repro.core.families.ragged_prefill import kernel_blocks
        from repro.core.kernelspec import check_block_shapes
        from repro.kernels.ragged_prefill import verified_config
        cfg = verified_config(tq, tk, 2, q_heads=16, kv_heads=8,
                              head_dim=128)
        assert cfg is not None
        assert check_block_shapes(kernel_blocks(
            cfg, total_q=tq, total_k=tk, q_heads=16, kv_heads=8,
            head_dim=128)) == []

    def test_blocks_must_tile_the_packed_buffer(self):
        eng = VerificationEngine()
        res = eng.verify("ragged_prefill",
                         FAM.config_cls(block_q=96, block_kv=64), PROB)
        assert res.build_error is not None
        assert any(f.stage == "build" for f in res.violations)


def _packed_case(lens, total, H=4, HK=2, D=32, seed=0, dtype=np.float32):
    import jax.numpy as jnp
    from repro.kernels.ragged_prefill import cu_seqlens, ragged_metadata
    rng = np.random.default_rng(seed)
    cu = cu_seqlens(lens)
    seg, pos = ragged_metadata(cu, total)
    q = jnp.asarray(rng.normal(size=(H, total, D)), dtype)
    k = jnp.asarray(rng.normal(size=(HK, total, D)), dtype)
    v = jnp.asarray(rng.normal(size=(HK, total, D)), dtype)
    return q, k, v, seg, pos, cu


class TestOracle:
    def test_ragged_lengths_match_the_masked_oracle(self):
        """Interpret-mode kernel vs the dense masked oracle on a ragged
        packing with an empty sequence and a padded tail."""
        from repro.core.families.ragged_prefill import RaggedPrefillConfig
        from repro.kernels.ragged_prefill import (ragged_prefill_attend,
                                                  ragged_prefill_ref)
        q, k, v, seg, pos, cu = _packed_case([60, 0, 100], 192)
        cfg = RaggedPrefillConfig(block_q=32, block_kv=32)
        got = ragged_prefill_attend(q, k, v, seg, pos, seg, pos,
                                    cfg=cfg, interpret=True)
        want = ragged_prefill_ref(q, k, v, seg, pos, seg, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # padding queries read nothing: exact zero rows
        assert float(np.abs(np.asarray(got)[:, 160:]).max()) == 0.0

    def test_full_buffer_single_sequence_is_plain_causal(self):
        """One sequence spanning the whole buffer degenerates to plain
        causal attention — cross-check against the flash oracle."""
        from repro.core.families.ragged_prefill import RaggedPrefillConfig
        from repro.kernels.flash_attention.ref import mha_ref
        from repro.kernels.ragged_prefill import ragged_prefill_attend
        q, k, v, seg, pos, _cu = _packed_case([128], 128)
        got = ragged_prefill_attend(
            q, k, v, seg, pos, seg, pos,
            cfg=RaggedPrefillConfig(block_q=32, block_kv=32),
            interpret=True)
        want = mha_ref(q[None], k[None], v[None], causal=True)[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_poisoned_foreign_kv_never_reaches_other_sequences(self):
        """The leakage canary: fill one sequence's KV tokens AND every
        padding slot with sentinel garbage — all *other* sequences'
        outputs must be bit-identical to the clean run, and padding
        rows stay exactly zero.  (The runtime mirror of the family's
        gate-conformity invariant; extends the PR-8 poisoned-page
        oracle test to the prefill path.)"""
        from repro.core.families.ragged_prefill import RaggedPrefillConfig
        from repro.kernels.ragged_prefill import ragged_prefill_attend
        q, k, v, seg, pos, cu = _packed_case([48, 64, 30], 192, seed=3)
        cfg = RaggedPrefillConfig(block_q=32, block_kv=32)
        kw = dict(cfg=cfg, interpret=True)
        clean = np.asarray(ragged_prefill_attend(
            q, k, v, seg, pos, seg, pos, **kw))
        k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
        lo, hi = int(cu[1]), int(cu[2])      # sequence 1's packed span
        k2[:, lo:hi] = 1e6
        v2[:, lo:hi] = 1e6
        k2[:, int(cu[-1]):] = 1e6            # every padding slot
        v2[:, int(cu[-1]):] = 1e6
        import jax.numpy as jnp
        poisoned = np.asarray(ragged_prefill_attend(
            q, jnp.asarray(k2), jnp.asarray(v2), seg, pos, seg, pos,
            **kw))
        np.testing.assert_array_equal(clean[:, :lo], poisoned[:, :lo])
        np.testing.assert_array_equal(clean[:, hi:int(cu[-1])],
                                      poisoned[:, hi:int(cu[-1])])
        assert float(np.abs(poisoned[:, int(cu[-1]):]).max()) == 0.0

    def test_poisoned_padding_leaves_everything_bit_identical(self):
        """Sentinel garbage confined to padding (past cu[S]) must leave
        the *entire* output bit-identical — kernel and oracle agree."""
        import jax.numpy as jnp
        from repro.core.families.ragged_prefill import RaggedPrefillConfig
        from repro.kernels.ragged_prefill import (ragged_prefill_attend,
                                                  ragged_prefill_ref)
        q, k, v, seg, pos, cu = _packed_case([50, 70], 160, seed=5)
        cfg = RaggedPrefillConfig(block_q=32, block_kv=32)
        k2, v2 = np.asarray(k).copy(), np.asarray(v).copy()
        k2[:, int(cu[-1]):] = 1e6
        v2[:, int(cu[-1]):] = 1e6
        for fn, kw in ((ragged_prefill_attend,
                        dict(cfg=cfg, interpret=True)),
                       (ragged_prefill_ref, {})):
            clean = np.asarray(fn(q, k, v, seg, pos, seg, pos, **kw))
            poisoned = np.asarray(fn(q, jnp.asarray(k2), jnp.asarray(v2),
                                     seg, pos, seg, pos, **kw))
            np.testing.assert_array_equal(clean, poisoned)

    @pytest.mark.slow
    def test_interpret_mode_matches_dense_oracle(self):
        assert FAM.reference_check(CFG, PROB)

    def test_validated_entry_rejects_non_tiling_blocks(self):
        import jax.numpy as jnp
        from repro.core.families.ragged_prefill import RaggedPrefillConfig
        from repro.kernels.ragged_prefill import (InvariantViolation,
                                                  ragged_prefill_attend)
        q = jnp.zeros((2, 64, 32), jnp.float32)
        k = jnp.zeros((1, 64, 32), jnp.float32)
        seg = jnp.zeros((64,), jnp.int32)
        with pytest.raises(InvariantViolation):
            ragged_prefill_attend(
                q, k, k, seg, seg, seg, seg,
                cfg=RaggedPrefillConfig(block_q=48, block_kv=32),
                interpret=True)

    def test_verified_config_gate(self):
        from repro.kernels.ragged_prefill import verified_config
        cfg = verified_config(256, 256, 4, q_heads=8, kv_heads=2,
                              head_dim=64)
        assert cfg is not None
        assert 256 % cfg.block_q == 0 and 256 % cfg.block_kv == 0
        # a geometry no block can tile is unverifiable -> dense fallback
        assert verified_config(100, 100, 4, q_heads=8, kv_heads=2,
                               head_dim=64) is None
