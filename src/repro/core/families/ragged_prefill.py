"""Ragged-prefill attention family — packed variable-length prefill
(the chunked-prefill kernel ROADMAP item 1 needs).

Prefill packs every pending sequence's prompt chunk into one token
buffer: queries and KV both live at *packed* offsets, and the only
record of which token belongs to which sequence is the cu_seqlens
offset vector (segment s spans ``[cu(s), cu(s+1))``).  The family
models that metadata as uninterpreted applications — ``seg(t) ∈ [0, S)``
(packed token → segment) and ``cu(s) ∈ [0, T]`` (segment → packed start
offset) — and makes every tile carry (sequence-id, position)
provenance, where position is the *segment-relative* offset
``t - cu(seg(t))``:

  * **offset-bound** — every segment offset the mask consumes stays
    inside the packed buffer (``assert_in_range``): a cu_seqlens table
    whose declared range escapes ``[0, T]`` is rejected at the
    *analysis* stage, pre-solver;
  * **GQA head mapping** — as in the dense families;
  * **no cross-sequence leakage** — the segment/causal gate that zeroes
    a score carries the (seg_q, seg_k, pos_q, pos_k) quadruple of the
    score it gates, and the weight entering the accumulator must
    conform with that gate: every attended KV element provably belongs
    to the query's sequence with position ≤ the query's position.  A
    gate whose segment id was hoisted to the query block's first row
    (cross-boundary leak), an off-by-one causal bound, or positions
    computed from the wrong cu_seqlens base all yield concrete
    counterexamples;
  * **tail masking** — packed buffers are padded past ``cu(S)``; the
    tail gate's (packed position, total) provenance catches a mask
    applied at block granularity (the classic dropped-tail bug);
  * **packed coverage** — across kv-block steps the packed KV range is
    read exactly once per (head, query block): skip / replay bugs
    surface as coverage / disjointness counterexamples on a
    read-marker tensor;
  * **carried-output stability** — the online-softmax accumulator must
    not depend on the sequential kv-block axis.

The oracle (``reference_check``) runs the Pallas kernel in interpret
mode against the dense masked oracle
(:func:`repro.kernels.ragged_prefill.ref.ragged_prefill_ref`).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

from .. import dsl
from ..costs import (CostEstimate, HBM_BW, PEAK_FLOPS, occupancy,
                     sol_estimate)
from ..kernelspec import (DTYPE_BYTES, StructuralIssue, check_alignment,
                          check_block_shapes, check_vmem)
from ..tags import Expr, app, make_tag
from .base import (BugSignature, KernelFamily, generic_skill,
                   register)


@dataclass(frozen=True)
class RaggedPrefillProblem:
    n_seqs: int               # packed segments (sequences) per batch
    total_tokens: int         # packed buffer length T (padding included)
    q_heads: int
    kv_heads: int
    head_dim: int
    dtype: str = "bf16"

    @property
    def group(self) -> int:
        return self.q_heads // self.kv_heads

    @property
    def avg_len(self) -> float:
        return self.total_tokens / max(self.n_seqs, 1)


@dataclass(frozen=True)
class RaggedPrefillConfig:
    """Tunable knobs (the harness' action space for this family)."""

    block_q: int = 128        # packed query rows per grid step
    block_kv: int = 128       # packed kv columns per sequential step

    def name(self) -> str:
        return f"ragged[bq={self.block_q},bkv={self.block_kv}]"


def build_ragged_prefill_program(cfg: RaggedPrefillConfig,
                                 prob: RaggedPrefillProblem,
                                 *, inject_bug: Optional[str] = None
                                 ) -> dsl.TileProgram:
    """Packed self-attention masked by segment identity and causality.

    ``inject_bug`` deliberately mis-lowers one aspect (the fault model's
    menu; every entry must be caught).  Supported:
    "cu_oob"           — cu_seqlens declared with a result range past the
                         packed buffer (caught at the analysis stage by
                         the interval check, pre-solver);
    "wrong_kv_head"    — KV read for head h instead of h // group;
    "cross_seq_leak"   — the segment/causal gate's query segment id is
                         hoisted to the query block's first row, so a
                         block straddling a sequence boundary attends
                         across it;
    "causal_off_by_one"— the gate admits kv position pos_q + 1
                         (<= instead of <, shifted);
    "wrong_cu_base"    — the gate's positions are computed from the
                         *next* segment's cu_seqlens entry (a 1-based /
                         0-based confusion on the offset vector);
    "segment_skip"     — the sequential kv grid is one block short;
    "segment_replay"   — the kv block offset is dropped, so every step
                         re-reads the first packed block;
    "mask_dropped_tail"— the padding-tail gate is applied at block
                         granularity (its provenance is the block's
                         first column), so a partial trailing block
                         admits padding tokens past cu(S);
    "acc_depends_kv"   — the carried output tagged with the kv axis.
    """
    T, S, D = prob.total_tokens, prob.n_seqs, prob.head_dim
    H, HK, G = prob.q_heads, prob.kv_heads, prob.group
    bq, bkv = cfg.block_q, cfg.block_kv
    if T % bq or T % bkv:
        raise ValueError(
            f"block_q {bq} and block_kv {bkv} must tile the packed "
            f"buffer ({T} tokens)")
    nq = T // bq
    nk = T // bkv
    if inject_bug == "segment_skip":
        nk = max(1, nk - 1)
    if inject_bug == "wrong_kv_head" and H == HK:
        raise ValueError("wrong_kv_head requires GQA")

    p = dsl.TileProgram(cfg.name())
    hq = p.add_grid("hq", H, "parallel")
    qb = p.add_grid("qb", nq, "parallel")
    kb = p.add_grid("kb", nk, "arbitrary")

    p.tensor("Q", (H, T, D), prob.dtype,
             tag_fn=lambda h, t, c: make_tag(h // G, t, c))
    p.tensor("K", (HK, T, D), prob.dtype)
    p.tensor("V", (HK, T, D), prob.dtype)
    # read-marker: the packed kv rows this (hq, qb, kb) step consumed
    p.tensor("KV_READ", (H * nq, T, D), prob.dtype, kind="output")
    p.tensor("O", (H, T, D), "f32", kind="output")

    hk = hq if inject_bug == "wrong_kv_head" else hq // G

    # the packing metadata: segment ids and cu_seqlens offsets are
    # runtime routing data (like paged attention's block table), modeled
    # as uninterpreted applications.  An out-of-range offset vector
    # models packing metadata that can point past the buffer.
    cu_extent = T + 2 if inject_bug == "cu_oob" else T + 1
    sg = lambda t: app("seg_id", t, S)
    cu = lambda s: app("cu_seqlens", s, cu_extent)
    pos = lambda t: t - cu(sg(t))
    # total valid tokens: everything at or past cu(S) is packing padding
    cu_total = cu(Expr.of(S))

    tq0, tk0 = qb * bq, kb * bkv
    if inject_bug == "segment_replay":
        tk0 = kb * 0             # block offset dropped: block 0 again

    # invariant 1 — offset-bound: every segment offset the mask consumes
    # stays inside the packed buffer (interval verdict: analysis stage)
    p.assert_in_range(cu(sg(tq0)), T + 1, "segment offset (q)")
    p.assert_in_range(cu(sg(tk0)), T + 1, "segment offset (kv)")
    p.assert_in_range(cu_total, T + 1, "segment offset (total)")

    q = p.squeeze(p.load("Q", (hq, tq0, 0), (1, bq, D)))
    k = p.squeeze(p.load("K", (hk, tk0, 0), (1, bkv, D)))
    v = p.squeeze(p.load("V", (hk, tk0, 0), (1, bkv, D)))

    # invariant 2 — GQA head mapping (q's kv-group == loaded kv head)
    p.assert_conform(q, k, bind=((1, 1),), components=((0,), (0,)))

    # relabel packed tiles with their (segment, position) provenance —
    # the tags the leakage mask consumes; identity components stay
    # asserted (packed row and channel)
    q_seg = p.elementwise(
        "seg_relabel", q,
        retag=lambda i, c, _o=tq0: make_tag(
            hq // G, sg(_o + i), pos(_o + i), c))
    p.assert_conform(q, q_seg, bind=((0, 0), (1, 1)),
                     components=((0, 2), (0, 3)))
    k_seg = p.elementwise(
        "seg_relabel", k,
        retag=lambda j, c, _o=tk0: make_tag(
            hk, sg(_o + j), pos(_o + j), c))
    p.assert_conform(k, k_seg, bind=((0, 0), (1, 1)),
                     components=((0, 2), (0, 3)))
    v_seg = p.elementwise(
        "seg_relabel", v,
        retag=lambda j, c, _o=tk0: make_tag(
            hk, sg(_o + j), pos(_o + j), c))

    # invariant 5 — packed coverage: across (hq, qb, kb) the packed kv
    # range is read exactly once per (head, query block)
    p.store("KV_READ", k_seg, (hq * nq + qb, tk0, 0))

    st_tag = lambda i, j, _q=tq0, _k=tk0: make_tag(
        sg(_q + i), sg(_k + j), pos(_q + i), pos(_k + j))
    st = p.matmul(q_seg, p.transpose(k_seg), retag=st_tag)
    # invariant 3 — position honesty: the score's declared kv
    # (segment, position) is that of the key it was computed from
    p.assert_conform(st, k_seg, bind=((1, 0),),
                     components=((1, 3), (1, 2)))

    pt = p.elementwise("exp_sub_m", st, retag=st_tag)
    # the weighted value consumes the same (segment, position) pairs
    p.assert_conform(pt, v_seg, bind=((1, 0),),
                     components=((1, 3), (1, 2)))

    # invariant 4 — leakage-gate conformity: the segment/causal gate
    # admits a score only when the kv element belongs to the query's
    # sequence (seg_q == seg_k) at a position not past the query's
    # (pos_k <= pos_q).  The gate's tag carries the exact
    # (seg_q, seg_k, pos_q, pos_k) quadruple it gated, and the weight
    # entering the accumulator must conform with it — so cross-sequence
    # reads, off-by-one causality and mis-based offsets are all
    # solver-refutable, not silent.
    if inject_bug == "cross_seq_leak":
        # query segment id hoisted to the block's first row: rows past
        # a sequence boundary inside the block leak across it
        gate_tag = lambda i, j, _q=tq0, _k=tk0: make_tag(
            sg(_q), sg(_k + j), pos(_q + i), pos(_k + j))
    elif inject_bug == "causal_off_by_one":
        # gate admits kv position pos_q + 1 (<= instead of <, shifted)
        gate_tag = lambda i, j, _q=tq0, _k=tk0: make_tag(
            sg(_q + i), sg(_k + j), pos(_q + i) + 1, pos(_k + j))
    elif inject_bug == "wrong_cu_base":
        # positions measured from the NEXT segment's start offset
        wpos = lambda t: t - cu(sg(t) + 1)
        gate_tag = lambda i, j, _q=tq0, _k=tk0: make_tag(
            sg(_q + i), sg(_k + j), wpos(_q + i), wpos(_k + j))
    else:
        gate_tag = st_tag
    gate = p.elementwise("seg_causal_gate", st, retag=gate_tag)
    ptg = p.elementwise("apply_seg_gate", pt, gate, retag=st_tag)
    p.assert_conform(ptg, gate, bind=((0, 0), (1, 1)),
                     components=((0, 1, 2, 3), (0, 1, 2, 3)))

    # invariant 4b — tail gate: packed positions at or past cu(S) are
    # padding and must die before the accumulator.  Its provenance is
    # (packed kv position, total): a gate applied at block granularity
    # carries the block's first column instead and fails to conform.
    if inject_bug == "mask_dropped_tail":
        tail_tag = lambda i, j, _k=tk0: make_tag(_k, cu_total)
    else:
        tail_tag = lambda i, j, _k=tk0: make_tag(_k + j, cu_total)
    tail = p.elementwise("tail_gate", st, retag=tail_tag)
    pt2 = p.elementwise(
        "apply_tail_gate", ptg, tail,
        retag=lambda i, j, _k=tk0: make_tag(_k + j, cu_total))
    p.assert_conform(pt2, tail, bind=((0, 0), (1, 1)),
                     components=((0, 1), (0, 1)))

    o_part = p.matmul(pt2, v_seg,
                      retag=lambda i, c, _q=tq0: make_tag(hq, _q + i, c))
    acc = p.alloc((bq, D), "f32")
    if inject_bug == "acc_depends_kv":
        acc_tag = lambda i, c, _q=tq0: make_tag(hq, _q + i, Expr.of(kb), c)
    else:
        acc_tag = lambda i, c, _q=tq0: make_tag(hq, _q + i, c)
    p.update(acc, o_part, fn="flash_acc", retag=acc_tag)

    # invariant 6 — online-softmax carry is stable across the kv axis
    p.assert_stable(acc, "kb")
    p.assert_disjoint_writes("KV_READ", axes=("hq", "qb", "kb"))
    p.assert_coverage("KV_READ")

    p.store("O", acc, (hq, tq0, 0))
    p.assert_disjoint_writes("O", axes=("hq", "qb"))
    p.assert_coverage("O")
    return p


def kernel_blocks(cfg: RaggedPrefillConfig, *, total_q: int, total_k: int,
                  q_heads: int, kv_heads: int, head_dim: int):
    """operand -> (block shape, array shape) for every operand the
    Pallas kernel tiles: the one layout :mod:`repro.kernels
    .ragged_prefill` builds its BlockSpecs from and the structural gate
    checks against the TPU compiler's block rule.  The per-token
    metadata rides as a (TQ, 1) column tiled (block_q, 1) on the query
    side and as (TK / block_kv, 1, block_kv) rows tiled (1, 1, block_kv)
    on the kv side: the kernel reads them as the (bq, 1) and (1, bkv)
    operands of its mask with no relayout, and the kv block's trailing
    pair equals the array's for every block_kv."""
    bq, bkv, D = cfg.block_q, cfg.block_kv, head_dim
    q = ((1, bq, D), (q_heads, total_q, D))
    kv = ((1, bkv, D), (kv_heads, total_k, D))
    q_meta = ((bq, 1), (total_q, 1))
    kv_meta = ((1, 1, bkv), (total_k // bkv, 1, bkv))
    return {"Q": q, "K": kv, "V": kv, "O": q,
            "seg_q": q_meta, "pos_q": q_meta,
            "seg_k": kv_meta, "pos_k": kv_meta}


def structural_ragged_prefill(cfg: RaggedPrefillConfig,
                              prob: RaggedPrefillProblem):
    issues = []
    # the family models packed self-attention: one token axis for both
    issues += check_block_shapes(kernel_blocks(
        cfg, total_q=prob.total_tokens, total_k=prob.total_tokens,
        q_heads=prob.q_heads, kv_heads=prob.kv_heads,
        head_dim=prob.head_dim))
    if prob.total_tokens % cfg.block_q or prob.total_tokens % cfg.block_kv:
        issues.append(StructuralIssue(
            "masking", f"blocks ({cfg.block_q}, {cfg.block_kv}) do not "
                       f"tile the packed buffer ({prob.total_tokens} "
                       f"tokens) — pad before packing"))
    if prob.n_seqs > prob.total_tokens:
        issues.append(StructuralIssue(
            "capacity", f"{prob.n_seqs} segments cannot pack into "
                        f"{prob.total_tokens} tokens"))
    issues += check_alignment("K", (cfg.block_kv, prob.head_dim),
                              prob.dtype)
    issues += check_vmem(
        {"Q": ((cfg.block_q, prob.head_dim), prob.dtype),
         "K": ((cfg.block_kv, prob.head_dim), prob.dtype),
         "V": ((cfg.block_kv, prob.head_dim), prob.dtype),
         "S": ((cfg.block_q, cfg.block_kv), "f32")},
        scratch={"acc": ((cfg.block_q, prob.head_dim), "f32"),
                 "m": ((cfg.block_q, 1), "f32"),
                 "l": ((cfg.block_q, 1), "f32")})
    return issues


def ragged_prefill_cost(cfg: RaggedPrefillConfig,
                        prob: RaggedPrefillProblem) -> CostEstimate:
    """Flash-style packed prefill: each (head, query-block) step streams
    the whole packed KV, so smaller query blocks trade occupancy against
    KV re-reads — the block_q/block_kv pair the harness tunes."""
    sz = DTYPE_BYTES.get(prob.dtype, 2)
    T, D = prob.total_tokens, prob.head_dim
    H, HK = prob.q_heads, prob.kv_heads
    nq = max(T // cfg.block_q, 1)
    # causal within each segment: ~half the full packed score rectangle
    flops = 4.0 * H * T * (prob.avg_len / 2.0) * D
    q_bytes = 2 * H * T * D * sz                      # Q in, O out (f32~)
    kv_bytes = 2 * HK * T * D * sz
    meta_bytes = (prob.n_seqs + 1) * 4 + 2 * T * 4    # cu + seg/pos ids
    util = occupancy(H * nq) * min(
        1.0, cfg.block_q * cfg.block_kv / (128.0 * 128.0)) * 0.7
    return CostEstimate(
        compute_s=flops / (PEAK_FLOPS * max(util, 1e-3)),
        memory_s=(q_bytes + nq * kv_bytes + meta_bytes) / HBM_BW,
        flops=flops, hbm_bytes=q_bytes + nq * kv_bytes + meta_bytes)


def ragged_prefill_sol(prob: RaggedPrefillProblem) -> CostEstimate:
    """Speed of light: one dense-rate pass over the packed Q/KV/O plus
    the packing metadata — KV re-reads are a config artifact and do not
    appear in the floor."""
    sz = DTYPE_BYTES.get(prob.dtype, 2)
    T, D = prob.total_tokens, prob.head_dim
    H, HK = prob.q_heads, prob.kv_heads
    flops = 4.0 * H * T * (prob.avg_len / 2.0) * D
    traffic = (2 * H * T * D + 2 * HK * T * D) * sz \
        + (prob.n_seqs + 1) * 4 + 2 * T * 4
    return sol_estimate(flops, traffic)


# -- skills -----------------------------------------------------------------

def _block_steps(cfg: RaggedPrefillConfig, prob: RaggedPrefillProblem):
    out = []
    for field in ("block_q", "block_kv"):
        cur = getattr(cfg, field)
        for nxt in (cur * 2, cur // 2):
            if 8 <= nxt <= 512 and prob.total_tokens % nxt == 0:
                out.append((f"{field}={nxt}",
                            replace(cfg, **{field: nxt})))
    return out


SKILLS = (
    generic_skill("retile", "ragged_prefill", _block_steps),
    generic_skill("software_pipelining", "ragged_prefill"),
    generic_skill("vectorized_io", "ragged_prefill"),
    generic_skill("f32_vmem_accumulate", "ragged_prefill"),
)


# -- fault model ------------------------------------------------------------

INJECTABLE_BUGS = ("cu_oob", "wrong_kv_head", "cross_seq_leak",
                   "causal_off_by_one", "wrong_cu_base", "segment_skip",
                   "segment_replay", "mask_dropped_tail",
                   "acc_depends_kv")


def compatible_bugs(cfg: RaggedPrefillConfig,
                    prob: RaggedPrefillProblem):
    menu = list(INJECTABLE_BUGS)
    if prob.q_heads == prob.kv_heads:
        menu.remove("wrong_kv_head")
    if cfg.block_q < 2:
        menu.remove("cross_seq_leak")   # one row per block: no hoist
    if cfg.block_kv < 2:
        menu.remove("mask_dropped_tail")  # no partial-block tail
    if prob.total_tokens // cfg.block_kv < 2:
        menu.remove("segment_skip")     # one block IS the whole range
        menu.remove("segment_replay")   # nothing to replay into
    return menu


# Ground truth (tests/test_families.py checks it against live feedback).
# segment_replay additionally under-covers the packed KV range, but only
# the disjointness pattern is *its* fingerprint.
BUG_SIGNATURES = (
    BugSignature("cu_oob", ("analysis",),
                 ("assert_in_range(segment offset",)),
    BugSignature("wrong_kv_head", ("solver",),
                 ("assert_conform(sq_1,sq_3)",)),
    BugSignature("cross_seq_leak", ("solver",),
                 ("assert_conform(e_13,e_12)",)),
    BugSignature("causal_off_by_one", ("solver",),
                 ("assert_conform(e_13,e_12)",)),
    BugSignature("wrong_cu_base", ("solver",),
                 ("assert_conform(e_13,e_12)",)),
    BugSignature("segment_skip", ("solver",),
                 ("assert_coverage(KV_READ)",)),
    BugSignature("segment_replay", ("solver",),
                 ("assert_disjoint(KV_READ)",)),
    BugSignature("mask_dropped_tail", ("solver",),
                 ("assert_conform(e_15,e_14)",)),
    BugSignature("acc_depends_kv", ("analysis",), ("assert_stable(",)),
)


# -- reference execution (interpret mode vs the masked dense oracle) --------

def reference_check(cfg: RaggedPrefillConfig,
                    prob: RaggedPrefillProblem) -> bool:
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.ragged_prefill import (ragged_prefill_attend,
                                              ragged_prefill_ref)
    from repro.kernels.ragged_prefill.packing import (cu_seqlens,
                                                      ragged_metadata)
    rng = np.random.default_rng(0)
    HK, D = max(prob.kv_heads, 1), min(prob.head_dim, 64)
    H = HK * min(prob.group, 4)
    bq, bkv = min(cfg.block_q, 64), min(cfg.block_kv, 64)
    scfg = RaggedPrefillConfig(block_q=bq, block_kv=bkv)
    T = 4 * max(bq, bkv)
    S = 3
    # ragged lengths with a deliberately partial tail: ~25% padding
    lens = [T // 4, 0, T // 2]
    cu = cu_seqlens(lens)
    seg, pos = ragged_metadata(cu, T)
    q = jnp.asarray(rng.normal(size=(H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(HK, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(HK, T, D)), jnp.float32)
    o = ragged_prefill_attend(q, k, v, seg, pos, seg, pos, cfg=scfg,
                              interpret=True)
    w = ragged_prefill_ref(q, k, v, seg, pos, seg, pos)
    return bool(np.allclose(np.asarray(o), np.asarray(w),
                            rtol=2e-3, atol=2e-3))


def _lower():
    from repro.kernels import ragged_prefill
    return ragged_prefill


def _example():
    # a chunked-prefill serving tick: 8 pending prompts packed into a
    # 2k buffer, GQA 8:1 (the reduced serving arch's head geometry)
    return (RaggedPrefillConfig(block_q=128, block_kv=128),
            RaggedPrefillProblem(8, 2048, 8, 1, 128, "bf16"))


def _sweep():
    # pow2 bucket grid: the serving point plus a many-short-sequences
    # and a few-long-sequences point
    return [RaggedPrefillProblem(8, 2048, 8, 1, 128, "bf16"),
            RaggedPrefillProblem(32, 8192, 8, 1, 128, "bf16"),
            RaggedPrefillProblem(4, 512, 8, 1, 128, "bf16")]


FAMILY = register(KernelFamily(
    name="ragged_prefill",
    config_cls=RaggedPrefillConfig,
    problem_cls=RaggedPrefillProblem,
    build_program=build_ragged_prefill_program,
    structural=structural_ragged_prefill,
    cost=ragged_prefill_cost,
    skills=SKILLS,
    injectable_bugs=INJECTABLE_BUGS,
    bug_signatures=BUG_SIGNATURES,
    compatible_bugs=compatible_bugs,
    reference_check=reference_check,
    lower=_lower,
    example=_example,
    sweep_problems=_sweep,
    sol_bound=ragged_prefill_sol,
))


def verify_ragged_prefill(cfg: RaggedPrefillConfig,
                          prob: RaggedPrefillProblem,
                          *, inject_bug: Optional[str] = None):
    return FAMILY.verify(cfg, prob, inject_bug=inject_bug)
