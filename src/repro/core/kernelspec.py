"""Bridge between Pallas kernel configurations and ARGUS tile programs.

Each Pallas kernel family in :mod:`repro.kernels` exposes a *config*
(block shapes, grid order, staging policy — the knobs the agentic harness
mutates) and a *problem* (operand shapes/dtypes).  This module turns
(config, problem) into:

* a :class:`repro.core.dsl.TileProgram` carrying the family's data-flow
  invariants (built by :mod:`repro.core.invariants`), validated by
  :func:`repro.core.analysis.check`;
* *structural* TPU checks — the MI300X-specific entries of the paper's
  Table 1 map to TPU-native constraints (DESIGN.md §2):
    - lane/sublane alignment of every block (the TPU analogue of shared-
      memory bank-conflict mitigation),
    - VMEM working-set fit including the pipeline's double buffering
      (the analogue of register/LDS budget),
    - out-of-bounds masking obligations for non-divisible dims (the
      analogue of buffer_load OOB guards).

``verify()`` is the single entry point: zero runtime overhead, pure
compile-time reasoning, concrete counterexamples on failure.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .analysis import CheckReport, check
from .solver import Counterexample, ProofResult, Status

# --- TPU model constants (v5e; see DESIGN.md §7) ---------------------------
LANE = 128                    # last-dim tiling quantum
SUBLANE = {"f32": 8, "bf16": 16, "i8": 32, "fp8": 32, "i32": 8}
VMEM_BYTES = 16 * 2 ** 20     # per-core VMEM budget (model constant)
DTYPE_BYTES = {"f32": 4, "bf16": 2, "i8": 1, "fp8": 1, "i32": 4}
MXU = 128                     # systolic array edge


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclass
class StructuralIssue:
    kind: str
    message: str
    hard: bool = False      # the chip's compiler refuses it: rejects


def check_block_shapes(blocks: Dict[str, Tuple[Sequence[int],
                                               Sequence[int]]]
                       ) -> List[StructuralIssue]:
    """Mosaic's BlockSpec rule, as the Pallas TPU lowering enforces it:
    the last two dims of every block are divisible by (8, 128) or equal
    to the array's.  ``blocks`` maps operand -> (block shape, array
    shape).  The compiler refuses anything else, so each issue is hard."""
    issues: List[StructuralIssue] = []
    for name, (block, array) in blocks.items():
        bs, arr = (1, 1, *block)[-2:], (1, 1, *array)[-2:]
        if not all(b == a or b % q == 0
                   for b, a, q in zip(bs, arr, (8, LANE))):
            issues.append(StructuralIssue(
                "block_shape",
                f"{name}: block {tuple(block)} of array {tuple(array)} — "
                f"the trailing pair must be divisible by (8, {LANE}) or "
                f"equal the array's", hard=True))
    return issues


def check_alignment(name: str, block_shape: Sequence[int], dtype: str,
                    *, full_shape: Optional[Sequence[int]] = None
                    ) -> List[StructuralIssue]:
    """TPU lane/sublane alignment: last dim % 128, second-to-last %
    sublane(dtype) — misalignment forces relayout copies (the TPU analogue
    of a bank conflict).  Blocks covering the entire (smaller) dim pass."""
    issues: List[StructuralIssue] = []
    bs = tuple(block_shape)
    sub = SUBLANE.get(dtype, 8)
    if len(bs) >= 1:
        last = bs[-1]
        covers = full_shape is not None and last == tuple(full_shape)[-1]
        if last % LANE != 0 and not (covers and last < LANE):
            issues.append(StructuralIssue(
                "alignment",
                f"{name}: last block dim {last} not a multiple of {LANE} "
                f"(lane misalignment => relayout copy)"))
    if len(bs) >= 2:
        sl = bs[-2]
        covers = full_shape is not None and sl == tuple(full_shape)[-2]
        if sl % sub != 0 and not (covers and sl < sub):
            issues.append(StructuralIssue(
                "alignment",
                f"{name}: sublane dim {sl} not a multiple of {sub} "
                f"for dtype {dtype}"))
    return issues


def check_vmem(blocks: Dict[str, Tuple[Sequence[int], str]],
               *, pipeline_buffers: int = 2,
               scratch: Dict[str, Tuple[Sequence[int], str]] = None
               ) -> List[StructuralIssue]:
    """Working-set fit: pipelined operand blocks are double-buffered by the
    Pallas pipeline; scratch is single-buffered."""
    issues: List[StructuralIssue] = []
    total = 0
    for name, (shape, dtype) in blocks.items():
        total += math.prod(shape) * DTYPE_BYTES.get(dtype, 2) * \
            pipeline_buffers
    for name, (shape, dtype) in (scratch or {}).items():
        total += math.prod(shape) * DTYPE_BYTES.get(dtype, 2)
    if total > VMEM_BYTES:
        issues.append(StructuralIssue(
            "vmem",
            f"working set {total / 2**20:.2f} MiB exceeds VMEM budget "
            f"{VMEM_BYTES / 2**20:.0f} MiB "
            f"(pipeline_buffers={pipeline_buffers})"))
    return issues


def check_masking(name: str, dim_sizes: Sequence[int],
                  block_shape: Sequence[int],
                  masked_dims: Sequence[int]) -> List[StructuralIssue]:
    """Non-divisible dims must be declared masked (OOB-guard obligation)."""
    issues: List[StructuralIssue] = []
    for d, (n, b) in enumerate(zip(dim_sizes, block_shape)):
        if n % b != 0 and d not in masked_dims:
            issues.append(StructuralIssue(
                "masking",
                f"{name}: dim {d} ({n}) not divisible by block {b} and not "
                f"declared masked — OOB elements reach compute"))
    return issues


@dataclass
class VerifyResult:
    """Combined invariant + structural verdict for one kernel config."""

    report: Optional[CheckReport]
    structural: List[StructuralIssue] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.report is None or self.report.ok) and not self.structural

    @property
    def hard_ok(self) -> bool:
        """Data-flow invariants plus the structural issues the compiler
        would refuse (the others are perf warnings in some contexts,
        e.g. alignment on edge blocks)."""
        return ((self.report is None or self.report.ok)
                and not any(s.hard for s in self.structural))

    def render(self) -> str:
        lines = []
        if self.report is not None:
            lines.append(self.report.render())
        for s in self.structural:
            lines.append(f"  STRUCT[{s.kind}] {s.message}")
        if self.ok:
            lines.append("  VERDICT: ok")
        else:
            lines.append("  VERDICT: REJECTED")
        return "\n".join(lines)


def verify_program(prog, structural: List[StructuralIssue]) -> VerifyResult:
    return VerifyResult(check(prog), structural)
