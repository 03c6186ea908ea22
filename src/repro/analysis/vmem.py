"""A4 — static VMEM estimator for Pallas kernel BlockSpecs (DESIGN.md §13).

A TPU core has ~16 MiB of VMEM feeding the MXU/VPU; a ``pallas_call``
whose resident working set — one block per input/output BlockSpec plus
every scratch buffer — exceeds it fails at compile time on hardware (and
silently *passes* under ``interpret=True``, which is exactly how an
oversized tile config survives CPU CI). This module prices a kernel's
working set from its BlockSpecs alone, so the check runs anywhere.

The estimator is the single source of truth for runtime fallback
decisions too: ``kernels.ops.spmm_ata`` asks :func:`ata_resident_bytes`
whether the fused normal-equations kernel's Y-stripe + output-stripe fit
the budget before choosing one launch over two (previously an ad-hoc
inline byte count with its own private budget constant).

``KERNEL_SPECS`` declares every kernel's blocks for representative tile
configs; the jaxpr-audit lane walks it and fails CI when a kernel's
default tiling stops fitting. The per-platform budget deliberately uses
a safety fraction: XLA needs VMEM headroom for semaphores, DMA staging
and double buffering, so committing all 16 MiB to declared blocks is
already an overflow in practice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np

from .findings import Finding

__all__ = ["BlockUse", "KernelEstimate", "estimate_kernel",
           "vmem_budget_bytes", "ata_resident_bytes", "KERNEL_SPECS",
           "audit_vmem", "VMEM_BYTES_PER_CORE", "VMEM_SAFETY_FRACTION"]

#: physical VMEM per TPU core (v4/v5 class); see /opt guide "~16 MB/core".
VMEM_BYTES_PER_CORE = 16 * 2**20
#: fraction of physical VMEM the declared working set may claim — the rest
#: is headroom for double buffering and DMA staging.
VMEM_SAFETY_FRACTION = 0.75

# (sublane, lane) tiling granule for f32 — blocks not aligned to it are
# padded up by Mosaic, so the estimator prices the padded footprint.
_SUBLANE = 8
_LANE = 128


def vmem_budget_bytes(platform: str = "tpu") -> int:
    """Usable VMEM budget for one kernel's declared working set."""
    if platform != "tpu":  # interpret/jnp tiers have no VMEM ceiling
        return 2**62
    return int(VMEM_BYTES_PER_CORE * VMEM_SAFETY_FRACTION)


@dataclasses.dataclass(frozen=True)
class BlockUse:
    """One BlockSpec (or scratch shape) of a kernel invocation."""

    name: str                       # operand label, for the report
    block_shape: tuple[int, ...]    # per-grid-step resident block
    dtype: str = "float32"
    array_shape: tuple[int, ...] | None = None  # full (padded) operand

    def padded_block(self) -> tuple[int, ...]:
        """Block shape padded to the (8, 128) f32 tiling granule."""
        shape = tuple(int(s) for s in self.block_shape)
        if len(shape) == 0:
            return shape
        out = list(shape)
        out[-1] = max(1, math.ceil(out[-1] / _LANE)) * _LANE
        if len(out) >= 2:
            out[-2] = max(1, math.ceil(out[-2] / _SUBLANE)) * _SUBLANE
        return tuple(out)

    def nbytes(self) -> int:
        return int(np.prod(self.padded_block(), dtype=np.int64)
                   * np.dtype(self.dtype).itemsize)

    def divisibility_issues(self) -> list[str]:
        """Block dims must tile the (padded) array dims exactly — a ragged
        final block reads out of bounds on the DMA path."""
        if self.array_shape is None:
            return []
        issues = []
        for axis, (b, a) in enumerate(zip(self.block_shape,
                                          self.array_shape)):
            if b <= 0:
                issues.append(f"{self.name}: axis {axis} block dim {b} <= 0")
            elif a % b != 0:
                issues.append(
                    f"{self.name}: array dim {a} not divisible by block "
                    f"dim {b} on axis {axis}")
        return issues


@dataclasses.dataclass(frozen=True)
class KernelEstimate:
    name: str
    blocks: tuple[BlockUse, ...]
    total_bytes: int
    budget_bytes: int
    issues: tuple[str, ...]

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.budget_bytes and not self.issues

    def describe(self) -> str:
        parts = [f"{b.name}={b.block_shape}:{b.nbytes()}B"
                 for b in self.blocks]
        return (f"{self.name}: total {self.total_bytes}B of "
                f"{self.budget_bytes}B budget ({', '.join(parts)})")


def estimate_kernel(name: str, blocks: list[BlockUse],
                    platform: str = "tpu") -> KernelEstimate:
    total = sum(b.nbytes() for b in blocks)
    issues: list[str] = []
    for b in blocks:
        issues.extend(b.divisibility_issues())
    return KernelEstimate(name=name, blocks=tuple(blocks),
                          total_bytes=total,
                          budget_bytes=vmem_budget_bytes(platform),
                          issues=tuple(issues))


def ata_resident_bytes(n_tile_rows: int, n_tile_cols: int, bm: int, bk: int,
                       bn: int, itemsize: int = 4, *,
                       with_gram: bool = False,
                       scaled: bool = False) -> int:
    """Resident bytes of the fused ``A.T @ (A @ x)`` kernel per column
    stripe: the whole-height VMEM Y scratch ``(n_tr * bm, bn)`` plus the
    whole-height output stripe ``(n_tc * bk, bn)`` (both live across the
    full payload sweep — see ``kernels.spmm.spmm_ata_pallas``). The
    payload/x blocks stream through and are amortized against double-
    buffering headroom, not this figure.

    ``with_gram`` adds the ``(bn, bn)`` Gram output of the fused
    subspace-iteration step; ``scaled`` adds the per-payload row/col
    scale slivers (``(1, 1, bm)`` + ``(1, 1, bk)`` blocks, priced at
    their padded sublane granule)."""
    total = (n_tile_rows * bm + n_tile_cols * bk) * bn * itemsize
    if with_gram:
        total += bn * bn * itemsize
    if scaled:
        total += (_SUBLANE * max(bm, _LANE) + _SUBLANE * max(bk, _LANE)) \
            * itemsize
    return total


def _scale_blocks(bm: int, bk: int) -> list[BlockUse]:
    return [
        BlockUse("row_scale", (1, 1, bm)),
        BlockUse("col_scale", (1, 1, bk)),
    ]


def _spmm_tiled_blocks(g: int, bm: int, bk: int, bn: int, n_pad: int,
                       m_out: int, scaled: bool = False) -> list[BlockUse]:
    blocks = [
        BlockUse("payload", (1, bm, bk), array_shape=(g, bm, bk)),
        BlockUse("rhs", (bk, bn), array_shape=(bk * 4, n_pad)),
        BlockUse("out", (bm, bn), array_shape=(m_out, n_pad)),
    ]
    if scaled:
        blocks += _scale_blocks(bm, bk)
    return blocks


def _spmm_ata_blocks(n_tr: int, n_tc: int, bm: int, bk: int, bn: int,
                     scaled: bool = False,
                     with_gram: bool = False) -> list[BlockUse]:
    blocks = [
        BlockUse("payload", (1, bm, bk)),
        BlockUse("x", (bk, bn)),
        BlockUse("out_stripe", (n_tc * bk, bn)),
        BlockUse("y_scratch", (n_tr * bm, bn)),
    ]
    if scaled:
        blocks += _scale_blocks(bm, bk)
    if with_gram:
        blocks.append(BlockUse("gram", (bn, bn)))
    return blocks


#: kernel name -> () -> KernelEstimate at its shipped default tile config.
#: These are the shapes the ops wrappers actually launch; the audit fails
#: when an edit makes any default config stop fitting VMEM.
KERNEL_SPECS: dict[str, Callable[[], KernelEstimate]] = {
    # ops.kmeans_assign: tile_p=512 points, d<=1024 feature cols, k<=512
    "kmeans_assign": lambda: estimate_kernel("kmeans_assign", [
        BlockUse("x", (512, 1024), array_shape=(4096, 1024)),
        BlockUse("centroids", (512, 1024), array_shape=(512, 1024)),
        BlockUse("labels", (1, 512), dtype="int32", array_shape=(1, 4096)),
        BlockUse("d2", (1, 512), array_shape=(1, 4096)),
    ]),
    # ops.kmeans_update adds the (K, D) sums and (1, K) counts accumulators
    "kmeans_update": lambda: estimate_kernel("kmeans_update", [
        BlockUse("x", (512, 1024), array_shape=(4096, 1024)),
        BlockUse("centroids", (512, 1024), array_shape=(512, 1024)),
        BlockUse("weights", (1, 512), array_shape=(1, 4096)),
        BlockUse("labels", (1, 512), dtype="int32", array_shape=(1, 4096)),
        BlockUse("d2", (1, 512), array_shape=(1, 4096)),
        BlockUse("sums", (512, 1024), array_shape=(512, 1024)),
        BlockUse("counts", (1, 512), array_shape=(1, 512)),
    ]),
    # ops.cosine_assign: serving scorer, q<=1024 anchor dims, K<=1024 sigs
    "cosine_assign": lambda: estimate_kernel("cosine_assign", [
        BlockUse("x", (512, 1024), array_shape=(4096, 1024)),
        BlockUse("signatures", (1024, 1024), array_shape=(1024, 1024)),
        BlockUse("labels", (1, 512), dtype="int32", array_shape=(1, 4096)),
        BlockUse("score", (1, 512), array_shape=(1, 4096)),
    ]),
    "cosine_topk": lambda: estimate_kernel("cosine_topk", [
        BlockUse("x", (512, 1024), array_shape=(4096, 1024)),
        BlockUse("signatures", (1024, 1024), array_shape=(1024, 1024)),
        BlockUse("labels", (512, 8), dtype="int32", array_shape=(4096, 8)),
        BlockUse("scores", (512, 8), array_shape=(4096, 8)),
    ]),
    # kernels.bipartite_normalize at its default 256x256 tiles
    "scale_apply": lambda: estimate_kernel("scale_apply", [
        BlockUse("a", (256, 256), array_shape=(4096, 4096)),
        BlockUse("d1", (1, 256), array_shape=(1, 4096)),
        BlockUse("d2", (1, 256), array_shape=(1, 4096)),
        BlockUse("out", (256, 256), array_shape=(4096, 4096)),
    ]),
    # flash attention: tile_q=512, tile_k=512, head dim 128 + m/l/acc scratch
    "flash_attention": lambda: estimate_kernel("flash_attention", [
        BlockUse("q", (1, 512, 128), array_shape=(8, 4096, 128)),
        BlockUse("k", (1, 512, 128), array_shape=(8, 4096, 128)),
        BlockUse("v", (1, 512, 128), array_shape=(8, 4096, 128)),
        BlockUse("out", (1, 512, 128), array_shape=(8, 4096, 128)),
        BlockUse("acc_scratch", (512, 128)),
        BlockUse("m_scratch", (512, _LANE)),
        BlockUse("l_scratch", (512, _LANE)),
    ]),
    # tiled SpMM family at the shipped bm=bk=bn=128 tiles
    "spmm_tiled": lambda: estimate_kernel(
        "spmm_tiled", _spmm_tiled_blocks(g=64, bm=128, bk=128, bn=128,
                                         n_pad=512, m_out=1024)),
    # fused normal equations at the largest tile grid the runtime fallback
    # admits under the shared budget (see ops.spmm_ata)
    "spmm_ata": lambda: estimate_kernel(
        "spmm_ata", _spmm_ata_blocks(n_tr=16, n_tc=16, bm=128, bk=128,
                                     bn=128)),
    # scale-fused variants (normalize_bipartite applied in VMEM): the two
    # per-payload scale slivers ride along with every payload block
    "spmm_tiled_scaled": lambda: estimate_kernel(
        "spmm_tiled_scaled", _spmm_tiled_blocks(g=64, bm=128, bk=128,
                                                bn=128, n_pad=512,
                                                m_out=1024, scaled=True)),
    # fused subspace-iteration step: scaled SpMM -> Gram of the resident
    # output stripe, all in one launch (see ops.spmm_ata with_gram=True)
    "spmm_ata_fused_step": lambda: estimate_kernel(
        "spmm_ata_fused_step", _spmm_ata_blocks(n_tr=16, n_tc=16, bm=128,
                                                bk=128, bn=128, scaled=True,
                                                with_gram=True)),
}


def audit_vmem(platform: str = "tpu") -> list[Finding]:
    """A4 pass: every registered kernel's default config must fit."""
    findings = []
    for name, build in sorted(KERNEL_SPECS.items()):
        est = build()
        if est.total_bytes > est.budget_bytes:
            findings.append(Finding(
                rule="A4", path=f"kernel:{name}", line=0,
                message=f"VMEM working set {est.total_bytes} B exceeds "
                        f"budget {est.budget_bytes} B",
                evidence=est.describe()))
        for issue in est.issues:
            findings.append(Finding(
                rule="A4", path=f"kernel:{name}", line=0,
                message=f"block/array divisibility violation: {issue}",
                evidence=est.describe()))
    return findings
