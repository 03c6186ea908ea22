"""Work that a LAMC fit requires, as a lower bound on any correct program.

``fit_roofline`` divides the least time this work could take on the chip
(``peaks.least_seconds``) by the device time a fit took, so the count has
to be a floor: a program that does less than this is not computing the
same thing, and one that reads above 100% means the count is too high.

The count for one fit, per device, with the randomized subspace iteration
of the SCC atom (``svd_method="randomized"``):

    r        = max(k, d).bit_length() + 1   # sketch width of the SVD
    passes   = svd_iters + 3                # over the block's entries
    flops    = E * (2 + 4 * r * svd_iters + 4 * r)
    bytes    = passes * E * B
    per fit  = t_p * blocks_per_device * (flops, bytes)

``E`` is the number of stored entries of one block and ``B`` the bytes of
one stored entry: ``phi * psi`` entries of 4 bytes (float32) for a dense
block, ``nnz`` entries of 8 bytes (a float32 value and an int32 index)
for a sparse one.

Assumptions, each of which keeps the count at or under what any correct
program does:

- One pass over the block reads its entries once: the row and column
  degree sums of the bipartite normalization (1 pass, 2 operations per
  entry), then ``svd_iters`` normal-equation steps ``A^T (A X)``, each
  counted as one pass because a fused kernel does it in one (as the tiled
  route's ``spmm_ata`` does), then ``A X`` (1 pass) and the projection
  ``A^T Q`` (1 pass), each of ``2 r`` operations per entry.
- The normalization's scaling is folded into the products; the block
  extraction's gathers, the anchor slivers, the k-means, the merge and
  the tall-skinny ``(M, r)`` operands are left out (all small, or not
  required of a program that works on the matrix in place).
- The count depends only on sizes and on the algorithm's own parameters
  (k, d, ``svd_iters``, ``t_p``); never on ``assign_impl``, ``qr_method``
  or ``spmm_impl``, which choose how the same work is done.
"""

from __future__ import annotations


def sketch_rank(k: int, d: int) -> int:
    """Sketch width of ``spectral.scc``'s SVD: ``l + 1`` singular vectors."""
    return max(k, d).bit_length() + 1


def atom(entries: float, entry_bytes: int, *, k: int, d: int,
         svd_iters: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one SCC atom over a block of ``entries``."""
    r = sketch_rank(k, d)
    flops = entries * (2 + 4 * r * svd_iters + 4 * r)
    return flops, (svd_iters + 3) * entries * entry_bytes


def lamc_fit(*, phi: int, psi: int, k: int, d: int, svd_iters: int,
             t_p: int = 1, blocks_per_device: int = 1,
             nnz: int | None = None) -> tuple[float, float]:
    """``(flops, bytes)`` per device of one LAMC fit.

    ``nnz`` is given for a sparse block (then ``phi * psi`` is unused);
    ``None`` means a dense float32 block.
    """
    entries, entry_bytes = ((phi * psi, 4) if nnz is None else (nnz, 8))
    flops, nbytes = atom(entries, entry_bytes, k=k, d=d, svd_iters=svd_iters)
    n = t_p * blocks_per_device
    return n * flops, n * nbytes
