"""LAMC driver — partition -> parallel atom co-clustering -> hierarchical merge.

Single-host reference implementation of the full Algorithm 1 pipeline. The
multi-device version (``core.distributed``) reuses the same pieces under
``shard_map``; this module is its oracle in tests.

Per resample ``t``:
  1. ``partition.extract_blocks`` gathers the (m*n, phi, psi) block stack
     (a whole-matrix plan's is ``A`` itself, ``partition.whole_matrix``).
  2. The atom co-clusterer (SCC or NMTF) runs *vmapped* over the stack —
     on real hardware this is the embarrassingly parallel phase.
  3. Atom signatures are computed in the shared projection space.
Afterwards, ``merging.signature_merge`` produces consensus labels.

Everything except the plan search is jittable; the resample loop is a
``lax.scan`` so the whole pipeline lowers to one XLA program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from . import merging, nmtf, partition, probability, spectral
from . import sparse as _sparse

__all__ = ["LAMCConfig", "LAMCResult", "lamc_cocluster", "run_resample",
           "anchor_features", "validate_assignment"]


@dataclasses.dataclass(frozen=True)
class LAMCConfig:
    n_row_clusters: int
    n_col_clusters: int
    # block k/d: clusters the atom method looks for inside one block.
    atom_row_clusters: int | None = None
    atom_col_clusters: int | None = None
    atom: str = "scc"               # "scc" | "nmtf"
    min_cocluster_rows: int = 8     # adversarial C_k for the Theorem-1 plan
    min_cocluster_cols: int = 8
    p_thresh: float = 0.95
    workers: int = 1
    seed: int = 0
    svd_iters: int = 4
    kmeans_iters: int = 16
    nmtf_iters: int = 64
    merge_kmeans_iters: int = 25
    merge_restarts: int = 4    # best-of-N seedings for the signature k-means
    signature_dim: int = 64    # number of shared anchor rows/cols for merging
    expected_failed_blocks: int = 0
    grid_candidates: tuple = (1, 2, 4, 8, 16, 32)
    assign_impl: str = "jnp"        # "jnp" | "pallas" — k-means hot path
    svd_method: str = "randomized"  # "randomized" (TPU-adapted) | "exact" (paper)
    qr_method: str = "qr"           # "qr" (LAPACK) | "cholesky" (Gram, batched)
    input_format: str = "dense"     # "dense" | "bcoo" — sparse execution path
    # SpMM backend for the sparse spectral path: "auto" routes per matrix
    # density (probability.spmm_route), or pin "dense" | "dual_ell" |
    # "tiled". Decides how a single-block (m = n = 1) plan's full-matrix
    # atom runs: a non-dense route keeps A in its sparse operator form
    # (converted once, amortized across all resamples) instead of
    # densifying the block. Multi-block plans always densify their
    # phi x psi blocks (the MXU-shaped atom work unit, DESIGN.md §9).
    spmm_impl: str = "auto"
    # Assignment mode (DESIGN.md §11). "hard" (default): every point gets
    # exactly the argmax of its vote table — bit-identical to the
    # pre-overlap pipeline. "overlap": non-exhaustive soft assignment —
    # a point joins every cluster whose vote share clears
    # overlap_threshold (membership matrices on the result); clearing
    # none marks it an outlier (label -1) unless min_membership > 0
    # guarantees its top clusters. overlap_threshold > 0.5 with
    # min_membership=1 reduces exactly to hard mode.
    assignment: str = "hard"
    overlap_threshold: float = 0.25
    min_membership: int = 0

    @property
    def atom_k(self) -> int:
        return self.atom_row_clusters or self.n_row_clusters

    @property
    def atom_d(self) -> int:
        return self.atom_col_clusters or self.n_col_clusters


class LAMCResult(NamedTuple):
    row_labels: jax.Array
    col_labels: jax.Array
    row_votes: jax.Array
    col_votes: jax.Array
    plan: partition.PartitionPlan
    # Serving artifact fields (merged cluster signatures in anchor space +
    # the anchor index sets) — what ``streaming.model_from_result`` packs
    # into a CoclusterModel. None only for results built by old callers.
    row_sigs: jax.Array | None = None     # (K_row, q_row) unit rows
    col_sigs: jax.Array | None = None     # (K_col, q_col)
    row_mean: jax.Array | None = None     # (q_row,) centering mean
    col_mean: jax.Array | None = None     # (q_col,)
    anchor_rows: jax.Array | None = None  # (q_col,) int32 global row ids
    anchor_cols: jax.Array | None = None  # (q_row,) int32 global col ids
    # Boolean membership matrices (DESIGN.md §11): one-hot of the labels
    # in hard mode; soft non-exhaustive membership in overlap mode
    # (all-False row = outlier, label -1).
    row_membership: jax.Array | None = None  # (M, K_row) bool
    col_membership: jax.Array | None = None  # (N, K_col) bool


def _atom_fn(cfg: LAMCConfig):
    if cfg.atom == "scc":
        def atom(key, block):
            res = spectral.scc(
                key, block, cfg.atom_k, cfg.atom_d,
                svd_iters=cfg.svd_iters, kmeans_iters=cfg.kmeans_iters,
                assign_impl=cfg.assign_impl, svd_method=cfg.svd_method,
                qr_method=cfg.qr_method,
            )
            return res.row_labels, res.col_labels
    elif cfg.atom == "nmtf":
        def atom(key, block):
            res = nmtf.nmtf(key, block, cfg.atom_k, cfg.atom_d, n_iter=cfg.nmtf_iters)
            return res.row_labels, res.col_labels
    else:
        raise ValueError(f"unknown atom method {cfg.atom!r}")
    return atom


def anchor_features(a, anchor_rows, anchor_cols):
    """Anchor slivers ``(A[:, anchor_cols] (M, q), A[anchor_rows] (q, N))``.

    Gather order matters on the dense path: restricting to the ``q``
    anchor columns *first* keeps the intermediate at ``(M, q)`` — indexing
    rows first would materialize an ``(m, phi, N)`` tensor, the same
    gather-order bug ``extract_blocks`` fixed for blocks. A BCOO input
    scatters its nonzeros straight into the slivers, O(nnz).
    """
    if _sparse.is_bcoo(a):
        return (_sparse.gather_cols_dense(a, anchor_cols),
                _sparse.gather_rows_dense(a, anchor_rows))
    return a[:, anchor_cols], a[anchor_rows]


def run_resample(a, plan, cfg: LAMCConfig, anchor_rows, anchor_cols, t,
                 operator=None):
    """One resample: extract blocks, co-cluster them (vmapped), summarize.

    ``anchor_rows`` / ``anchor_cols`` are the globally shared anchor index
    sets (see ``merging.anchor_indices``). Returns the per-resample tensors
    consumed by ``merging.signature_merge``. ``a`` may be dense or BCOO
    (``cfg.input_format``); the block stack and anchor slivers the atom
    phase consumes are identical either way.

    ``operator`` (whole-matrix plans only, ``partition.whole_matrix``): a
    prepared sparse operand of the whole matrix
    (``sparse.prepare_operator``). The atom then runs SCC directly on it —
    SpMM subspace iteration, O(nnz)/O(occupied tiles) per product — and
    the ``M x N`` block is never densified.
    """
    b = plan.blocks_per_resample
    with jax.named_scope("extract"):
        if operator is None:
            extract = (partition.extract_blocks_sparse
                       if cfg.input_format == "bcoo"
                       else partition.extract_blocks)
            blocks, row_idx, col_idx = extract(a, plan, t)
        else:
            assert partition.whole_matrix(plan), \
                "operator path requires a whole-matrix plan"
            row_idx, col_idx = partition.resample_indices(plan, t)
    with jax.named_scope("atom/svd"):
        keys = jax.vmap(
            lambda i: jax.random.fold_in(jax.random.fold_in(jax.random.key(plan.seed + 1), t), i)
        )(jnp.arange(b))
    if operator is None:
        row_labels, col_labels = jax.vmap(_atom_fn(cfg))(keys, blocks)  # (B,phi),(B,psi)
    else:
        row_labels, col_labels = (
            lab[None] for lab in _atom_fn(cfg)(keys[0], operator))  # (1,phi),(1,psi)

    with jax.named_scope("signatures"):
        # anchor features: every block's points restricted to the shared anchors
        j_of_b = jnp.arange(b) % plan.n
        i_of_b = jnp.arange(b) // plan.n
        row_sliver, col_sliver = anchor_features(a, anchor_rows, anchor_cols)
        row_feats = row_sliver[row_idx]                    # (m, phi, q)
        col_feats = col_sliver[:, col_idx]                 # (q, n, psi)
        col_feats = jnp.transpose(col_feats, (1, 2, 0))    # (n, psi, q)
        row_sigs, row_counts = merging.atom_signatures(
            row_feats[i_of_b], row_labels, cfg.atom_k)
        col_sigs, col_counts = merging.atom_signatures(
            col_feats[j_of_b], col_labels, cfg.atom_d)
    return dict(
        row_sigs=row_sigs, row_counts=row_counts, row_labels=row_labels,
        row_index=row_idx,
        col_sigs=col_sigs, col_counts=col_counts, col_labels=col_labels,
        col_index=col_idx,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "plan"))
def _lamc_jit(a, cfg: LAMCConfig, plan: partition.PartitionPlan,
              operator=None, block_mask=None):
    q = cfg.signature_dim
    # Device phases as named scopes (DESIGN.md §14): every instruction of
    # the program carries extract, atom/{normalize,svd,kmeans}, signatures
    # or merge in its op_name; only the T_p scan's loop control has none.
    with jax.named_scope("signatures"):
        kproj = jax.random.key(plan.seed + 7)
        kar, kac, kmerge = jax.random.split(kproj, 3)
        anchor_rows = merging.anchor_indices(kar, plan.n_rows, q)
        anchor_cols = merging.anchor_indices(kac, plan.n_cols, q)

    def body(_, t):
        out = run_resample(a, plan, cfg, anchor_rows, anchor_cols, t,
                           operator=operator)
        return None, out

    _, stacked = jax.lax.scan(body, None, jnp.arange(plan.t_p))
    with jax.named_scope("merge"):
        # serving signatures are cluster means over the same anchor slivers
        # the merge consumes — computed from the final consensus labels
        row_sliver, col_sliver = anchor_features(a, anchor_rows, anchor_cols)
        merged = merging.signature_merge(
            kmerge,
            n_rows=plan.n_rows, n_cols=plan.n_cols,
            k_row=cfg.n_row_clusters, k_col=cfg.n_col_clusters,
            m=plan.m, n=plan.n,
            kmeans_iters=cfg.merge_kmeans_iters,
            n_restarts=cfg.merge_restarts,
            row_features=row_sliver, col_features=col_sliver.T,
            assignment=cfg.assignment,
            overlap_threshold=cfg.overlap_threshold,
            min_membership=cfg.min_membership,
            block_mask=block_mask,
            **stacked,
        )
    return merged, anchor_rows, anchor_cols


def validate_assignment(cfg: LAMCConfig) -> None:
    """Fail loudly on bad assignment knobs before any jit trace."""
    if cfg.assignment not in ("hard", "overlap"):
        raise ValueError(
            f"assignment must be 'hard' or 'overlap', got {cfg.assignment!r}")
    if not 0.0 < cfg.overlap_threshold <= 1.0:
        raise ValueError(
            f"overlap_threshold must be in (0, 1], got {cfg.overlap_threshold}")
    if not 0 <= cfg.min_membership <= min(cfg.n_row_clusters,
                                          cfg.n_col_clusters):
        raise ValueError(
            f"min_membership must be in [0, n_clusters], got "
            f"{cfg.min_membership}")


def lamc_cocluster(a, cfg: LAMCConfig,
                   plan: partition.PartitionPlan | None = None,
                   block_mask=None) -> LAMCResult:
    """Full LAMC pipeline (Algorithm 1). ``plan=None`` derives the optimal
    plan from the probabilistic model.

    ``cfg.input_format='bcoo'`` runs the sparse execution path: ``a`` must
    be a 2-D BCOO matrix, which is never densified — blocks and anchor
    slivers are scattered out of the nonzeros, and the auto-plan is priced
    against the matrix's actual density. ``cfg.spmm_impl`` picks the SpMM
    backend for the spectral step (``"auto"`` routes on density; the
    decision is surfaced on ``result.plan.spmm_route``); on a
    single-block plan a non-dense route runs the atom straight on the
    sparse operator — converted once, amortized across all resamples.

    ``block_mask`` (``(T_p, blocks_per_resample)`` bool, True = survived)
    drops the masked blocks' atoms from the consensus merge — the
    simulation seam for worker failure (DESIGN.md §12). See
    ``probability.sample_block_failures`` and the T_p fault-budget
    differential test.
    """
    _sparse.validate_spmm_impl(cfg.spmm_impl)
    validate_assignment(cfg)
    if cfg.input_format == "bcoo":
        _sparse.validate_bcoo(a)
        density = _sparse.density(a)
    elif _sparse.is_bcoo(a):
        raise ValueError(
            "got a BCOO matrix with input_format='dense'; set "
            "LAMCConfig(input_format='bcoo') for the sparse path")
    else:
        density = 1.0
    n_rows, n_cols = a.shape
    with obs.span("lamc", rows=int(n_rows), cols=int(n_cols),
                  input_format=cfg.input_format, atom=cfg.atom) as root:
        if plan is None:
            with obs.span("plan"):
                plan = partition.make_plan(
                    n_rows, n_cols,
                    min_cocluster_rows=cfg.min_cocluster_rows,
                    min_cocluster_cols=cfg.min_cocluster_cols,
                    p_thresh=cfg.p_thresh,
                    workers=cfg.workers,
                    seed=cfg.seed,
                    k=cfg.atom_k,
                    expected_failed_blocks=cfg.expected_failed_blocks,
                    grid_candidates=cfg.grid_candidates,
                    svd_method=cfg.svd_method,
                    density=density,
                    spmm_impl=cfg.spmm_impl,
                )
        operator = None
        if cfg.input_format == "bcoo":
            # Only a single-block SCC plan covering the whole matrix can run
            # on the sparse operator (a subsampling (1,1) plan — phi < M or
            # psi < N — still needs the per-resample extraction); every other
            # plan densifies its blocks, so its route is "dense" whatever the
            # knob says. The shared resolver keeps this decision identical to
            # the plan search's pricing/surfacing — what runs is what was
            # priced.
            single = partition.whole_matrix(plan) and cfg.atom == "scc"
            route = probability.resolve_spmm_route(
                cfg.spmm_impl, density, float(plan.phi) * plan.psi,
                single=single, svd_method=cfg.svd_method)
            if plan.spmm_route != route:
                plan = dataclasses.replace(plan, spmm_route=route)
            if single and route != "dense":
                # single-block plan: the block IS the matrix — keep it sparse.
                # One conversion (device-resident on TPU), reused by every
                # resample's ~10 subspace-iteration products, and served
                # from the pattern cache (core.opcache) when the fit loop
                # re-prepares a matrix whose sparsity pattern it has seen —
                # a repeat fit/resample pays a values refresh at most.
                with obs.span("prepare_operator", route=route) as ops:
                    operator = ops.fence(_sparse.prepare_operator(a, route))
        # Resolved-plan attributes on the root span: what actually ran.
        root.set(m=plan.m, n=plan.n, phi=plan.phi, psi=plan.psi,
                 t_p=plan.t_p, spmm_route=plan.spmm_route,
                 extract=partition.extraction(
                     plan, cfg.input_format == "bcoo"),
                 density=round(float(density), 6))
        if block_mask is not None:
            block_mask = jnp.asarray(block_mask, dtype=bool)
            want = (plan.t_p, plan.blocks_per_resample)
            if tuple(block_mask.shape) != want:
                raise ValueError(
                    f"block_mask must be (t_p, blocks_per_resample) = {want}, "
                    f"got {tuple(block_mask.shape)}")
        # One XLA program runs extract -> atom -> signatures -> merge; its
        # phases are named scopes on the device (DESIGN.md §14). On the
        # host: the call up to its return, then the wait for its outputs.
        with obs.span("dispatch"):
            out = _lamc_jit(a, cfg, plan, operator, block_mask)
        with obs.span("wait") as ws:
            ws.fence(out)
        with obs.span("finalize"):
            merged, anchor_rows, anchor_cols = out
            return LAMCResult(
                merged.row_labels, merged.col_labels,
                merged.row_votes, merged.col_votes, plan,
                row_sigs=merged.row_sigs, col_sigs=merged.col_sigs,
                row_mean=merged.row_mean, col_mean=merged.col_mean,
                anchor_rows=anchor_rows, anchor_cols=anchor_cols,
                row_membership=merged.row_membership,
                col_membership=merged.col_membership)
