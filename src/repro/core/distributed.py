"""Distributed LAMC — the paper's parallel structure mapped onto a TPU mesh.

Phase map (DESIGN.md §2):

  1. **Block scatter** (jit + GSPMD): ``extract_blocks`` gathers the
     permuted row/col groups out of the mesh-sharded data matrix. XLA emits
     the all-to-all; this is the only phase that moves matrix data, and it
     moves each element exactly once per resample. A whole-matrix plan
     gathers nothing (``partition.whole_matrix``).

  2. **Per-block co-clustering** (shard_map): every device owns
     ``m*n / n_devices`` blocks and runs the atom co-clusterer *locally* —
     small per-device SVD/QR/k-means, never a partitioned factorization.
     This is the paper's "parallel co-clustering of submatrices": identical
     static shapes, zero communication.

  3. **Hierarchical merge** (shard_map collectives): devices exchange only
     atom *signatures* (``k x q`` floats each) via ``all_gather`` — a
     log-depth tree on ICI — cluster them identically everywhere (tiny
     replicated k-means), then ``psum`` the per-point vote tables.
     Total bytes on the wire per resample: ``B*(k+d)*q*4`` + the two vote
     tables — independent of the data matrix size. This is the paper's
     communication-overhead fix realized as collectives.

The pipeline is one jitted program; resamples run under ``lax.scan``.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .. import obs
from . import merging, partition
from . import sparse as _sparse
from .lamc import LAMCConfig, LAMCResult, _atom_fn, anchor_features, validate_assignment


def _validate_input_format(a, cfg: LAMCConfig) -> None:
    """Same format/knob guards as ``lamc_cocluster`` — fail loudly before jit.

    ``cfg.spmm_impl`` is validated here too; the distributed driver always
    densifies its (device-local, MXU-shaped) blocks, so the knob's
    single-block sparse-operator route is the single-host driver's — a
    multi-device mesh implies a multi-block plan.
    """
    _sparse.validate_spmm_impl(cfg.spmm_impl)
    validate_assignment(cfg)
    if cfg.input_format == "bcoo":
        _sparse.validate_bcoo(a)
    elif _sparse.is_bcoo(a):
        raise ValueError(
            "got a BCOO matrix with input_format='dense'; set "
            "LAMCConfig(input_format='bcoo') for the sparse path")

__all__ = ["distributed_lamc", "lamc_step_fn", "lamc_input_specs"]


def _merge_votes_local(point_global, index_of_points, n_points, k_global):
    """Scatter votes for this device's blocks into a global vote table."""
    votes = jnp.zeros((n_points, k_global), jnp.float32)
    return votes.at[index_of_points.reshape(-1), point_global.reshape(-1)].add(1.0)


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis in Auto mode.

    ``jax.make_mesh`` builds Explicit axes by default; the block scatter
    relies on GSPMD to place the gathered stack, and
    ``with_sharding_constraint`` only accepts Auto axes.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def lamc_step_fn(cfg: LAMCConfig, plan: partition.PartitionPlan,
                 mesh: Mesh, block_axes: Sequence[str],
                 resample_axis: str | None = None):
    """Build the jitted distributed-LAMC step for ``mesh``.

    ``block_axes``: mesh axis names the block dimension is sharded over
    (e.g. ``("data", "model")``). ``resample_axis``: optional extra mesh
    axis (the cross-pod one) that parallelizes the ``T_p`` resamples —
    the paper's resamples are embarrassingly parallel, so on a multi-pod
    mesh each pod runs its own subset of resamples instead of duplicating
    them (without this, every pod recomputes identical blocks and the
    signature gathers span 2x the devices for zero extra information —
    measured collective-bound in benchmarks/README.md §Perf iteration L3.1).
    Requires ``plan.t_p %% mesh.shape[resample_axis] == 0``.
    Returns ``(step, in_shardings, out_shardings)``.
    """
    mesh = _auto_axes(mesh)
    n_dev = 1
    for ax in block_axes:
        n_dev *= mesh.shape[ax]
    b_total = plan.blocks_per_resample
    if b_total % n_dev != 0:
        raise ValueError(
            f"blocks per resample ({plan.m}x{plan.n}={b_total}) must be a "
            f"multiple of the device count {n_dev}; adjust the plan grid"
        )
    if resample_axis is not None and plan.t_p % mesh.shape[resample_axis] != 0:
        raise ValueError(
            f"T_p={plan.t_p} must be a multiple of the resample axis size "
            f"{mesh.shape[resample_axis]}")
    b_loc = b_total // n_dev
    axes = tuple(block_axes)
    # Effective per-axis signature widths: anchor_indices clamps the anchor
    # set to the axis length, so row signatures (means over anchor *cols*)
    # carry min(signature_dim, n_cols) features and col signatures
    # min(signature_dim, n_rows) — reshaping with the raw cfg.signature_dim
    # crashed on matrices with a short axis.
    q_row = min(cfg.signature_dim, plan.n_cols)
    q_col = min(cfg.signature_dim, plan.n_rows)

    block_spec = P(axes, None, None)     # blocks sharded over all mesh axes
    rep = P()                            # replicated

    def local_atom_phase(blocks, keys, row_feats, col_feats):
        """shard_map body, phase 2: blocks (b_loc, phi, psi) device-local.

        Pure local compute — small per-device SVD/QR/k-means, identical
        static shapes everywhere, zero communication.
        """
        row_labels, col_labels = jax.vmap(_atom_fn(cfg))(keys, blocks)
        with jax.named_scope("signatures"):
            row_sigs, row_counts = merging.atom_signatures(
                row_feats, row_labels, cfg.atom_k)
            col_sigs, col_counts = merging.atom_signatures(
                col_feats, col_labels, cfg.atom_d)
        return row_labels, col_labels, row_sigs, row_counts, col_sigs, col_counts

    atom_phase = shard_map(
        local_atom_phase,
        mesh=mesh,
        in_specs=(block_spec, P(axes), block_spec, block_spec),
        out_specs=(P(axes, None), P(axes, None), block_spec, P(axes, None),
                   block_spec, P(axes, None)),
        check_vma=False,
    )

    def local_atom_phase_tp(blocks, keys, row_feats, col_feats):
        """Like local_atom_phase but with a leading local-resample dim."""
        f = jax.vmap(local_atom_phase)
        return f(blocks, keys, row_feats, col_feats)

    ra = resample_axis
    tp_block = P(ra, axes, None, None)
    atom_phase_tp = shard_map(
        local_atom_phase_tp,
        mesh=mesh,
        in_specs=(tp_block, P(ra, axes), tp_block, tp_block),
        out_specs=(P(ra, axes, None), P(ra, axes, None), tp_block,
                   P(ra, axes, None), tp_block, P(ra, axes, None)),
        check_vma=False,
    ) if ra is not None else None

    def merge_phase(row_sigs, row_counts, row_labels, row_pos,
                    col_sigs, col_counts, col_labels, col_pos, merge_key):
        """shard_map body, phase 3: one joint merge over ALL resamples.

        Inputs are (T_p, b_loc, ...) device-local stacks. Only signatures
        (k x q floats per atom) cross the interconnect; the tiny consensus
        k-means runs replicated so no broadcast of its result is needed.
        """
        all_row_sigs, all_row_counts = row_sigs, row_counts
        all_col_sigs, all_col_counts = col_sigs, col_counts
        # log-tree per axis. Gather order matters: P(("data","model")) lays
        # blocks out data-major, and each tiled all_gather makes the gathered
        # axis *outermost* — so gather the innermost mesh axis first.
        for ax in reversed(axes):
            all_row_sigs = jax.lax.all_gather(all_row_sigs, ax, axis=1, tiled=True)
            all_row_counts = jax.lax.all_gather(all_row_counts, ax, axis=1, tiled=True)
            all_col_sigs = jax.lax.all_gather(all_col_sigs, ax, axis=1, tiled=True)
            all_col_counts = jax.lax.all_gather(all_col_counts, ax, axis=1, tiled=True)
        if resample_axis is not None:
            # resample dim sharded over the pod axis: gather it on axis 0
            all_row_sigs = jax.lax.all_gather(all_row_sigs, resample_axis,
                                              axis=0, tiled=True)
            all_row_counts = jax.lax.all_gather(all_row_counts, resample_axis,
                                                axis=0, tiled=True)
            all_col_sigs = jax.lax.all_gather(all_col_sigs, resample_axis,
                                              axis=0, tiled=True)
            all_col_counts = jax.lax.all_gather(all_col_counts, resample_axis,
                                                axis=0, tiled=True)

        kr, kc = jax.random.split(merge_key)
        # joint clustering across resamples AND blocks: one shared label
        # space, exactly like the single-host merge (label spaces from
        # different resamples must not be mixed unaligned).
        atom_global_r = merging.cluster_atoms_best(
            kr, all_row_sigs.reshape(-1, q_row), all_row_counts.reshape(-1),
            cfg.n_row_clusters, cfg.merge_kmeans_iters,
            n_restarts=cfg.merge_restarts,
        ).reshape(plan.t_p, b_total, cfg.atom_k)
        atom_global_c = merging.cluster_atoms_best(
            kc, all_col_sigs.reshape(-1, q_col), all_col_counts.reshape(-1),
            cfg.n_col_clusters, cfg.merge_kmeans_iters,
            n_restarts=cfg.merge_restarts,
        ).reshape(plan.t_p, b_total, cfg.atom_d)

        # this device's slice of the replicated global atom table
        dev_linear = jnp.int32(0)
        stride = 1
        for ax in reversed(axes):
            dev_linear = dev_linear + jax.lax.axis_index(ax) * stride
            stride = stride * mesh.shape[ax]
        my_atoms_r = jax.lax.dynamic_slice_in_dim(
            atom_global_r, dev_linear * b_loc, b_loc, axis=1)
        my_atoms_c = jax.lax.dynamic_slice_in_dim(
            atom_global_c, dev_linear * b_loc, b_loc, axis=1)
        if resample_axis is not None:
            t_loc = plan.t_p // mesh.shape[resample_axis]
            t_start = jax.lax.axis_index(resample_axis) * t_loc
            my_atoms_r = jax.lax.dynamic_slice_in_dim(
                my_atoms_r, t_start, t_loc, axis=0)
            my_atoms_c = jax.lax.dynamic_slice_in_dim(
                my_atoms_c, t_start, t_loc, axis=0)

        point_global_r = jnp.take_along_axis(my_atoms_r, row_labels, axis=2)
        point_global_c = jnp.take_along_axis(my_atoms_c, col_labels, axis=2)
        row_votes = _merge_votes_local(
            point_global_r, row_pos, plan.n_rows, cfg.n_row_clusters)
        col_votes = _merge_votes_local(
            point_global_c, col_pos, plan.n_cols, cfg.n_col_clusters)
        reduce_axes = axes + ((resample_axis,) if resample_axis else ())
        for ax in reduce_axes:
            row_votes = jax.lax.psum(row_votes, ax)
            col_votes = jax.lax.psum(col_votes, ax)
        return row_votes, col_votes

    # (T_p, blocks, ...) stacks: blocks sharded on axis 1; resample dim on
    # axis 0 sharded over the pod axis when resample parallelism is on.
    tdim = resample_axis  # None -> replicated t dim
    tblock = P(tdim, axes)
    merge = shard_map(
        merge_phase,
        mesh=mesh,
        in_specs=(P(tdim, axes, None, None), tblock, P(tdim, axes, None), tblock,
                  P(tdim, axes, None, None), tblock, P(tdim, axes, None), tblock,
                  rep),
        out_specs=(rep, rep),
        check_vma=False,
    )

    def step(a):
        # device phases as named scopes, as in lamc._lamc_jit (DESIGN.md
        # §14); the merge's collectives fall under merge
        with jax.named_scope("signatures"):
            kroot = jax.random.key(plan.seed + 7)
            kar, kac, kmerge = jax.random.split(kroot, 3)
            anchor_rows = merging.anchor_indices(kar, plan.n_rows,
                                                 cfg.signature_dim)
            anchor_cols = merging.anchor_indices(kac, plan.n_cols,
                                                 cfg.signature_dim)
        b = plan.blocks_per_resample
        with jax.named_scope("extract"):
            i_of_b = jnp.arange(b) // plan.n
            j_of_b = jnp.arange(b) % plan.n
        extract_fn = (partition.extract_blocks_sparse
                      if cfg.input_format == "bcoo" else partition.extract_blocks)

        def extract(t):
            # phase 1: block scatter (GSPMD all-to-all, data moves once)
            with jax.named_scope("extract"):
                blocks, row_idx, col_idx = extract_fn(a, plan, t)
                row_pos, col_pos = row_idx[i_of_b], col_idx[j_of_b]
            with jax.named_scope("atom/svd"):
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(
                        jax.random.fold_in(jax.random.key(plan.seed + 1), t), i)
                )(jnp.arange(b))
            with jax.named_scope("signatures"):
                # anchor slivers first ((M, q_row) / (q_col, N)) — indexing
                # rows first would materialize an (m, phi, N) intermediate
                # (same gather-order fix as extract_blocks).
                row_sliver, col_sliver = anchor_features(a, anchor_rows,
                                                         anchor_cols)
                row_feats = row_sliver[row_idx][i_of_b]         # (B, phi, q_row)
                col_feats = jnp.transpose(
                    col_sliver[:, col_idx], (1, 2, 0))[j_of_b]  # (B, psi, q_col)
            return blocks, keys, row_feats, col_feats, row_pos, col_pos

        if resample_axis is None:
            # resamples run sequentially (lax.scan) — single-pod path
            def body(_, t):
                blocks, keys, row_feats, col_feats, row_pos, col_pos = extract(t)
                with jax.named_scope("extract"):
                    blocks = jax.lax.with_sharding_constraint(
                        blocks, NamedSharding(mesh, block_spec))
                rl, cl, rs, rc, cs, cc = atom_phase(blocks, keys, row_feats,
                                                    col_feats)
                return None, dict(
                    row_labels=rl, col_labels=cl,
                    row_sigs=rs, row_counts=rc, col_sigs=cs, col_counts=cc,
                    row_pos=row_pos, col_pos=col_pos,
                )

            _, stk = jax.lax.scan(body, None, jnp.arange(plan.t_p))
        else:
            # resamples parallel over the pod axis: (T_p, B, ...) sharded
            # (pod, (data, model), ...) — one block-task per device, no
            # duplicated work across pods.
            ext = jax.vmap(extract)(jnp.arange(plan.t_p))
            with jax.named_scope("extract"):
                blocks_t = jax.lax.with_sharding_constraint(
                    ext[0], NamedSharding(mesh, P(resample_axis, axes, None,
                                                  None)))
            rl, cl, rs, rc, cs, cc = atom_phase_tp(
                blocks_t, ext[1], ext[2], ext[3])
            stk = dict(row_labels=rl, col_labels=cl, row_sigs=rs,
                       row_counts=rc, col_sigs=cs, col_counts=cc,
                       row_pos=ext[4], col_pos=ext[5])

        with jax.named_scope("merge"):
            # phase 3: one hierarchical merge across all resamples
            row_votes, col_votes = merge(
                stk["row_sigs"], stk["row_counts"], stk["row_labels"], stk["row_pos"],
                stk["col_sigs"], stk["col_counts"], stk["col_labels"], stk["col_pos"],
                kmerge,
            )
            # assignment semantics shared with the single-host merge: the psum'd
            # vote tables are bit-identical to the single-host scatter (small
            # integer counts in f32, exact under any summation order), so the
            # labels AND the overlap memberships match bit-for-bit at equal
            # seeds (DESIGN.md §11).
            row_labels, row_member = merging.finalize_assignment(
                row_votes, cfg.assignment, cfg.overlap_threshold,
                cfg.min_membership)
            col_labels, col_member = merging.finalize_assignment(
                col_votes, cfg.assignment, cfg.overlap_threshold,
                cfg.min_membership)
            # serving signatures: cluster means over the anchor slivers under the
            # final consensus labels — tiny (K x q), replicated; GSPMD emits the
            # gathers for the sliver reads of the sharded matrix.
            row_sliver, col_sliver = anchor_features(a, anchor_rows, anchor_cols)
            row_sigs, row_mean, _ = merging.cluster_signatures(
                row_sliver, row_labels, cfg.n_row_clusters)
            col_sigs, col_mean, _ = merging.cluster_signatures(
                col_sliver.T, col_labels, cfg.n_col_clusters)
        return dict(
            row_labels=row_labels,
            col_labels=col_labels,
            row_votes=row_votes,
            col_votes=col_votes,
            row_sigs=row_sigs, col_sigs=col_sigs,
            row_mean=row_mean, col_mean=col_mean,
            anchor_rows=anchor_rows, anchor_cols=anchor_cols,
            row_membership=row_member, col_membership=col_member,
        )

    # data matrix sharded over the first two trailing mesh axes (row, col);
    # a BCOO input replicates — its (nse,)/(nse, 2) leaves have no grid
    # layout, and the O(nnz) block scatter is re-derived per device.
    if cfg.input_format == "bcoo":
        a_spec = P()
    elif len(block_axes) >= 2:
        a_axes = list(block_axes)
        a_spec = P(tuple(a_axes[:-1]), a_axes[-1])
    else:
        a_spec = P(block_axes[0], None)
    in_shardings = NamedSharding(mesh, a_spec)
    out_shardings = NamedSharding(mesh, P())
    return step, in_shardings, out_shardings


def lamc_input_specs(plan: partition.PartitionPlan, dtype=jnp.float32):
    """ShapeDtypeStruct stand-in for the data matrix (dry-run input)."""
    return jax.ShapeDtypeStruct((plan.n_rows, plan.n_cols), dtype)


def distributed_lamc(mesh: Mesh, a: jax.Array, cfg: LAMCConfig,
                     plan: partition.PartitionPlan,
                     block_axes: Sequence[str] = ("data", "model"),
                     resample_axis: str | None = None) -> LAMCResult:
    """Run distributed LAMC on ``mesh``. See module docstring."""
    _validate_input_format(a, cfg)
    with obs.span("distributed_lamc", devices=mesh.size,
                  mesh=str(dict(mesh.shape)),
                  block_axes="/".join(block_axes),
                  resample_axis=resample_axis or "",
                  m=plan.m, n=plan.n, phi=plan.phi, psi=plan.psi,
                  t_p=plan.t_p, spmm_route=plan.spmm_route,
                  extract=partition.extraction(
                      plan, cfg.input_format == "bcoo")):
        with obs.span("build_step"):
            step, in_sh, out_sh = lamc_step_fn(cfg, plan, mesh, block_axes,
                                               resample_axis=resample_axis)
            step_c = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        # One XLA program runs the phases, named scopes on the device
        # (DESIGN.md §14); on the host, the call and the wait for it.
        with obs.span("dispatch"):
            with in_sh.mesh:          # lamc_step_fn's Auto view of mesh
                out = step_c(a)
        with obs.span("wait") as ws:
            ws.fence(out)
        with obs.span("finalize"):
            return LAMCResult(
                out["row_labels"], out["col_labels"],
                out["row_votes"], out["col_votes"], plan,
                row_sigs=out["row_sigs"], col_sigs=out["col_sigs"],
                row_mean=out["row_mean"], col_mean=out["col_mean"],
                anchor_rows=out["anchor_rows"],
                anchor_cols=out["anchor_cols"],
                row_membership=out["row_membership"],
                col_membership=out["col_membership"])
