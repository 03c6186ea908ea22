"""Out-of-core streaming co-clustering fit (DESIGN.md §10, §12).

``fit(chunks, cfg)`` consumes the data matrix as a stream of **row
chunks** (dense arrays or BCOO, each ``(r, N)``) and grows a
:class:`~repro.streaming.model.CoclusterModel` without ever holding the
``M x N`` matrix: peak resident data is one chunk plus model-sized state.

Per chunk ``t`` (all static-shape, DESIGN.md §2 — one jit trace per chunk
shape, keys counter-derived from ``(seed, t, block)``):

  1. **Atom phase.** The chunk is cut into ``col_blocks`` column blocks
     (``(r, psi)`` each) for each of ``chunk_resamples`` independent
     column permutations (re-derived from ``fold_in(seed, t, resample)``
     — the streaming analogue of the batch ``T_p``), and the atom
     co-clusterer (SCC) runs vmapped over the block stack — the same
     embarrassingly parallel unit as the batch pipeline, with the chunk
     playing the role of one row-band of a resample.
  2. **Signature fold.** Each block's atoms are reduced to anchor-column
     signatures (``merging.atom_signatures``) with member counts and raw
     anchor-feature sums, and those **atom summaries** — never the chunk
     — are folded into the growing model state: ``O(B * k * q)`` floats
     plus the ``(B, r)`` local labels per chunk. This is the hierarchy of
     the batch merge (block -> signature local reduce) applied stream-side.
  3. **Anchor-row reservoir.** A uniform reservoir sample (Algorithm R)
     of ``anchor_rows`` rows is maintained with its ``(q, N)`` data
     sliver; at finalize it is the anchor-row feature space in which
     columns are clustered and served.

``finalize()`` completes the hierarchical merge exactly as the batch
pipeline does: one best-of-restarts signature k-means over **all** chunk
atoms (``merging.cluster_atoms_best`` — the same global alignment the
batch merge runs over all resample atoms), per-row votes through each
chunk's aligned atoms, and column clustering + serving signatures in the
reservoir sliver space. Because the global alignment sees every atom —
not a first-chunk bootstrap — streaming consensus quality matches the
batch merge instead of depending on the first chunk's luck.

**Resumable chunk steps (DESIGN.md §12).** Every chunk fold is a keyed,
re-runnable unit: its randomness is counter-derived from ``(seed, t)``
(atom keys, column permutations, AND the reservoir draws — a fresh
``default_rng([seed + 13, t])`` per chunk, never a sequential host RNG),
and the whole accumulator is a serializable pytree (``state_tree`` /
``from_state_tree``) checkpointed via ``repro.checkpoint``. ``fit``
accepts ``ckpt_dir``/``save_every`` (periodic ``FitState`` checkpoints
driven through ``runtime.fault_tolerance.run_with_recovery``),
``failure_injector`` (a ``SimulatedFailure`` mid-fit restores the latest
state and refolds the lost chunks from a bounded replay buffer), and
``resume_from`` (a new process continues a killed fit). An interrupted
fit that resumes produces a **bit-identical** ``CoclusterModel`` to the
uninterrupted run at equal seeds — the recovery-equivalence invariant
``tests/test_fault_tolerance.py`` pins, including across a real SIGKILL
and an elastic restore onto a different device count.

Memory audit (the O(chunk + model) claim): resident at any time are one
chunk (``r x N``), the reservoir sliver (``anchor_rows x N``), and the
accumulated atom summaries + local labels, which are O(atoms * q + M *
B/r) — proportional to model/label state, never ``M x N``. With recovery
enabled, a replay buffer of the last ``save_every + 2`` chunks is also
resident (the chunks a restore may need to refold). ``FitStats`` reports
the measured peaks.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from collections import OrderedDict
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as _ckpt
from repro import obs
from repro.core import merging as _merging
from repro.core import sparse as _sparse
from repro.core import spectral as _spectral
from repro.core.lamc import LAMCConfig
from repro.core.lamc import validate_assignment as _validate_assignment

from .model import CoclusterModel

__all__ = ["StreamConfig", "FitStats", "StreamingCocluster", "fit",
           "iter_row_chunks", "stream_config_from_lamc",
           "FIT_STATE_KIND", "save_fit_state", "load_fit_state"]

logger = logging.getLogger("repro.streaming.fit")

#: extra_meta["kind"] tag of a FitState checkpoint — distinguishes an
#: in-progress fit from a servable CoclusterModel artifact.
FIT_STATE_KIND = "stream_fit_state"
_FIT_STATE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    n_row_clusters: int
    n_col_clusters: int
    # block k/d: clusters the atom method looks for inside one chunk block
    atom_row_clusters: int | None = None
    atom_col_clusters: int | None = None
    col_blocks: int = 4             # column blocks per chunk resample
    chunk_resamples: int = 1        # independent column permutations per chunk
    signature_dim: int = 64         # shared anchor columns q (row signatures)
    anchor_rows: int = 64           # row reservoir size (column features)
    seed: int = 0
    svd_iters: int = 4
    kmeans_iters: int = 16
    merge_kmeans_iters: int = 25
    merge_restarts: int = 4
    assign_impl: str = "jnp"        # "jnp" | "pallas" — atom k-means hot path
    qr_method: str = "qr"           # "qr" | "cholesky"
    # Sparse-route knob, mirrored from LAMCConfig so stream/batch configs
    # stay interchangeable (stream_config_from_lamc). For BCOO chunks it
    # decides how column blocks materialize: only a gather route
    # ("dual_ell" pinned, or "auto" below the probability.spmm_route
    # crossover) keeps the chunk sparse and scatters each resample's
    # blocks straight from the nonzeros, O(chunk nnz) per resample; any
    # other verdict densifies the chunk once (streaming has no tiled
    # backend — its trade is scatter vs densify). Either way the block
    # values are bit-identical — this is a memory/compute trade only.
    spmm_impl: str = "auto"
    # Assignment knobs mirrored from LAMCConfig (DESIGN.md §11), applied
    # at finalize(): "overlap" marks rows whose vote share clears no
    # cluster as outliers (label -1), exactly like the batch drivers.
    # The CoclusterModel keeps the full vote tables either way, so
    # membership *matrices* stay a load-time view
    # (``model_memberships``) with whatever knobs the caller passes.
    assignment: str = "hard"
    overlap_threshold: float = 0.25
    min_membership: int = 0

    @property
    def atom_k(self) -> int:
        return self.atom_row_clusters or self.n_row_clusters

    @property
    def atom_d(self) -> int:
        return self.atom_col_clusters or self.n_col_clusters

    @property
    def blocks_per_chunk(self) -> int:
        return self.col_blocks * self.chunk_resamples


def stream_config_from_lamc(cfg: LAMCConfig, **overrides) -> StreamConfig:
    """Carry the shared knobs of a batch LAMCConfig into a StreamConfig."""
    base = dict(
        n_row_clusters=cfg.n_row_clusters, n_col_clusters=cfg.n_col_clusters,
        atom_row_clusters=cfg.atom_row_clusters,
        atom_col_clusters=cfg.atom_col_clusters,
        signature_dim=cfg.signature_dim, seed=cfg.seed,
        svd_iters=cfg.svd_iters, kmeans_iters=cfg.kmeans_iters,
        merge_kmeans_iters=cfg.merge_kmeans_iters,
        merge_restarts=cfg.merge_restarts, assign_impl=cfg.assign_impl,
        qr_method=cfg.qr_method, spmm_impl=cfg.spmm_impl,
        assignment=cfg.assignment, overlap_threshold=cfg.overlap_threshold,
        min_membership=cfg.min_membership,
    )
    base.update(overrides)
    return StreamConfig(**base)


class FitStats(NamedTuple):
    rows_seen: int
    n_cols: int
    chunks: int
    fit_seconds: float
    rows_per_s: float
    peak_chunk_bytes: int   # largest single chunk held resident
    state_bytes: int        # model-sized accumulator footprint at finalize


@functools.partial(jax.jit, static_argnames=("cfg",))
def _chunk_atoms(cfg: StreamConfig, chunk_blocks: jax.Array,
                 feats: jax.Array, t: jax.Array):
    """Atom phase + signature reduce for one chunk (static per (r, psi)).

    ``chunk_blocks``: (blocks_per_chunk, r, psi) dense block stack;
    ``feats``: (r, q) anchor-column features. Returns per-block row
    labels, centered/unit atom signatures with member counts, and the
    *raw* per-atom anchor-feature sums (for the serving signatures —
    those are centered globally, not per block).
    """
    b = cfg.blocks_per_chunk
    keys = jax.vmap(
        lambda i: jax.random.fold_in(
            jax.random.fold_in(jax.random.key(cfg.seed + 1), t), i)
    )(jnp.arange(b))

    def atom(key, block):
        res = _spectral.scc(
            key, block, cfg.atom_k, cfg.atom_d,
            svd_iters=cfg.svd_iters, kmeans_iters=cfg.kmeans_iters,
            assign_impl=cfg.assign_impl, qr_method=cfg.qr_method)
        return res.row_labels

    row_labels = jax.vmap(atom)(keys, chunk_blocks)          # (B, r)
    r = feats.shape[0]
    block_feats = jnp.broadcast_to(feats[None], (b, r, feats.shape[1]))
    sigs, counts = _merging.atom_signatures(block_feats, row_labels, cfg.atom_k)
    onehot = jax.nn.one_hot(row_labels, cfg.atom_k, dtype=jnp.float32)
    raw_sums = jnp.einsum("brk,rq->bkq", onehot, feats.astype(jnp.float32))
    return row_labels, sigs, counts, raw_sums


def _nbytes(x) -> int:
    if _sparse.is_bcoo(x):
        return int(x.data.size * x.data.dtype.itemsize
                   + x.indices.size * x.indices.dtype.itemsize)
    return int(np.asarray(x).nbytes if isinstance(x, np.ndarray)
               else x.size * x.dtype.itemsize)


def _chunk_fingerprint(chunk) -> tuple[str, np.dtype]:
    """(format, value dtype) of one chunk — the trace-shaping properties a
    stream must hold constant (validated per chunk, DESIGN.md §12)."""
    if _sparse.is_bcoo(chunk):
        return "bcoo", np.dtype(chunk.data.dtype)
    return "dense", np.dtype(chunk.dtype)


class StreamingCocluster:
    """Stateful out-of-core fitter: ``partial_fit`` chunks, then ``finalize``.

    State is model-sized only: per-chunk atom summaries (signatures,
    counts, anchor-feature sums — ``O(B * k * q)`` each), per-chunk local
    labels (``(B, r)`` ints), and the ``(anchor_rows, N)`` reservoir
    sliver. The data chunks themselves are never retained. The whole
    accumulator serializes to a checkpointable pytree (``state_tree``)
    and rebuilds from one (``from_state_tree``) — every source of
    randomness is counter-derived from ``(cfg.seed, chunk index)``, so a
    rebuilt fitter continues bit-identically.
    """

    def __init__(self, cfg: StreamConfig):
        _sparse.validate_spmm_impl(cfg.spmm_impl)
        # StreamConfig mirrors every attribute the shared validator reads
        _validate_assignment(cfg)
        self.cfg = cfg
        self._n_cols: int | None = None
        self._anchor_cols: jax.Array | None = None
        self._atom_sigs: list[np.ndarray] = []       # per chunk (B*k, q)
        self._atom_cnts: list[np.ndarray] = []       # per chunk (B*k,)
        self._atom_sums: list[np.ndarray] = []       # per chunk (B*k, q) raw
        self._chunk_labels: list[np.ndarray] = []    # per chunk (B, r) int32
        self._anchor_sum: np.ndarray | None = None   # (q,)
        self._res_ids: np.ndarray | None = None      # (q_res,) global row ids
        self._res_vals: np.ndarray | None = None     # (q_res, N)
        self._res_fill = 0
        self._chunk_format: str | None = None        # "dense" | "bcoo"
        self._chunk_dtype: np.dtype | None = None
        self.rows_seen = 0
        self.chunks = 0
        self._t0 = time.perf_counter()
        self._peak_chunk_bytes = 0
        # (t, id(chunk)) -> (chunk ref, blocks, feats): recovery replays
        # refold the same chunk objects the cursor window retained, so the
        # densify/gather/permute prep of a refold is a pure repeat — serve
        # it from this bounded identity-keyed cache instead. Session-local
        # (never serialized): a restored fitter has no chunk objects.
        self._prep_cache: "OrderedDict[tuple, tuple]" = OrderedDict()

    # ------------------------------------------------------------------ setup

    def _init_state(self, n_cols: int) -> None:
        cfg = self.cfg
        self._n_cols = n_cols
        kroot = jax.random.key(cfg.seed + 7)
        _, kac, _ = jax.random.split(kroot, 3)
        self._anchor_cols = _merging.anchor_indices(kac, n_cols, cfg.signature_dim)
        q = int(self._anchor_cols.shape[0])
        self._anchor_sum = np.zeros((q,), np.float32)
        self._res_ids = np.zeros((cfg.anchor_rows,), np.int64)
        self._res_vals = np.zeros((cfg.anchor_rows, n_cols), np.float32)

    def _chunk_route(self, chunk) -> str:
        """Resolve cfg.spmm_impl for one BCOO chunk (host-side)."""
        from repro.core import probability as _prob

        if self.cfg.spmm_impl != "auto":
            return self.cfg.spmm_impl
        r, n = chunk.shape
        return _prob.spmm_route(chunk.nse / float(max(r * n, 1)),
                                float(r) * n)

    # --------------------------------------------------------------- validate

    def _validate_chunk(self, chunk, t: int) -> None:
        """Loud, chunk-indexed failure on a malformed mid-stream chunk.

        Catches the three drifts that otherwise surface as a deep jit
        shape/dtype error many frames below the ingest loop: wrong column
        count, value-dtype drift, and a dense<->BCOO format flip.
        """
        if _sparse.is_bcoo(chunk):
            _sparse.validate_bcoo(chunk)
        shape = tuple(chunk.shape)
        if len(shape) != 2:
            raise ValueError(
                f"chunk {t}: must be 2-D (rows, n_cols), got shape {shape}")
        fmt, dtype = _chunk_fingerprint(chunk)
        if self._n_cols is None:
            return  # first chunk defines the stream fingerprint
        if int(shape[1]) != self._n_cols:
            raise ValueError(
                f"chunk {t}: chunk has {shape[1]} columns, stream started "
                f"with {self._n_cols} — expected shape "
                f"(rows, {self._n_cols}), got {shape}")
        if self._chunk_format is not None and fmt != self._chunk_format:
            raise ValueError(
                f"chunk {t}: stream started with {self._chunk_format} "
                f"chunks, got {fmt} — a dense/BCOO flip mid-stream changes "
                "the compiled chunk program; convert upstream "
                "(data.synthetic.to_bcoo or .todense) instead")
        if self._chunk_dtype is not None and dtype != self._chunk_dtype:
            raise ValueError(
                f"chunk {t}: value dtype drifted — stream started with "
                f"{self._chunk_dtype}, got {dtype}; cast the chunk before "
                "partial_fit")

    def check_replayed_chunk(self, chunk, t: int) -> None:
        """Validate a chunk being skipped on resume against the recorded
        fold: its shape must match what checkpoint step ``t`` folded."""
        if t >= self.chunks:
            raise ValueError(
                f"chunk {t} replayed but only {self.chunks} chunks are in "
                "the restored state")
        want_rows = int(self._chunk_labels[t].shape[1])
        shape = tuple(chunk.shape)
        if shape != (want_rows, self._n_cols):
            raise ValueError(
                f"resumed stream does not match the checkpoint: chunk {t} "
                f"was folded with shape ({want_rows}, {self._n_cols}), the "
                f"replayed stream yields {shape} — resume requires the "
                "same chunking of the same stream")

    # -------------------------------------------------------------- reservoir

    def _reservoir_update(self, chunk, r: int, t: int) -> None:
        """Algorithm R over the arriving rows (uniform over the stream).

        Vectorized per chunk: one RNG call draws every row's slot
        candidate, so ingest pays no per-row Python loop. Duplicate slot
        hits within a chunk resolve to the *last* arriving row (numpy
        fancy assignment applies writes in index order), matching the
        sequential formulation. The generator is counter-derived from
        ``(seed, t)`` — chunk ``t``'s draws are a pure function of the
        chunk index, never of how many draws preceded them, so a fit
        resumed from a checkpoint replays the identical reservoir
        (DESIGN.md §12 RNG-provenance invariant).
        """
        cap = self.cfg.anchor_rows
        rng = np.random.default_rng([self.cfg.seed + 13, t])
        gids = self.rows_seen + np.arange(r, dtype=np.int64)
        n_fill = min(max(cap - self._res_fill, 0), r)
        fill_slots = np.arange(self._res_fill, self._res_fill + n_fill)
        j = rng.integers(0, gids[n_fill:] + 1)                  # (r - n_fill,)
        keep = j < cap
        rows = np.concatenate([np.arange(n_fill), n_fill + np.nonzero(keep)[0]])
        slots = np.concatenate([fill_slots, j[keep]])
        self._res_fill += n_fill
        if rows.size == 0:
            return
        self._res_ids[slots] = gids[rows]
        if _sparse.is_bcoo(chunk):
            vals = np.asarray(_sparse.gather_rows_dense(chunk, jnp.asarray(rows)))
        else:
            # gather where the chunk lives: a device chunk sends only the
            # kept rows to the host, not all r of them
            vals = np.asarray(chunk[rows]).astype(np.float32)
        self._res_vals[slots] = vals

    # ------------------------------------------------------------------- fold

    def _blocks_and_feats(self, chunk, t: int):
        """(blocks_per_chunk, r, psi) block stack + (r, q) anchor features.

        Each of the ``chunk_resamples`` local resamples cuts the chunk's
        columns with an independent permutation (counter-derived from
        ``(seed, t, resample)``) — the streaming analogue of the batch
        ``T_p``: more independent atoms per row, stronger consensus.

        Keyed by ``(t, chunk identity)`` in a small cache: a recovery
        replay refolds the *same* chunk object at the same step index, so
        its prep (densify/gather + permutation assembly) is served from
        the first fold — bit-identical by construction (same objects,
        same counter-derived permutations).
        """
        cfg = self.cfg
        chunk_obj = chunk               # identity anchor (chunk is rebound)
        ck = (t, id(chunk))
        hit = self._prep_cache.get(ck)
        if hit is not None and hit[0] is chunk:
            obs.get_registry().counter(
                "stream_chunk_prep",
                help="streaming chunk prep cache events",
            ).labels(event="hit").inc()
            return hit[1], hit[2]
        obs.get_registry().counter(
            "stream_chunk_prep",
            help="streaming chunk prep cache events",
        ).labels(event="miss").inc()
        n = self._n_cols
        psi = n // cfg.col_blocks
        key_t = jax.random.fold_in(jax.random.key(cfg.seed), t)
        perms = [
            jax.random.permutation(jax.random.fold_in(key_t, ri),
                                   n)[: cfg.col_blocks * psi]
            for ri in range(cfg.chunk_resamples)
        ]
        if _sparse.is_bcoo(chunk) and self._chunk_route(chunk) != "dual_ell":
            # Streaming has no tiled backend — the chunk trade is
            # scatter-vs-densify only, so any non-gather verdict (tiled
            # or dense; BENCH_sparse: gathers lose ~1.9x by d = 0.2)
            # densifies the chunk once instead of paying a per-resample
            # scatter. Same values bit-exact either way (each cell holds
            # one stored nonzero or zero).
            chunk = chunk.todense()
        if _sparse.is_bcoo(chunk):
            # one gather per resample: gather_cols_dense inverts the column
            # map, so the index set must be duplicate-free — true within one
            # permutation, not across the concatenation of several
            sub = jnp.concatenate(
                [_sparse.gather_cols_dense(chunk, p) for p in perms], axis=1)
            feats = _sparse.gather_cols_dense(chunk, self._anchor_cols)
        else:
            dense = jnp.asarray(chunk)
            sub = dense[:, jnp.concatenate(perms)]
            feats = dense[:, self._anchor_cols]
        r = sub.shape[0]
        blocks = jnp.transpose(
            sub.reshape(r, cfg.blocks_per_chunk, psi), (1, 0, 2))
        feats = feats.astype(jnp.float32)
        self._prep_cache[ck] = (chunk_obj, blocks, feats)
        # bound by the cursor's replay window: older steps can't refold
        while len(self._prep_cache) > 4:
            self._prep_cache.popitem(last=False)
        return blocks, feats

    def partial_fit(self, chunk, *, replayed: bool = False
                    ) -> "StreamingCocluster":
        """Fold one ``(r, N)`` row chunk (dense or BCOO) into the model.

        ``replayed=True`` marks the chunk span as a refold — the fit
        driver passes it when a recovery rolled the step counter back, so
        a trace distinguishes first-time folds from recovery replays.
        """
        t = self.chunks
        self._validate_chunk(chunk, t)
        shape = tuple(chunk.shape)
        if self._n_cols is None:
            self._init_state(int(shape[1]))
        if self._chunk_format is None:
            # first chunk — or a fitter rebuilt from a tree without stream
            # metadata (elastic restore): adopt this chunk's fingerprint
            self._chunk_format, self._chunk_dtype = _chunk_fingerprint(chunk)
        r = int(shape[0])
        if r == 0:
            return self  # not a step: no span either (one span per fold)
        self._peak_chunk_bytes = max(self._peak_chunk_bytes, _nbytes(chunk))

        with obs.span("chunk", t=t, rows=r, replayed=replayed):
            with obs.span("blocks"):
                blocks, feats = self._blocks_and_feats(chunk, t)
            with obs.span("atoms") as asp:
                row_labels, sigs, counts, raw_sums = asp.fence(_chunk_atoms(
                    self.cfg, blocks, feats, jnp.int32(t)))

            q = sigs.shape[-1]
            self._atom_sigs.append(np.asarray(sigs).reshape(-1, q))
            self._atom_cnts.append(np.asarray(counts).reshape(-1))
            self._atom_sums.append(np.asarray(raw_sums).reshape(-1, q))
            self._chunk_labels.append(np.asarray(row_labels))
            self._anchor_sum += np.asarray(feats, dtype=np.float32).sum(axis=0)

            with obs.span("reservoir"):
                self._reservoir_update(chunk, r, t)
        self.rows_seen += r
        self.chunks += 1
        return self

    # ------------------------------------------------------------- checkpoint

    def state_tree(self) -> dict:
        """The fit accumulator as a checkpointable pytree (host arrays).

        Everything ``from_state_tree`` needs to continue the fit
        bit-identically: atom summaries + local labels per chunk (keyed
        by zero-padded chunk index so flattened leaf names sort), the
        reservoir (ids, sliver, fill), the running anchor sum, and the
        integer counters packed into one ``scalars`` vector. RNG state is
        deliberately absent — all randomness is ``(seed, chunk)``
        counter-derived, so provenance is the counters themselves.
        """
        if self._n_cols is None:
            raise ValueError("no chunks folded yet — nothing to checkpoint")
        scalars = np.asarray(
            [self._n_cols, self.rows_seen, self.chunks, self._res_fill,
             self._peak_chunk_bytes], np.int64)
        return {
            "scalars": scalars,
            "anchor_cols": np.asarray(self._anchor_cols),
            "anchor_sum": np.asarray(self._anchor_sum),
            "res_ids": np.asarray(self._res_ids),
            "res_vals": np.asarray(self._res_vals),
            "atom_sigs": {f"{i:06d}": a for i, a in enumerate(self._atom_sigs)},
            "atom_cnts": {f"{i:06d}": a for i, a in enumerate(self._atom_cnts)},
            "atom_sums": {f"{i:06d}": a for i, a in enumerate(self._atom_sums)},
            "chunk_labels": {f"{i:06d}": a
                             for i, a in enumerate(self._chunk_labels)},
        }

    @classmethod
    def from_state_tree(cls, cfg: StreamConfig, tree: dict,
                        chunk_format: str | None = None,
                        chunk_dtype: str | None = None
                        ) -> "StreamingCocluster":
        """Rebuild a fitter from a ``state_tree`` pytree (leaves may be
        numpy or device arrays — an elastic restore hands sharded device
        arrays straight in; they are gathered to host here)."""
        self = cls(cfg)
        sc = np.asarray(tree["scalars"]).astype(np.int64)
        self._n_cols = int(sc[0])
        self.rows_seen = int(sc[1])
        self.chunks = int(sc[2])
        self._res_fill = int(sc[3])
        self._peak_chunk_bytes = int(sc[4])
        self._anchor_cols = jnp.asarray(np.asarray(tree["anchor_cols"]))
        # explicit copies: these are mutated in place by partial_fit, and
        # np.asarray of a device array yields a read-only view
        self._anchor_sum = np.array(tree["anchor_sum"], np.float32)
        self._res_ids = np.array(tree["res_ids"], np.int64)
        self._res_vals = np.array(tree["res_vals"], np.float32)
        for field, dst in (("atom_sigs", self._atom_sigs),
                           ("atom_cnts", self._atom_cnts),
                           ("atom_sums", self._atom_sums),
                           ("chunk_labels", self._chunk_labels)):
            node = tree.get(field, {})
            for key in sorted(node):
                dst.append(np.asarray(node[key]))
            if len(dst) != self.chunks:
                raise ValueError(
                    f"fit state is inconsistent: {self.chunks} chunks "
                    f"recorded but {field} holds {len(dst)} entries — "
                    "partial or foreign checkpoint")
        if chunk_format is not None:
            self._chunk_format = chunk_format
        if chunk_dtype is not None:
            self._chunk_dtype = np.dtype(chunk_dtype)
        return self

    # --------------------------------------------------------------- finalize

    def finalize(self) -> tuple[CoclusterModel, FitStats]:
        if self.rows_seen == 0:
            raise ValueError("no chunks were fit; stream was empty")
        cfg = self.cfg
        k_row, k_col = cfg.n_row_clusters, cfg.n_col_clusters
        n = self._n_cols
        k = cfg.atom_k
        b = cfg.blocks_per_chunk

        with obs.span("finalize", chunks=self.chunks,
                      rows=self.rows_seen) as fin:
            # global atom alignment: the batch merge's signature k-means over
            # ALL chunk atoms (count-weighted, best-of-restarts) — the top of
            # the streaming hierarchy (block -> signature -> global clusters)
            with obs.span("align", atoms=sum(len(c) for c in self._atom_cnts)):
                flat_sigs = jnp.asarray(np.concatenate(self._atom_sigs, axis=0))
                flat_cnt = jnp.asarray(np.concatenate(self._atom_cnts, axis=0))
                kmerge = jax.random.fold_in(jax.random.key(cfg.seed + 7), 2)
                atom_global = np.asarray(_merging.cluster_atoms_best(
                    kmerge, flat_sigs, flat_cnt, k_row,
                    cfg.merge_kmeans_iters, n_restarts=cfg.merge_restarts))

            with obs.span("votes") as vsp:
                # per-row votes through each chunk's aligned atoms (numpy:
                # chunk sizes vary, keep this off the jit cache)
                vote_rows = []
                for t, labels in enumerate(self._chunk_labels):
                    ag = atom_global[t * b * k:(t + 1) * b * k].reshape(b, k)
                    point_global = np.take_along_axis(ag, labels, axis=1)  # (B, r)
                    r = labels.shape[1]
                    votes = np.zeros((r, k_row), np.float32)
                    np.add.at(votes,
                              (np.arange(r)[None, :].repeat(b, 0), point_global),
                              1.0)
                    vote_rows.append(votes)
                row_votes = jnp.asarray(np.concatenate(vote_rows, axis=0))
                # assignment semantics shared with the batch drivers (§11):
                # overlap mode marks rows whose vote share clears no cluster as
                # outliers (-1); the vote tables ride in the model either way
                row_labels, _ = _merging.finalize_assignment(
                    row_votes, cfg.assignment, cfg.overlap_threshold,
                    cfg.min_membership)

                # row serving signatures: atom anchor-feature sums grouped by
                # the atoms' global cluster, centered by the global anchor mean
                row_mean = jnp.asarray(self._anchor_sum / self.rows_seen)
                sums = np.concatenate(self._atom_sums, axis=0)      # (A, q)
                cnts = np.concatenate(self._atom_cnts, axis=0)      # (A,)
                sig_sum = np.zeros((k_row, sums.shape[1]), np.float32)
                sig_cnt = np.zeros((k_row,), np.float32)
                np.add.at(sig_sum, atom_global, sums)
                np.add.at(sig_cnt, atom_global, cnts)
                sig = (jnp.asarray(sig_sum) / jnp.maximum(
                    jnp.asarray(sig_cnt)[:, None], 1.0)) - row_mean[None, :]
                row_sigs = sig / jnp.maximum(
                    jnp.linalg.norm(sig, axis=1, keepdims=True), 1e-12)
                vsp.fence((row_labels, row_sigs))

            with obs.span("columns") as csp:
                # columns: clustered in the reservoir-sliver feature space
                # (the anchor-row features serving uses), centered +
                # unit-normalized so profile *direction* decides, then the
                # same best-of-restarts k-means as the row alignment
                fill = max(self._res_fill, 1)
                sliver = jnp.asarray(self._res_vals[:fill])         # (q_res, N)
                feats_c = sliver.T                                  # (N, q_res)
                feats_c = feats_c - jnp.mean(feats_c, axis=0, keepdims=True)
                feats_c = feats_c / jnp.maximum(
                    jnp.linalg.norm(feats_c, axis=1, keepdims=True), 1e-12)
                kcols = jax.random.fold_in(jax.random.key(cfg.seed + 7), 3)
                col_labels = _merging.cluster_atoms_best(
                    kcols, feats_c, jnp.ones((n,), jnp.float32), k_col,
                    cfg.merge_kmeans_iters, n_restarts=cfg.merge_restarts)
                col_votes = jax.nn.one_hot(col_labels, k_col, dtype=jnp.float32)
                col_sigs, col_mean, _ = _merging.cluster_signatures(
                    sliver.T, col_labels, k_col)
                anchor_rows = jnp.asarray(self._res_ids[:fill].astype(np.int32))

                model = csp.fence(CoclusterModel(
                    row_labels=row_labels,
                    col_labels=col_labels.astype(jnp.int32),
                    row_votes=row_votes, col_votes=col_votes,
                    row_sigs=row_sigs, col_sigs=col_sigs,
                    row_mean=row_mean.astype(jnp.float32),
                    col_mean=col_mean.astype(jnp.float32),
                    anchor_rows=anchor_rows,
                    anchor_cols=self._anchor_cols.astype(jnp.int32),
                ))
            fin.fence(model)
        dt = time.perf_counter() - self._t0
        state_bytes = int(
            sum(v.nbytes for vs in (self._atom_sigs, self._atom_cnts,
                                    self._atom_sums, self._chunk_labels)
                for v in vs)
            + self._res_vals.nbytes + self._anchor_sum.nbytes)
        stats = FitStats(
            rows_seen=self.rows_seen, n_cols=n, chunks=self.chunks,
            fit_seconds=dt, rows_per_s=self.rows_seen / max(dt, 1e-9),
            peak_chunk_bytes=self._peak_chunk_bytes, state_bytes=state_bytes)
        return model, stats


# ---------------------------------------------------------------------------
# FitState checkpoint round-trip
# ---------------------------------------------------------------------------


def save_fit_state(ckpt_dir: str, fitter: StreamingCocluster) -> str:
    """Checkpoint an in-progress fit (atomic, hash-manifested commit).

    The checkpoint step is the number of chunks folded, so
    ``checkpoint.latest_step`` IS the resume point.
    """
    meta = {
        "kind": FIT_STATE_KIND,
        "version": _FIT_STATE_VERSION,
        "stream_config": dataclasses.asdict(fitter.cfg),
        "chunks": fitter.chunks,
        "rows_seen": fitter.rows_seen,
        "chunk_format": fitter._chunk_format,
        "chunk_dtype": (str(fitter._chunk_dtype)
                        if fitter._chunk_dtype is not None else None),
    }
    return _ckpt.save(ckpt_dir, fitter.chunks, fitter.state_tree(),
                      extra_meta=meta)


def load_fit_state(ckpt_dir: str, cfg: StreamConfig, step: int | None = None
                   ) -> tuple[StreamingCocluster, int]:
    """Restore ``(fitter, chunks_folded)`` from a FitState checkpoint.

    Loud failure modes: no committed checkpoint (``FileNotFoundError``),
    foreign/stale checkpoint kind, and a config that differs from the
    one the state was fit with — recovery equivalence (DESIGN.md §12)
    only holds when the resumed fit runs the *same* program, so every
    differing field is named instead of silently continuing. Corrupt or
    truncated payloads surface as ``checkpoint.CheckpointCorruptError``
    naming the bad leaf.
    """
    if step is None:
        step = _ckpt.latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(
            f"no committed fit state under {ckpt_dir!r} — nothing to resume "
            "from (the fit died before its first checkpoint, or the path is "
            "wrong); rerun without resume_from")
    tree, meta = _ckpt.restore_tree(ckpt_dir, step)
    meta = meta or {}
    if meta.get("kind") != FIT_STATE_KIND:
        raise ValueError(
            f"checkpoint at {ckpt_dir!r} step {step} is "
            f"kind={meta.get('kind')!r}, expected {FIT_STATE_KIND!r} — not "
            "an in-progress streaming fit (a finished CoclusterModel "
            "artifact loads via streaming.load_model instead)")
    saved_cfg = meta.get("stream_config") or {}
    want_cfg = dataclasses.asdict(cfg)
    diffs = sorted(k for k in want_cfg
                   if saved_cfg.get(k) != want_cfg[k])
    if diffs:
        detail = ", ".join(
            f"{k}: checkpoint={saved_cfg.get(k)!r} vs resume={want_cfg[k]!r}"
            for k in diffs)
        raise ValueError(
            "resume config mismatch — recovery equivalence requires the "
            f"identical StreamConfig; differing fields: {detail}")
    fitter = StreamingCocluster.from_state_tree(
        cfg, tree, chunk_format=meta.get("chunk_format"),
        chunk_dtype=meta.get("chunk_dtype"))
    if fitter.chunks != int(meta.get("chunks", fitter.chunks)):
        raise ValueError(
            f"fit state at step {step} records {meta.get('chunks')} chunks "
            f"in its meta but {fitter.chunks} in its tree — corrupt or "
            "hand-edited checkpoint")
    return fitter, fitter.chunks


# ---------------------------------------------------------------------------
# fit driver: plain loop, or resumable chunk steps through run_with_recovery
# ---------------------------------------------------------------------------


class _ChunkCursor:
    """Stream cursor with a bounded replay buffer.

    ``get(t)`` returns chunk ``t``: from the buffer when a recovery
    rolled the step counter back, else by advancing the underlying
    iterator (strictly sequential). The buffer keeps the last
    ``save_every + 2`` chunks — exactly the window a restore from the
    latest checkpoint can need to refold — so recovery never requires a
    rewindable stream. Raises ``StopIteration`` on exhaustion (the
    stream-driven termination signal of ``run_with_recovery``).
    """

    def __init__(self, it, start: int, keep: int):
        self._it = it
        self._next = start
        self._keep = max(keep, 1)
        self._buf: dict = {}

    def get(self, t: int):
        if t in self._buf:
            return self._buf[t]
        if t != self._next:
            raise RuntimeError(
                f"chunk {t} requested but the replay buffer holds "
                f"{sorted(self._buf)} and the stream cursor is at "
                f"{self._next} — the restore point fell behind the "
                f"{self._keep}-chunk buffer (save_every too large for the "
                "failure pattern?)")
        chunk = next(self._it)          # StopIteration = stream exhausted
        while _skip_empty(chunk):
            chunk = next(self._it)      # empty chunks are not steps
        self._buf[t] = chunk
        if len(self._buf) > self._keep:
            del self._buf[min(self._buf)]
        self._next = t + 1
        return chunk


def _skip_empty(chunk) -> bool:
    return int(chunk.shape[0]) == 0 if len(chunk.shape) == 2 else False


def fit(chunks: Iterable, cfg: StreamConfig, *,
        ckpt_dir: str | None = None, save_every: int = 0,
        resume_from: str | None = None,
        failure_injector=None, max_retries: int = 8
        ) -> tuple[CoclusterModel, FitStats]:
    """Out-of-core fit over an iterable of row chunks (dense or BCOO).

    Rows are assigned global ids by arrival order. Returns
    ``(model, stats)``; peak resident data is one chunk + the model-sized
    accumulators (``stats`` reports both).

    Crash-consistent, resumable operation (DESIGN.md §12):

    ``ckpt_dir`` + ``save_every``
        checkpoint the ``FitState`` every ``save_every`` chunks (and at
        stream end) via ``repro.checkpoint`` — atomic, fsync'd,
        hash-manifested commits. The chunk loop runs through
        ``runtime.fault_tolerance.run_with_recovery``.
    ``resume_from``
        restore the latest committed ``FitState`` from this directory
        before consuming the stream; the already-folded chunks are drawn
        off the iterable and shape-checked against the recorded folds.
        Raises ``FileNotFoundError`` when nothing is committed there.
    ``failure_injector``
        a ``runtime.fault_tolerance.FailureInjector`` whose
        ``maybe_fail(t)`` runs after each chunk fold — a
        ``SimulatedFailure`` exercises the real restore path (state is
        rebuilt from the latest checkpoint and the lost chunks refold
        from a bounded replay buffer). Requires ``ckpt_dir``.

    Equivalence guarantee: with equal seeds and the same stream, an
    interrupted-and-resumed fit returns a bit-identical
    ``CoclusterModel`` to an uninterrupted one — every chunk step's
    randomness is ``(seed, t)`` counter-derived and the accumulator
    round-trips exactly.
    """
    if save_every < 0:
        raise ValueError(f"save_every must be >= 0, got {save_every}")
    if (ckpt_dir is None) != (save_every == 0):
        raise ValueError(
            "checkpointing needs both knobs: pass ckpt_dir AND save_every "
            f">= 1 together (got ckpt_dir={ckpt_dir!r}, "
            f"save_every={save_every})")
    recovery = ckpt_dir is not None
    if failure_injector is not None and not recovery:
        raise ValueError(
            "failure_injector without ckpt_dir/save_every cannot recover — "
            "there is no checkpoint to restore from")

    if resume_from is not None:
        fitter, start = load_fit_state(resume_from, cfg)
        logger.info("resuming fit from %s at chunk %d (%d rows folded)",
                    resume_from, start, fitter.rows_seen)
    else:
        fitter, start = StreamingCocluster(cfg), 0

    with obs.span("stream_fit", resumed=resume_from is not None,
                  resume_step=start, recovery=recovery) as root:
        it = iter(chunks)

        # draw the already-folded chunks off the stream, checking each
        # against the recorded fold — a different stream/chunking cannot
        # silently masquerade as a resume. Each skipped fold gets a trivial
        # span so the trace still shows one chunk span per non-empty chunk,
        # marked as a replay that was not re-folded.
        skipped = 0
        while skipped < start:
            try:
                chunk = next(it)
            except StopIteration:
                raise ValueError(
                    f"resume_from state has {start} chunks folded but the "
                    f"stream ended after {skipped} — resuming needs the same "
                    "stream, re-chunked identically") from None
            if _skip_empty(chunk):
                continue
            with obs.span("chunk", t=skipped, rows=int(chunk.shape[0]),
                          replayed=True, skipped=True):
                fitter.check_replayed_chunk(chunk, skipped)
            skipped += 1

        if not recovery:
            for chunk in it:
                fitter.partial_fit(chunk)
            out = fitter.finalize()
            root.set(chunks=out[1].chunks, rows_seen=out[1].rows_seen)
            return out

        cursor = _ChunkCursor(it, start=start, keep=save_every + 2)
        hi = {"max": start}  # high-water chunk step: steps below it are refolds

        def step_fn(t: int, f: StreamingCocluster) -> StreamingCocluster:
            # the cursor never buffers empty chunks, so every step folds rows
            f.partial_fit(cursor.get(t), replayed=t < hi["max"])
            hi["max"] = max(hi["max"], t + 1)
            if failure_injector is not None:
                # post-fold: the in-memory state is dirty, so recovery must
                # genuinely rebuild from the checkpoint, not shrug and retry
                failure_injector.maybe_fail(t)
            return f

        def restore_state(step: int) -> StreamingCocluster:
            if step < 0:
                # no checkpoint committed yet: from scratch (or the
                # resume point)
                if resume_from is not None:
                    f, _ = load_fit_state(resume_from, cfg)
                    return f
                return StreamingCocluster(cfg)
            f, _ = load_fit_state(ckpt_dir, cfg, step=step)
            return f

        from repro.runtime import fault_tolerance as _ft

        fitter, loop_stats = _ft.run_with_recovery(
            total_steps=None, step_fn=step_fn, state=fitter,
            ckpt_dir=ckpt_dir, save_every=save_every,
            restore_state=restore_state, max_retries=max_retries,
            start_step=start,
            save_fn=lambda _step, f: save_fit_state(ckpt_dir, f))
        if loop_stats["failures"]:
            logger.info("fit recovered from %d injected failure(s); final "
                        "chunk step %d", loop_stats["failures"],
                        loop_stats["final_step"])
        out = fitter.finalize()
        root.set(chunks=out[1].chunks, rows_seen=out[1].rows_seen,
                 failures=loop_stats["failures"])
        return out


def iter_row_chunks(matrix: np.ndarray, chunk_rows: int,
                    format: str = "dense"):
    """Yield ``(chunk_rows, N)`` row chunks of an in-memory matrix.

    Test/benchmark helper: real out-of-core callers stream chunks from
    disk or the wire. ``format='bcoo'`` converts each chunk (only the
    chunk — O(chunk nnz)) via ``data.synthetic.to_bcoo``. The yielded
    chunking is deterministic, so the same call replays the same stream
    — what ``fit(resume_from=...)`` needs to continue a killed fit.
    """
    if format not in ("dense", "bcoo"):
        raise ValueError(f"format must be 'dense' or 'bcoo', got {format!r}")
    m = matrix.shape[0]
    for start in range(0, m, chunk_rows):
        chunk = np.asarray(matrix[start: start + chunk_rows])
        if format == "bcoo":
            from repro.data.synthetic import to_bcoo

            yield to_bcoo(chunk)
        else:
            yield jnp.asarray(chunk)
