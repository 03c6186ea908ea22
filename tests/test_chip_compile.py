"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode on the CPU accepts block shapes and VMEM footprints that
the chip's compiler refuses, so every kernel the fit, sparse-fit and
serving paths dispatch to is compiled here for one chip of a described
``v5e:2x2`` topology, at the shapes ``chip_smoke.py`` runs. Nothing
executes: a pass says the chip's compiler accepts the kernel, not that
it is fast or right (the interpret-mode tests check the numbers).

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and the test workers
all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import vmem
from repro.kernels.bipartite_normalize import scale_apply_pallas
from repro.kernels.kmeans_assign import (
    cosine_assign_pallas,
    cosine_topk_pallas,
    kmeans_assign_pallas,
)
from repro.kernels.kmeans_update import kmeans_update_pallas
from repro.kernels.spmm import spmm_ata_pallas, spmm_pallas, spmm_t_pallas

# Dense fit of chip_smoke.py: 65,536 x 16,384 as one block, k = d = 16.
# The atom's k-means runs on Z = [row_embed; col_embed], (M + N) points
# of l = 16.bit_length() = 5 coordinates, padded to (tile_p, 128) / 8.
_KM_POINTS, _KM_K = 65536 + 16384, 16
# Serving: 64-row service batches (and a 4,096-row bulk ``assign_rows``)
# over q = 64 anchor coordinates, 16 clusters.
_SERVE_BATCH, _BULK_BATCH, _SERVE_Q, _SERVE_K = 64, 4096, 64, 16
# Sparse fit: rcv1_proxy, 100,000 x 5,000 at 5%, 128 x 128 tiles (every
# tile occupied at that density); the sketch is one bn = 128 stripe.
_SP_ROWS, _SP_COLS, _TILE = 100_000, 5_000, 128


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kmeans_args(s):
    p = _ceil(_KM_POINTS, 512)
    return s((p, 128)), s((_KM_K, 128))


def _serve_args(s, batch=_SERVE_BATCH):
    return s((_ceil(batch, 512), _ceil(_SERVE_Q, 128))), s((_SERVE_K, 128))


def _tiled_args(s, n_tr, n_tc, *, scaled, rhs_rows):
    g = n_tr * n_tc
    args = dict(
        block_rows=s((g,), jnp.int32), block_cols=s((g,), jnp.int32),
        blocks=s((g, _TILE, _TILE)), rhs=s((rhs_rows, _TILE)))
    if scaled:
        args["row_scale"] = s((n_tr, _TILE))
        args["col_scale"] = s((n_tc, _TILE))
    return args


def _spmm(s, scaled):
    n_tr, n_tc = -(-_SP_ROWS // _TILE), -(-_SP_COLS // _TILE)
    a = _tiled_args(s, n_tr, n_tc, scaled=scaled, rhs_rows=n_tc * _TILE)
    fn = lambda rows, cols, blocks, b, *sc: spmm_pallas(
        rows, cols, blocks, b, m_out=n_tr * _TILE, bn=_TILE,
        row_scale=sc[0] if sc else None, col_scale=sc[1] if sc else None)
    return fn, [a["block_rows"], a["block_cols"], a["blocks"], a["rhs"],
                *([a["row_scale"], a["col_scale"]] if scaled else [])]


def _spmm_t(s, scaled):
    n_tr, n_tc = -(-_SP_ROWS // _TILE), -(-_SP_COLS // _TILE)
    a = _tiled_args(s, n_tr, n_tc, scaled=scaled, rhs_rows=n_tr * _TILE)
    fn = lambda rows, cols, order, blocks, b, *sc: spmm_t_pallas(
        rows, cols, order, blocks, b, k_out=n_tc * _TILE, bn=_TILE,
        row_scale=sc[0] if sc else None, col_scale=sc[1] if sc else None)
    return fn, [a["block_rows"], a["block_cols"], a["block_cols"],
                a["blocks"], a["rhs"],
                *([a["row_scale"], a["col_scale"]] if scaled else [])]


def _ata_edge_rows(n_tc: int, scaled: bool) -> int:
    """Most tile-rows the VMEM estimator admits for the fused Gram step at
    ``n_tc`` tile-cols: the kernel ``ops.spmm_ata`` would still launch.
    The sparse fit's 782 tile-rows are past it (ops falls back to two
    ``spmm_tiled`` launches), so the fused kernel is compiled at the
    estimator's edge instead — where a wrong estimate would show."""
    budget = vmem.vmem_budget_bytes("tpu")
    n_tr = 1
    while vmem.ata_resident_bytes(n_tr + 1, n_tc, _TILE, _TILE, _TILE,
                                  with_gram=True, scaled=scaled) <= budget:
        n_tr += 1
    return n_tr


def _spmm_ata(s, scaled):
    n_tc = -(-_SP_COLS // _TILE)
    n_tr = _ata_edge_rows(n_tc, scaled)
    a = _tiled_args(s, n_tr, n_tc, scaled=scaled, rhs_rows=n_tc * _TILE)
    fn = lambda rows, cols, blocks, x, *sc: spmm_ata_pallas(
        rows, cols, blocks, x, m_pad=n_tr * _TILE, bn=_TILE, with_gram=True,
        row_scale=sc[0] if sc else None, col_scale=sc[1] if sc else None)
    return fn, [a["block_rows"], a["block_cols"], a["blocks"], a["rhs"],
                *([a["row_scale"], a["col_scale"]] if scaled else [])]


_CASES = {
    "kmeans_update": lambda s: (
        lambda x, c, w: kmeans_update_pallas(x, c, w),
        [*_kmeans_args(s), s((1, _ceil(_KM_POINTS, 512)))]),
    "kmeans_assign": lambda s: (
        lambda x, c: kmeans_assign_pallas(x, c), list(_kmeans_args(s))),
    "cosine_assign": lambda s: (
        lambda x, sig: cosine_assign_pallas(x, sig, k_valid=_SERVE_K),
        list(_serve_args(s))),
    "cosine_assign_bulk": lambda s: (
        lambda x, sig: cosine_assign_pallas(x, sig, k_valid=_SERVE_K),
        list(_serve_args(s, _BULK_BATCH))),
    "cosine_topk": lambda s: (
        lambda x, sig: cosine_topk_pallas(x, sig, k_valid=_SERVE_K, k_top=4),
        list(_serve_args(s))),
    "cosine_topk_bulk": lambda s: (
        lambda x, sig: cosine_topk_pallas(x, sig, k_valid=_SERVE_K, k_top=4),
        list(_serve_args(s, _BULK_BATCH))),
    "spmm": lambda s: _spmm(s, scaled=False),
    "spmm_scaled": lambda s: _spmm(s, scaled=True),
    "spmm_t": lambda s: _spmm_t(s, scaled=False),
    "spmm_t_scaled": lambda s: _spmm_t(s, scaled=True),
    "spmm_ata_gram": lambda s: _spmm_ata(s, scaled=False),
    "spmm_ata_gram_scaled": lambda s: _spmm_ata(s, scaled=True),
    # off the main path (no core/ caller), compiled at its default tiles
    "scale_apply": lambda s: (
        lambda a, d1, d2: scale_apply_pallas(a, d1, d2),
        [s((4096, 4096)), s((1, 4096)), s((1, 4096))]),
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    shape = lambda shp, dt=jnp.float32: jax.ShapeDtypeStruct(
        shp, dt, sharding=one_chip)
    fn, args = _CASES[name](shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
