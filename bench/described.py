#!/usr/bin/env python3
"""Compile a cell's fit program for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/described.py dense_fit

For each cell, lowers the program its window drives at the cell's real
sizes (``_lamc_jit`` for one chip, the ``distributed_lamc`` step on a
described ``v5e:2x2`` for four) from shapes alone, compiles it for the
chip and prints ``memory_analysis()`` per device and ``cost_analysis()``.
A sparse configuration's operand is sized by the support it plants,
drawn on the host (O(nnz) memory; the file stores no widths). What the
chip's compiler refuses here costs no chip time. Code that asks
``jax.default_backend()`` still sees the CPU, so kernels that dispatch on
the backend compile their portable branch.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def topology(name: str = "v5e:2x2"):
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def support_summary(config: dict) -> dict:
    """``data.support_summary`` of the support that a sparse configuration
    plants (drawn here, on the host's default device: it depends on
    ``support_seed`` alone, so any ``--seed`` gives it)."""
    import data

    return data.support_summary(data.make(config, 0).a)


def sparse_operand(route: str, shape: tuple, summary: dict, sds):
    """The operand that ``route`` (``"dual_ell"`` or ``"tiled"``) builds
    for a support of ``summary``, as ``sds(shape, dtype)`` leaves."""
    import jax.numpy as jnp

    from repro.core import sparse as core_sparse

    m, n = shape
    if route == "tiled":
        from repro.kernels.spmm import BlockSparseMatrix

        g = summary["tiles_128"]
        return BlockSparseMatrix(sds((g, 128, 128), jnp.float32),
                                 sds((g,), jnp.int32), sds((g,), jnp.int32),
                                 sds((g,), jnp.int32), (m, n))
    w_r, w_c = summary["row_width"], summary["col_width"]
    return core_sparse.EllOperator(
        row_vals=sds((m, w_r), jnp.float32),
        row_cols=sds((m, w_r), jnp.int32),
        col_vals=sds((n, w_c), jnp.float32),
        col_rows=sds((n, w_c), jnp.int32))


def operand_bytes(route: str, shape: tuple, summary: dict) -> int:
    """Bytes of :func:`sparse_operand`'s arrays."""
    import jax
    import numpy as np

    op = sparse_operand(route, shape, summary, jax.ShapeDtypeStruct)
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(op))


def fit_program(config: dict, topo, chips: int):
    """``(compiled, plan)`` of the fit program of ``config`` for ``topo``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import sparse as jsparse
    from jax.sharding import AxisType, Mesh, SingleDeviceSharding

    from repro.core import LAMCConfig, lamc
    from repro.core.distributed import lamc_step_fn
    from repro.core.partition import make_plan

    cfg = LAMCConfig(**config["lamc"])
    m, n = config["rows"], config["cols"]
    density = config.get("density", 1.0)
    plan = make_plan(m, n, min_cocluster_rows=cfg.min_cocluster_rows,
                     min_cocluster_cols=cfg.min_cocluster_cols,
                     p_thresh=cfg.p_thresh, workers=chips, seed=cfg.seed,
                     k=cfg.atom_k, grid_candidates=cfg.grid_candidates,
                     svd_method=cfg.svd_method, density=density,
                     spmm_impl=cfg.spmm_impl)
    if chips > 1:
        shape, axes = config["mesh"]["shape"], tuple(config["mesh"]["axes"])
        devs = np.asarray(topo.devices[:chips]).reshape(shape)
        mesh = Mesh(devs, axes, axis_types=(AxisType.Auto,) * len(axes))
        step, in_sh, out_sh = lamc_step_fn(cfg, plan, mesh, axes)
        x = jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=in_sh)
        with in_sh.mesh:
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh).lower(x).compile()
        return compiled, plan
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    if config["format"] == "dense":
        compiled = lamc._lamc_jit.lower(sds((m, n), jnp.float32), cfg,
                                        plan).compile()
        return compiled, plan
    sup = support_summary(config)
    nnz = sup["nnz"]
    op = sparse_operand(cfg.spmm_impl, (m, n), sup, sds)
    plan = dataclasses.replace(plan, spmm_route=cfg.spmm_impl)

    def program(values, indices, op):
        a = jsparse.BCOO((values, indices), shape=(m, n), indices_sorted=True,
                         unique_indices=True)
        return lamc._lamc_jit(a, cfg, plan, op)

    compiled = jax.jit(program).lower(sds((nnz,), jnp.float32),
                                      sds((nnz, 2), jnp.int32), op).compile()
    return compiled, plan


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import spec

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topology()
    for name in (argv if argv is not None else sys.argv[1:]):
        _, w, config, _ = spec.cell(name)
        spec.apply_precision(config)
        compiled, plan = fit_program(config, topo, w["chips"])
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        print(f"{name}: plan {plan.m}x{plan.n} {plan.phi}x{plan.psi} "
              f"t_p={plan.t_p} route={plan.spmm_route}")
        print(f"  per device: argument={mem.argument_size_in_bytes} "
              f"output={mem.output_size_in_bytes} "
              f"temp={mem.temp_size_in_bytes} "
              f"generated_code={mem.generated_code_size_in_bytes}")
        print(f"  cost: flops={cost.get('flops')} "
              f"bytes_accessed={cost.get('bytes accessed')}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
