"""The k-sharded Ozaki GEMM over a mesh of chips:
``distributed_ozaki_matmul(a, b, mesh, OzakiConfig(num_splits, accum),
axis, schedule)``, jitted whole.

Config keys: ``mesh`` (shape), ``mesh_axes``, ``axis`` (the mesh axis k
is sharded over), ``schedule``, ``num_splits``, ``accum``. Operands are
float32; A is sharded over its columns and B over its rows, each chip
holding its share of k. With the ``psum`` schedule every chip ends with
the whole product, and every chip's copy is checked.
"""
from __future__ import annotations

import jax
import numpy as np


def build(cell, devices):
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.ozaki import OzakiConfig, resolve_accuracy_config
    from repro.parallel.ozaki_shard import distributed_ozaki_matmul

    from benchmarks.chip.counts import Plan
    from benchmarks.chip.harness import Route, copy_sampler

    c = cell.config
    axes = tuple(c["mesh_axes"])
    mesh = Mesh(np.asarray(devices).reshape(c["mesh"]), axes,
                axis_types=(AxisType.Auto,) * len(axes))
    cfg = OzakiConfig(num_splits=c["num_splits"], accum=c["accum"])
    axis, schedule = c["axis"], c["schedule"]
    gemm = jax.jit(lambda a, b: distributed_ozaki_matmul(
        a, b, mesh, cfg, axis=axis, schedule=schedule))
    lhs = NamedSharding(mesh, P(None, axis))
    rhs = NamedSharding(mesh, P(axis, None))
    shardings = {}
    for a, b in cell.traffic["calls"]:
        shardings[a], shardings[b] = lhs, rhs

    index, sample = copy_sampler(devices)

    def plan(m, n, k):
        r = resolve_accuracy_config(cfg, k)
        return Plan(splits=r.num_splits, pairs=r.num_gemms,
                    groups=len(r.diagonals()))

    return Route(call=gemm, lower=gemm.lower, sample=sample, index=index, plan=plan,
                 shardings=shardings, copies=len(devices),
                 output="int32_groups", word_bytes=4)
