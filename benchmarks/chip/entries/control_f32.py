"""The correctness control: the plain product computed in float32, put
in the program's place.

The configurations state float64 accuracy; float32 is the nearest
precision below it. Operands are rounded to float32 (a DW operand keeps
its ``hi`` word), multiplied by XLA's float32 ``dot`` at ``HIGHEST``
precision, and returned as ``DW(c, 0)``. On a configuration with a
``mesh`` the operands keep the program's shardings and every chip gets
the whole product, as on the k-shard path. ``correct`` has to read false
for this entry; the benchmark's own runs never use it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def build(cell, devices):
    from repro.core.xmath import DW

    from benchmarks.chip.counts import Plan
    from benchmarks.chip.harness import Route, copy_sampler

    c = cell.config
    kind = c["operands"]["kind"]

    def word(x):
        return x[0] if kind == "dw" else x

    shardings, out_sharding = None, None
    if "mesh" in c:
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        axes = tuple(c["mesh_axes"])
        mesh = Mesh(np.asarray(devices).reshape(c["mesh"]), axes,
                    axis_types=(AxisType.Auto,) * len(axes))
        shardings = {}
        for a, b in cell.traffic["calls"]:
            shardings[a] = NamedSharding(mesh, P(None, c["axis"]))
            shardings[b] = NamedSharding(mesh, P(c["axis"], None))
        out_sharding = NamedSharding(mesh, P())

    def product(a, b):
        a = a.reshape(-1, a.shape[-1])
        out = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        return DW(out, jnp.zeros_like(out))

    gemm = jax.jit(product, out_shardings=out_sharding)

    index, sample = copy_sampler(devices)

    return Route(
        call=lambda a, b: gemm(word(a), word(b)),
        lower=lambda a, b: gemm.lower(word(a), word(b)), sample=sample,
        index=index,
        plan=lambda m, n, k: Plan(splits=1, pairs=1, groups=1),
        shardings=shardings, copies=len(devices), output="df32",
        word_bytes=4)
