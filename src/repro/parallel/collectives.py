"""Collective helpers: exact integer reductions, ring primitives, and the
compute/comm-overlap chunked matmul used by the §Perf experiments.

``psum`` of int32 is associative -> bitwise reproducible for any mesh
shape/reduction order. That exactness is what upgrades the Ozaki scheme's
reproducibility story to an *elasticity invariant* (DESIGN.md §2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def psum_exact_int32(x: jax.Array, axis: str) -> jax.Array:
    """Integer all-reduce; order-independent by associativity."""
    assert jnp.issubdtype(x.dtype, jnp.integer), x.dtype
    return jax.lax.psum(x, axis)


def ring_all_gather(x: jax.Array, axis: str, axis_size: int,
                    hop: int = 1) -> jax.Array:
    """All-gather along ``axis`` built from collective_permutes (one hop
    per step) — the schedule that overlaps with per-step compute on TPU
    ICI rings. x: (chunk, ...) -> (axis_size * chunk, ...).

    ``hop`` is the ring stride: step j forwards every block one more
    ``hop`` around the axis, so after j steps device ``i`` holds the
    block that originated at ``(i - j * hop) % axis_size``. A
    non-contiguous ring (``hop > 1`` — e.g. skipping over devices that
    share a host link) visits every device iff
    ``gcd(hop, axis_size) == 1``. The source-order restore below indexes
    by the ACTUAL per-step source, not by position — the hop-1 shortcut
    ``src = idx - arange`` silently shuffled blocks for any other
    permutation.
    """
    if axis_size > 1 and math.gcd(hop % axis_size, axis_size) != 1:
        raise ValueError(
            f"hop={hop} does not generate the ring for axis_size="
            f"{axis_size} (gcd != 1): some source blocks would never "
            f"arrive")
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + hop) % axis_size) for i in range(axis_size)]

    def body(carry, _):
        block = carry
        block = jax.lax.ppermute(block, axis, perm)
        return block, block

    _, blocks = jax.lax.scan(body, x, None, length=axis_size - 1)
    all_blocks = jnp.concatenate([x[None], blocks], axis=0)
    # position j holds the block from source (idx - j*hop) % axis_size;
    # argsort over the true source ids restores global order for ANY hop
    src = (idx - hop * jnp.arange(axis_size)) % axis_size
    order = jnp.argsort(src)
    all_blocks = jnp.take(all_blocks, order, axis=0)
    return all_blocks.reshape((-1,) + x.shape[1:])


def chunked_matmul_psum(x: jax.Array, w: jax.Array, axis: str,
                        num_chunks: int) -> jax.Array:
    """k-sharded matmul with the reduction interleaved over n-chunks.

    Inside shard_map: x (m, k_local), w (k_local, n). Splitting n into
    chunks and issuing one psum per chunk lets chunk i's all-reduce
    overlap chunk i+1's matmul (XLA schedules the independent collective
    concurrently). Beyond-paper trick recorded in §Perf.
    """
    n = w.shape[1]
    chunk = n // num_chunks
    outs = []
    for i in range(num_chunks):
        part = x @ w[:, i * chunk:(i + 1) * chunk]
        outs.append(jax.lax.psum(part, axis))
    rest = n - chunk * num_chunks
    if rest:
        outs.append(jax.lax.psum(x @ w[:, n - rest:], axis))
    return jnp.concatenate(outs, axis=1)


def reduce_scatter_sum(x: jax.Array, axis: str, axis_size: int = None,
                       scatter_dim: int = 0) -> jax.Array:
    """psum_scatter wrapper (tiled=True keeps the dim, divided).

    Exact for integer ``x`` (associative adds), so the Ozaki k-shard
    schedules reduce their int32 pair partials through this — half the
    link bytes of an all-reduce, bitwise reproducible either way.
    ``axis_size`` is advisory (the sharded dim must divide by it).
    """
    return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                                tiled=True)
