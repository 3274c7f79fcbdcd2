"""Operations and bytes of one call, stage by stage, from its shapes.

All counts are lower bounds on the work a stage must do, so a share of
a roofline computed from them cannot pass 100% unless the time leaves
out part of the work:

* ``fp64_flops``: the FP64 product's own work, ``2 m n k``, whatever the
  route (a route that needs fewer int8 GEMMs shows as a higher rate).
* ``gemm_ops``: the int8 slice-pair GEMMs, ``pairs * 2 m n k_chip`` with
  the kept pairs of the plan the run resolved and each chip's share of k.
* ``gemm_bytes``: each slice stack read once (``splits * (m + n) *
  k_chip`` int8 bytes) and the stage's output written once: the df32
  accumulator pair (8 bytes an entry) where GEMM and accumulation are
  one kernel, or one int32 plane per anti-diagonal group where the
  group products are materialised (the k-shard's XLA dots).
* ``split_bytes``: both operands' words read once and their slices
  written once.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Plan:
    """What the run resolved for one product: split count, kept slice
    pairs, anti-diagonal groups."""

    splits: int
    pairs: int
    groups: int


@dataclasses.dataclass(frozen=True)
class CallCounts:
    fp64_flops: float
    gemm_ops: float
    gemm_bytes: float
    split_bytes: float


def call_counts(m: int, n: int, k: int, plan: Plan, *, chips: int = 1,
                word_bytes: int = 8, output: str = "df32") -> CallCounts:
    """Counts of one ``(m, k) @ (k, n)`` call (batches folded into m).

    ``chips``: the k-shards; counts are per chip except ``fp64_flops``,
    which is the whole product's. ``word_bytes``: bytes of one operand
    entry (8 for a DW pair, 4 for float32). ``output``: ``"df32"`` or
    ``"int32_groups"``."""
    if k % chips:
        raise ValueError(f"k={k} does not split over {chips} chips")
    kc = k // chips
    if output == "df32":
        out_bytes = 8 * m * n
    elif output == "int32_groups":
        out_bytes = 4 * plan.groups * m * n
    else:
        raise ValueError(f"unknown output kind {output!r}")
    return CallCounts(
        fp64_flops=2.0 * m * n * k,
        gemm_ops=plan.pairs * 2.0 * m * n * kc,
        gemm_bytes=float(plan.splits * (m + n) * kc + out_bytes),
        split_bytes=float((word_bytes + plan.splits) * (m + n) * kc))


def roofline_seconds(ops: float, nbytes: float, peaks: dict,
                     ops_key: str = "int8_ops_per_s") -> tuple[float, str]:
    """The least time the chip needs, and which bound sets it."""
    t_ops = ops / peaks[ops_key]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
