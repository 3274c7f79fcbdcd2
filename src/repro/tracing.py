"""Stage scopes, host spans and counters of the Ozaki pipeline.

Three kinds of record, all on the profiler's clock or in memory, none
behind a switch:

* **Stage scopes** (``scope``, over ``jax.named_scope``) name the stage
  that issued each operation. They change only the ``op_name`` metadata
  of the compiled program, which a TPU profiler trace carries as each
  device operation's ``tf_op``; the computation, its fusions and its
  results are the same with or without them. A trace reduction takes an
  operation's stage as the innermost ``ozaki.*`` component of that name.
* **Host spans** (``span``, over ``jax.profiler.TraceAnnotation``) mark
  the front door and plan resolution on the host. Under ``jax.jit`` they
  run once per trace, so a retrace inside a traced window shows under
  their names; an eager caller gets one span per call. With no profiler
  running a span costs next to nothing.
* **Counters** (``count``, ``counters``) count the work as it is issued,
  per process: front-door traces, plans resolved, GEMM launches and the
  slice pairs those launches cover. ``grid_steps`` counts the grid steps
  of the epilogue GEMM launches, each of which costs the kernel a fixed
  overhead beside its MXU work.
"""
from __future__ import annotations

import functools
import threading

import jax

# the front door, outermost scope and host span
MATMUL = "repro.matmul"
# plan resolution (accuracy target, split count, executor), host span
PLAN = "repro.plan"
# the front door's operand transposes and batch fold and unfold
LAYOUT = "ozaki.layout"
# per-row exponents of the operands and their outer sum, e_base
EXPONENTS = "ozaki.exponents"
# the slice extraction (split kernels) and its padding
SPLIT = "ozaki.split"
# accumulator zero-fill and every GEMM or GEMM+epilogue launch
GEMM = "ozaki.gemm"
# the final power-of-two scaling of the output planes
SCALE_OUT = "ozaki.scale_out"

STAGES = (LAYOUT, EXPONENTS, SPLIT, GEMM, SCALE_OUT)
SCOPES = (MATMUL,) + STAGES
SPANS = (MATMUL, PLAN)
COUNTERS = ("matmul_traces", "plans", "gemm_launches", "gemm_pairs")
# (m/bm) (n/bn) pairs (k/bk), times the batch, summed over the epilogue
# GEMM launches. Read by ``grid_steps()``, apart from ``counters()``:
# the chip benchmark's tests hold the detail run's counter deltas to
# exactly the four keys above.
GRID_STEPS = "gemm_grid_steps"

_lock = threading.Lock()
_counts = dict.fromkeys(COUNTERS + (GRID_STEPS,), 0)


def scope(name: str):
    """``jax.named_scope`` for one name of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; expected one of {SCOPES}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: run the function under ``scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def span(name: str):
    """A host span on the profiler's clock, for one name of ``SPANS``."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; expected one of {SPANS}")
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (one of ``COUNTERS``)."""
    if name not in COUNTERS:
        raise ValueError(f"unknown counter {name!r}; expected one of "
                         f"{COUNTERS}")
    with _lock:
        _counts[name] += n


def gemm_launch(pairs: int, launches: int = 1, grid_steps: int = 0) -> None:
    """Count ``launches`` GEMM launches covering ``pairs`` slice pairs in
    ``grid_steps`` kernel grid steps."""
    with _lock:
        _counts["gemm_launches"] += launches
        _counts["gemm_pairs"] += pairs
        _counts[GRID_STEPS] += grid_steps


def counters() -> dict:
    """A snapshot of every counter of ``COUNTERS``."""
    with _lock:
        return {k: _counts[k] for k in COUNTERS}


def grid_steps() -> int:
    """The grid steps the epilogue GEMM launches have issued."""
    with _lock:
        return _counts[GRID_STEPS]
