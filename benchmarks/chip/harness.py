"""Run one benchmark cell once: set up, measure a window of whole calls,
check what the window produced against the plain reference, report.

Everything that belongs to one configuration, traffic mix, timed entry
or metric sits in a file of its own, found by name:

* ``BENCHMARK.json``          — the cells (configuration + traffic), the
  metrics, which cells report which metric;
* ``configs/<config>.json``   — the deployment: route, operand kind and
  distribution, the timed entry's name (named by ``file`` in
  ``BENCHMARK.json``);
* ``traffic/<traffic>.json``  — the operands by name and shape, the cycle
  of products the caller asks for, and how much of each output is
  checked;
* ``limits/<cell>.json``      — each number compared, with its limit and
  the readings it was set from;
* ``entries/<entry>.py``      — builds the timed call of a route;
* ``metrics/<metric>.py``     — reads one metric from a finished run.

Each call is closed loop with one caller: the next call starts after the
previous one has ended in ``block_until_ready``. The window runs from the
first call's start to the last call's end, and only whole calls count.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Callable, Optional

import numpy as np

CHIP_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".chipbench")

# sample index sets drawn per product of the cycle; call i of a product
# checks with set i mod POOL, so the checked blocks vary from call to call
POOL = 4

# what a compile inside the window would record
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


# ----------------------------------------------------------------------------
# The benchmark's files
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict


class Bench:
    """``BENCHMARK.json`` and the files it names. ``dirs`` are searched
    in order for ``traffic/``, ``limits/``, ``entries/`` and ``metrics/``
    files, so a test can add a cell in a directory of its own."""

    def __init__(self, spec_path: Optional[str] = None,
                 dirs: tuple[str, ...] = (CHIP_DIR,)):
        self.spec_path = spec_path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = os.path.dirname(os.path.abspath(self.spec_path))
        with open(self.spec_path) as f:
            self.spec = json.load(f)
        self.dirs = tuple(dirs)
        self._modules: dict[str, Any] = {}
        self._routes: dict = {}

    def find(self, sub: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, sub, name + ext)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(f"no {sub}/{name}{ext} in {self.dirs}")

    def module(self, sub: str, name: str):
        path = self.find(sub, name, ".py")
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{sub}_{name.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def _json(self, sub: str, name: str) -> dict:
        with open(self.find(sub, name, ".json")) as f:
            return json.load(f)

    def cell(self, name: str) -> Cell:
        try:
            w = next(w for w in self.spec["workloads"] if w["name"] == name)
        except StopIteration:
            known = [w["name"] for w in self.spec["workloads"]]
            raise KeyError(f"no workload {name!r}; known: {known}") from None
        c = next(c for c in self.spec["configs"] if c["name"] == w["config"])
        with open(os.path.join(self.root, c["file"])) as f:
            config = json.load(f)
        return Cell(name=name, chips=int(w["chips"]), config=config,
                    traffic=self._json("traffic", w["traffic"]),
                    limits=self._json("limits", name))

    def route(self, cell: Cell, entry: str, devices) -> "Route":
        """The timed call ``entries/<entry>.py`` builds for a cell, built
        once per process so that later runs reuse its compiled programs."""
        key = (cell.name, entry, tuple(d.id for d in devices))
        if key not in self._routes:
            self._routes[key] = self.module("entries", entry).build(
                cell, devices)
        return self._routes[key]

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The metrics a cell reports: end-to-end without a trace,
        per-layer with one."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        return self.module("metrics", metric)

    def stages(self) -> dict:
        """``match(name, opcode)`` of every per-layer reader that has
        one: the operations each such metric claims. A later stage metric
        is added by its file and its ``BENCHMARK.json`` entry alone."""
        out = {}
        for m in self.spec["per_layer"]:
            match = getattr(self.reader(m["name"]), "match", None)
            if match is not None:
                out[m["name"]] = match
        return out


# ----------------------------------------------------------------------------
# A run's record, which the metric readers read
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    peaks: Optional[dict]            # peaks.peaks_for(device_kind) on a chip
    setup_s: float
    call_times: list                 # seconds of each call in the window
    window_s: float
    calls: list                      # counts.CallCounts of each call
    memory_peak_bytes: int
    scaled_err: float                # largest over the checked entries
    rms_scaled_err: float            # root mean square over them
    trace: Any = None                # trace.Reduced of a --trace 1 run
    # per-layer metric name -> its reader's ``match(name, opcode)``, for
    # every per-layer reader in BENCHMARK.json that classifies operations
    stages: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Route:
    """What an entry builds: the timed call and how to read its output.

    ``call(lhs, rhs)`` is the timed program and ``lower(lhs, rhs)`` the
    same program lowered for those operands (its compiled memory
    analysis gives the temporaries); ``sample(out, idx)`` returns one
    ``(hi, lo)`` block per copy of the output the chips hold;
    ``index(rows, cols)`` places a sample's indices where ``sample``
    needs them; ``plan(m, n, k)`` is the ``counts.Plan`` the program
    resolves for that product."""

    call: Callable
    lower: Callable
    sample: Callable
    index: Callable
    plan: Callable
    shardings: Optional[dict]        # operand name -> sharding, or None
    copies: int                      # copies of the output to check
    output: str                      # counts.call_counts' output kind
    word_bytes: int


class Held:
    """The check's samples held through a window: the newest call's, and
    a reservoir (Algorithm R, seeded) of ``calls - 1`` of the calls
    before it, so that at most ``calls`` stay on the device however many
    calls the window runs. The check reads them all once the window has
    closed: a uniform sample of the earlier calls, and the last call.

    ``add`` is host bookkeeping alone: a sample the reservoir does not
    keep is dropped at once, and the device frees it."""

    def __init__(self, calls: int, seed: int):
        self.slots = calls - 1
        self.rng = np.random.default_rng([seed & (2 ** 63 - 1), 2])
        self.reservoir: list = []        # (call, sample) of earlier calls
        self.newest: Optional[tuple] = None
        self.count = 0

    def add(self, sample) -> None:
        """Hold call ``count``'s sample as the newest, and offer the one
        it follows, call ``count - 1``, to the reservoir."""
        i, offered = self.count, self.newest
        if offered is not None:
            if len(self.reservoir) < self.slots:
                self.reservoir.append(offered)
            else:
                r = int(self.rng.integers(0, i))
                if r < self.slots:
                    self.reservoir[r] = offered
        self.newest = (i, sample)
        self.count += 1

    def picked(self) -> list:
        """``(call, sample)`` of every held call, in call order."""
        return sorted(self.reservoir + [self.newest], key=lambda p: p[0])


def copy_sampler(devices):
    """``(index, sample)`` for a route whose output has a copy on each of
    ``devices``: ``index(rows, cols)`` places a sample's indices on every
    chip, ``sample(out, idx)`` reads the ``(hi, lo)`` block of each chip's
    copy on that chip (a batch folded into rows)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def take(hi, lo, rows, cols):
        hi = hi.reshape(-1, hi.shape[-1])
        lo = lo.reshape(-1, lo.shape[-1])
        return hi[rows][:, cols], lo[rows][:, cols]

    def index(rows, cols):
        return [(jax.device_put(jnp.asarray(rows, jnp.int32), d),
                 jax.device_put(jnp.asarray(cols, jnp.int32), d))
                for d in devices]

    def sample(out, idx):
        his = {s.device: s.data for s in out.hi.addressable_shards}
        los = {s.device: s.data for s in out.lo.addressable_shards}
        return [take(his[d], los[d], *idx[q])
                for q, d in enumerate(devices) if d in his]

    return index, sample


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``$JAX_COMPILATION_CACHE_DIR`` wins where it is set), holding every
    program however quick its compile, so that only a checkout's first
    run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def chips_for(cell: Cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX's platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, "
                     f"found {len(devs)}")
    return devs[:cell.chips]


def _shape_of(traffic: dict, name: str) -> tuple:
    return tuple(traffic["operands"][name])


def _product(traffic: dict, lhs: str, rhs: str) -> tuple[int, int, int]:
    """(m, n, k) of one call, a batch folded into m."""
    a, b = _shape_of(traffic, lhs), _shape_of(traffic, rhs)
    return int(np.prod(a[:-1])), int(b[-1]), int(a[-1])


# ----------------------------------------------------------------------------
# The run
# ----------------------------------------------------------------------------

def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool = False, *, require_tpu: bool = True,
             entry: Optional[str] = None, wrap: Optional[Callable] = None,
             t_start: Optional[float] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    The window lasts ``seconds`` and at least as many calls as the cell
    checks. ``entry`` replaces the configuration's timed entry (the
    control runs ``control_f32``); ``wrap`` replaces the timed call by
    ``wrap(call)`` (the fault tests break the timed path with it);
    ``require_tpu=False`` lets a test run on the CPU. ``t_start`` is the
    process's start on ``perf_counter``'s clock, from which ``setup_s``
    is counted."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from . import counts as counts_mod
    from .operands import make_operands
    from .peaks import peaks_for

    jax.config.update("jax_enable_x64", True)
    cell = bench.cell(name)
    devs = chips_for(cell, require_tpu)
    kind = devs[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None
    setup = {"init_s": time.perf_counter() - t_start}

    route = bench.route(cell, entry or cell.config["entry"], devs)
    call = wrap(route.call) if wrap is not None else route.call
    traffic = cell.traffic
    names = list(traffic["operands"])
    t = time.perf_counter()
    opnd = cell.config["operands"]
    arrays = make_operands(
        seed, [_shape_of(traffic, n) for n in names], opnd["phi"],
        opnd["kind"],
        None if route.shardings is None else [route.shardings[n]
                                              for n in names])
    ops = dict(zip(names, arrays))
    setup["operands_s"] = time.perf_counter() - t

    cycle = [tuple(c) for c in traffic["calls"]]
    products = [_product(traffic, *c) for c in cycle]
    call_counts = [counts_mod.call_counts(*p, route.plan(*p),
                                          chips=cell.chips,
                                          word_bytes=route.word_bytes,
                                          output=route.output)
                   for p in products]

    # POOL sets of sample indices per product of the cycle, drawn from the
    # seed; call i of a product samples with set i mod POOL
    chk = traffic["check"]
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 1])
    host_idx, dev_idx = [], []
    for m, n, _ in products:
        sets = [(np.sort(rng.choice(m, chk["rows"], replace=False)),
                 np.sort(rng.choice(n, chk["cols"], replace=False)))
                for _ in range(POOL)]
        host_idx.append(sets)
        dev_idx.append([route.index(r, c) for r, c in sets])

    # warm-up: the first call compiles (or loads from the cache) and runs
    # once with its sample; each shape pair of the cycle that it did not
    # cover then runs once more, with its sample
    shapes = {}
    for j, (lhs, rhs) in enumerate(cycle):
        shapes.setdefault((_shape_of(traffic, lhs), _shape_of(traffic, rhs)),
                          j)
    warm = list(shapes.values())
    took = []
    for j in warm:
        t = time.perf_counter()
        lhs, rhs = cycle[j]
        out = call(ops[lhs], ops[rhs])
        jax.block_until_ready((out, route.sample(out, dev_idx[j][0])))
        del out
        took.append(time.perf_counter() - t)
    setup["first_call_s"], setup["warmup_s"] = took[0], sum(took[1:])
    setup_s = time.perf_counter() - t_start

    compiles = []

    def on_event(event, duration, **_):
        if event in _COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    window = float(seconds)
    trace_dir = None
    if trace:
        window = min(window, float(traffic.get("trace_seconds", window)))
        trace_dir = os.path.join(WORK_DIR, "trace", name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)

    times, starts, which = [], [], []
    min_calls = int(chk["calls"])
    held = Held(min_calls, seed)
    i = 0
    # Python's cyclic collector off in the window, as ``timeit`` does: on
    # a one-chip machine its passes stalled calls by 0.1-2.3 s, 1-5 times
    # in 30 s, and never with it off
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        deadline = t0 + window
        while True:
            j = i % len(cycle)
            lhs, rhs = cycle[j]
            s = (i // len(cycle)) % POOL
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("call"):
                out = call(ops[lhs], ops[rhs])
            # the sample for the check is queued behind the call; those
            # held are read once the window has closed
            with jax.profiler.TraceAnnotation("check"):
                sample = route.sample(out, dev_idx[j][s])
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready((out, sample))
            t2 = time.perf_counter()
            times.append(t2 - t1)
            starts.append(t1)
            held.add(sample)
            which.append((j, s))
            del out, sample
            i += 1
            if t2 >= deadline and i >= min_calls:
                break
        window_s = t2 - t0
    finally:
        gc.enable()
    if trace:
        jax.profiler.stop_trace()
    jax.monitoring.unregister_event_duration_listener(on_event)

    in_use = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in devs)
    t = time.perf_counter()
    temps = _temp_bytes(route, ops, [cycle[j] for j in warm])
    analysis_s = time.perf_counter() - t
    memory_peak = in_use + temps
    pulled = _pull(ops, cycle, host_idx, held.picked(), which)
    del ops, arrays, held               # the program's state, freed
    checks, rms = _compare(cell, route.copies, *pulled)
    reduced = None
    if trace:
        from .trace import reduce_trace
        reduced = reduce_trace(trace_dir, devs)
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(peaks=peaks, setup_s=setup_s, call_times=times,
              window_s=window_s, calls=[call_counts[j] for j, _ in which],
              memory_peak_bytes=memory_peak,
              scaled_err=checks["scaled_err"]["value"],
              rms_scaled_err=rms, trace=reduced, stages=bench.stages())
    metrics = {}
    for m in bench.metrics(name, trace):
        value = bench.reader(m["name"]).read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in cell {name}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(times),
              "failed": checks["failed_calls"]["value"], "metrics": metrics,
              "device": device, "setup": setup,
              "memory": {"peak_bytes_in_use": in_use, "temp_bytes": temps,
                         "analysis_s": analysis_s},
              "compiles_in_window": len(compiles),
              "calls_checked": len(pulled[0]),
              "stalls": _stalls(times, starts)}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = checks
    return result


def _temp_bytes(route: Route, ops: dict, products) -> int:
    """The largest temporary space of the timed programs, one per shape
    pair of the cycle, from their compiled memory analysis. On a TPU v5e
    ``peak_bytes_in_use`` counts the arguments and results a program
    holds but not its temporaries (the slice stacks), so the peak a
    product needs is the two together."""
    return max(int(route.lower(ops[lhs], ops[rhs]).compile()
                   .memory_analysis().temp_size_in_bytes)
               for lhs, rhs in products)


def _stalls(times: list, starts: list) -> dict:
    """Where a window lost time: its slowest calls, and the host's time
    between one call's end and the next call's start."""
    between = [b - (a + t) for a, t, b in zip(starts, times, starts[1:])]
    slow = sorted(range(len(times)), key=lambda i: -times[i])[:3]
    return {"call_median_ms": float(np.median(times)) * 1e3,
            "slowest_calls_ms": [[i, times[i] * 1e3] for i in slow],
            "between_max_ms": max(between, default=0.0) * 1e3,
            "between_total_s": sum(between)}


def _pull(ops: dict, cycle, host_idx, picked, which):
    """Bring the held calls (``Held.picked()``) to the host: for each,
    its operand rows and columns and every copy of its output block."""
    import jax

    @jax.jit
    def take_rows(x, rows):
        return jax.tree.map(lambda v: v.reshape(-1, v.shape[-1])[rows], x)

    @jax.jit
    def take_cols(x, cols):
        return jax.tree.map(lambda v: v[:, cols], x)

    def host_f64(x):
        if isinstance(x, (tuple, list)):
            return sum(np.asarray(v).astype(np.float64) for v in x)
        return np.asarray(x).astype(np.float64)

    a_blocks, b_blocks, c_blocks = [], [], []
    for i, sample in picked:
        j, s = which[i]
        rows, cols = host_idx[j][s]
        lhs, rhs = cycle[j]
        a_blocks.append(host_f64(take_rows(ops[lhs], rows)))
        b_blocks.append(host_f64(take_cols(ops[rhs], cols)))
        c_blocks.append([np.asarray(hi).astype(np.float64)
                         + np.asarray(lo).astype(np.float64)
                         for hi, lo in sample])
    return a_blocks, b_blocks, c_blocks


def _compare(cell: Cell, copies: int, a_blocks, b_blocks,
             c_blocks) -> tuple[dict, float]:
    """Each pulled output block, every copy, against the double-double
    reference of its operand rows and columns. Returns the checks, each
    number with its limit, and the root mean square of the scaled error
    over every checked entry."""
    from .reference import dd_matmul, scaled_errors

    held = min(len(c) for c in c_blocks)
    nonfinite = sum(int(np.sum(~np.isfinite(x))) for c in c_blocks for x in c)
    per_call = [0.0] * len(c_blocks)
    squares, entries = 0.0, 0
    groups: dict = {}                # one reference pass per block shape
    for q, (a, b) in enumerate(zip(a_blocks, b_blocks)):
        groups.setdefault((a.shape, b.shape), []).append(q)
    for qs in groups.values():
        a_all = np.stack([a_blocks[q] for q in qs])
        b_all = np.stack([b_blocks[q] for q in qs])
        ref_hi, ref_lo = dd_matmul(a_all, b_all)
        for g, q in enumerate(qs):
            for x in c_blocks[q]:
                err = scaled_errors(x, ref_hi[g], ref_lo[g], a_all[g],
                                    b_all[g])
                per_call[q] = max(per_call[q], float(np.max(err)))
                squares += float(np.sum(np.square(err)))
                entries += err.size
    limit = float(cell.limits["scaled_err"]["limit"])
    return {
        "copies_missing": {"value": copies - held, "limit": 0},
        "nonfinite": {"value": nonfinite, "limit": 0},
        "failed_calls": {"value": sum(e > limit for e in per_call),
                         "limit": 0},
        "scaled_err": {"value": max(per_call), "limit": limit},
    }, float(np.sqrt(squares / entries))
