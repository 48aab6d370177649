"""One run of one cell of the on-chip benchmark (``BENCHMARK.json``).

A cell is a configuration under a traffic mix.  Everything that belongs to one
of them lives in files of its own, found by the names ``BENCHMARK.json`` gives:

  bench/configs/<config>.json      the configuration as it is run
  bench/configs/<config>.py        its cell: data from the seed, the timed
                                   call into the program, the check
  bench/configs/<config>_ref.py    its plain reference (imports nothing of the
                                   program) and its FP64 work functions
  bench/traffic/<traffic>.json     sizes and call mix, read by the cell
  bench/limits/<cell>.json         the limit of each number compared, with the
                                   readings it was set from
  bench/metrics/<metric>.py        a per-layer metric: ``read(ctx)`` returns
                                   its value, or None where it finds nothing

A run: set-up (JAX configured, the chip found, data drawn on the device from
the seed, the cell's own shapes warmed up), then a window of closed-loop work
that lasts at least ``seconds`` and ends with the step that crosses it, then
the check of what the window produced against the plain reference.  The
result is one JSON object; ``run.py`` prints it as the last line of standard
output, after the numbers compared on standard error.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """A cell of ``BENCHMARK.json`` with its files."""

    def __init__(self, workload: str, root: str = ROOT):
        bench = _json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs[self.cell["config"]]
        self.config_name = entry["name"]
        self.config = _json(os.path.join(root, entry["file"]))
        self.traffic = _json(os.path.join(BENCH, "traffic",
                                          self.cell["traffic"] + ".json"))
        self.limits = _json(os.path.join(BENCH, "limits", workload + ".json"))

        def mine(m):
            return workload in m.get("workloads", [workload])
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]

    def cell_module(self):
        return load_module(os.path.join(BENCH, "configs", self.config_name + ".py"),
                           "bench.configs." + self.config_name)


def configure_jax() -> None:
    """x64 on, telemetry off, and the persistent compile cache at a fixed
    path: ``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/`` in
    the checkout.  Every program is cached, however small or quick."""
    os.environ.pop("REPRO_TELEMETRY", None)
    os.environ.pop("REPRO_DISPATCH", None)
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    """The devices of the run; raises NoChip unless JAX sees at least
    ``chips`` TPUs.  Never falls back to the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


def peaks_for(kind: str) -> Dict[str, Any]:
    """The peak table's row for a ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


class Counters:
    """JAX's compile and persistent-cache events, counted per phase."""

    NAMES = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    _instance: Optional["Counters"] = None

    def __init__(self):
        import jax.monitoring as mon
        self.phase = "setup"
        self.counts: Dict[str, int] = {}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    @classmethod
    def fresh(cls) -> "Counters":
        """The process's one set of listeners, with its counts reset."""
        if cls._instance is None:
            cls._instance = cls()
        cls._instance.phase, cls._instance.counts = "setup", {}
        return cls._instance

    def _bump(self, what: str) -> None:
        key = f"{self.phase}.{what}"
        self.counts[key] = self.counts.get(key, 0) + 1

    def _event(self, name: str, **_) -> None:
        if name in self.NAMES:
            self._bump(self.NAMES[name])

    def _duration(self, name: str, _secs: float, **_) -> None:
        if name == self.COMPILE:
            self._bump("compiles")

    def get(self, key: str) -> int:
        return self.counts.get(key, 0)


class Reservoir:
    """A uniform sample of ``size`` of the window's answers, drawn from the
    seed (Algorithm R), so that checking costs the same however many calls
    the window makes and holds at most ``size`` answers on the device."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.items: List[Any] = []
        self.seen = 0

    def offer(self, item: Any) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


class Context:
    """What a per-layer metric's reader reads: the reduced trace, the work
    units the window completed (calls, or CG iterations) and the FP64 work
    of one unit, and the chip's peaks."""

    def __init__(self, trace, units: int, flops: float, bytes_: float,
                 peaks: Dict[str, Any]):
        self.trace, self.units = trace, units
        self.flops, self.bytes = flops, bytes_
        self.peaks = peaks

    def per_unit_ms(self, seconds: float) -> Optional[float]:
        return 1e3 * seconds / self.units if seconds > 0 and self.units else None

    def least_s(self) -> float:
        """Least time of one unit's FP64 work on this chip: the larger of its
        operations over the int8 peak and its bytes over HBM bandwidth."""
        return max(self.flops / self.peaks["int8_ops_per_s"],
                   self.bytes / self.peaks["hbm_bytes_per_s"])

    def idle_pct(self) -> Optional[float]:
        """Share of the window in which the chip ran nothing, in percent."""
        if not self.trace.chips or self.trace.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def kernel_ms(self, kernel: str) -> Optional[float]:
        """Device milliseconds per unit of the operations naming ``kernel``;
        None where the trace holds none."""
        return self.per_unit_ms(self.trace.kernel_s(kernel))

    def xla_ms(self, kernel: str) -> Optional[float]:
        """Device busy milliseconds per unit outside ``kernel``; None where
        the device ran nothing."""
        busy = self.trace.busy_s
        if busy <= 0:
            return None
        return self.per_unit_ms(busy - self.trace.kernel_s(kernel))

    def roofline_pct(self) -> Optional[float]:
        """Least time of a unit's FP64 work over the busy time per unit, in
        percent; None where the device ran nothing."""
        per_unit = self.per_unit_ms(self.trace.busy_s)
        return None if per_unit is None else 100.0 * 1e3 * self.least_s() / per_unit


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Host randomness of the run, one independent stream per use."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def run(workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: Optional[float] = None, require_chip: bool = True,
        traffic: Optional[Dict[str, Any]] = None,
        patch: Optional[Callable[[], Any]] = None,
        control: bool = False) -> Dict[str, Any]:
    """One run; returns the result object.

    ``require_chip=False``, ``traffic`` (a replacement traffic mix) and
    ``patch`` (called after the program is imported, to break it) serve the
    CPU tests of the check.  ``control=True`` compares the control's answers
    (the reference in the precision below float64, in the program's place)
    instead of the program's: ``readings.py`` and the tests use it to read
    the upper end of each limit.  The benchmark's own runs use none of them.
    """
    t_start = time.time() if t_start is None else t_start
    spec = Spec(workload)
    if traffic is not None:
        spec.traffic = traffic
    configure_jax()
    import jax
    if require_chip:
        devs = find_chips(spec.chips)
    else:
        devs = jax.devices()
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_chip else None
    counters = Counters.fresh()
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro  # noqa: F401 — fails here when the checkout lacks the program
    if patch is not None:
        patch()
    log(f"bench: workload={workload} seed={seed} device_kind={dev.device_kind} "
        f"platform={dev.platform} count={len(devs)}")

    cell = spec.cell_module().Cell(spec.config, spec.traffic, seed)
    cell.setup()
    for line in cell.warm():
        log("bench: " + line)
    setup_s = time.time() - t_start

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    counters.phase = "window"
    attempted = failed = units = 0
    step_s: List[float] = []
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        t_prev = t0
        while True:
            attempted += 1
            try:
                units += cell.step(attempted - 1)
            except Exception as e:  # noqa: BLE001 — a failed call is counted
                failed += 1
                log(f"bench: step {attempted - 1} failed: {e!r}")
                break
            now = time.perf_counter()
            step_s.append(now - t_prev)
            t_prev = now
            if now - t0 >= seconds:
                break
    window_s = t_prev - t0
    counters.phase = "after"
    if trace:
        jax.profiler.stop_trace()

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if step_s:
        q = [float(v) for v in np.percentile(step_s, [0, 50, 100])]
        log(f"bench: window_s={window_s!r} steps={len(step_s)} units={units} "
            f"step_s min={q[0]!r} median={q[1]!r} max={q[2]!r}")
    log(f"bench: compiles setup={counters.get('setup.compiles')} "
        f"window={counters.get('window.compiles')}; persistent cache in setup "
        f"hits={counters.get('setup.cache_hits')} "
        f"misses={counters.get('setup.cache_misses')}")

    result: Dict[str, Any] = {"correct": False, "attempted": attempted,
                              "failed": failed}
    if trace:
        from bench.trace import Trace
        tr = Trace.from_file(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        flops, bytes_ = cell.work()
        ctx = Context(tr, units, flops, bytes_, peaks)
        metrics = {}
        for m in spec.per_layer:
            reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                                 "bench.metrics." + m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_device_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        e2e = cell.end_to_end(units, window_s) if units else {}
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end if m["name"] in e2e}
    result["metrics"] = metrics
    result["device"] = device

    compared = cell.check(control=control) if units else {}
    del cell
    gc.collect()
    limits = spec.limits
    out = {}
    for name, value in compared.items():
        limit = float(limits[name]["limit"])
        out[name] = {"value": value, "limit": limit}
    ok = failed == 0 and units > 0 and bool(out) and all(
        v["value"] <= v["limit"] for v in out.values())   # NaN fails
    result["correct"] = bool(ok)
    result["compared"] = out
    return result
