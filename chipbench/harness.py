"""Find a cell's files by name, run its driver once, and assemble the
result line.

The harness knows no configuration, traffic or metric by name. It reads
`BENCHMARK.json`, loads `configs/<config>.json`, `traffic/<traffic>.json`
and the driver that traffic names, runs the driver, and for `--trace 1`
loads `metrics/<metric>.py` for each per-layer metric of the cell. A
driver module has `run(cell) -> Outcome` and `SPANS`, the names of the
host spans it writes, which the trace's reduction reads.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

class NoAccelerator(RuntimeError):
    pass


def load_module(path: str, name: Optional[str] = None):
    """Import a file by path; file names may hold `-` and `.`."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = name or "chipbench_" + os.path.basename(path).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Check:
    """One number compared with its limit; `correct` needs value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after the window and the comparison."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    facts: Dict[str, float]          # counts the per-layer readers use
    checks: List[Check]
    memory_peak_bytes: int


class Cell:
    """One run of one workload: its files, its seed and its clock."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, t_start: Optional[float] = None,
                 log: Callable[[str], None] = lambda s: None):
        self.root = root
        self.t_start = time.monotonic() if t_start is None else t_start
        self.log = log
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.dir = os.path.join(root, "chipbench")
        self.config_name = self.workload["config"]
        self.config = load_json(os.path.join(
            self.dir, "configs", self.config_name + ".json"))
        self.traffic_name = self.workload["traffic"]
        self.traffic = load_json(os.path.join(
            self.dir, "traffic", self.traffic_name + ".json"))
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.window_start = self.window_end = None
        self.compiles_in_window = 0
        self.compile_s_in_window = 0.0
        self._counting = False
        self.setup_events: Dict[str, List[float]] = {}
        self._trace_dir = None
        self._driver = None

    # -------------------------------------------------------- files by name
    def reference(self):
        """The configuration's plain reference, beside its file."""
        return load_module(os.path.join(
            self.dir, "configs", self.config["reference"] + ".py"))

    def driver(self):
        if self._driver is None:
            self._driver = load_module(os.path.join(
                self.dir, "drivers", self.traffic["driver"] + ".py"))
        return self._driver

    # ------------------------------------------------------------ metrics
    def end_to_end_names(self) -> List[str]:
        return [m["name"] for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        e2e = set(self.end_to_end_names())
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    # ------------------------------------------------------------ window
    @contextlib.contextmanager
    def window(self):
        """The measured window: counts compilations inside it and, with
        tracing on, records it in the profiler's trace."""
        import jax

        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self._trace_dir)
        # A program whose shapes hang on the data (the fleet engine's
        # pools, where a replacement chain outgrows them) compiles inside
        # the window. The persistent cache is off here, neither read nor
        # written, so every run compiles its own and no run finds what an
        # earlier run of the checkout left.
        _persistent_cache(False)
        for event, (n, secs) in sorted(self.setup_events.items()):
            self.log(f"set-up {event}: {n} ({secs:.3f} s)")
        self.mark("window")
        self._counting = True
        self.window_start = time.monotonic()
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield self
                self.window_end = time.monotonic()
        finally:
            self._counting = False
            _persistent_cache(True)
            if self.trace:
                jax.profiler.stop_trace()

    def mark(self, phase: str) -> None:
        """Log the end of a set-up phase, in seconds from process start,
        so that a set-up that wanders shows which phase moved."""
        self.log(f"setup {phase} {time.monotonic() - self.t_start:.3f}")

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def remaining(self) -> float:
        return self.window_start + self.seconds - time.monotonic()

    def count_compile(self, event: str, duration: float, **_kw) -> None:
        if self.window_start is None and event in _COMPILE_EVENTS:
            n_s = self.setup_events.setdefault(event.rsplit("/", 1)[-1],
                                               [0, 0.0])
            n_s[0] += 1
            n_s[1] += duration
        if self._counting and event in _COMPILE_EVENTS:
            self.compiles_in_window += 1
            self.compile_s_in_window += duration

    def reduced_trace(self):
        from chipbench import trace
        paths = glob.glob(os.path.join(self._trace_dir, "**",
                                       "*.xplane.pb"), recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        try:
            return trace.reduce_file(paths[0], self.driver().SPANS)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


def _persistent_cache(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def place_compile_cache(root: str) -> None:
    """JAX's persistent cache: `JAX_COMPILATION_CACHE_DIR` if set, else
    the fixed `<checkout>/.jax_cache`, so only a cell's first run in a
    checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def memory_peak_bytes() -> int:
    """The allocator's peak on the fullest device, as JAX reports it."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoAccelerator(f"JAX found no TPU ({info})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return info


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             log: Callable[[str], None] = lambda s: print(
                 s, file=sys.stderr, flush=True)) -> dict:
    """Run one cell once and return the result line's object."""
    cell = Cell(root, workload, seed, seconds, trace, t_start, log)
    from jax._src import monitoring
    cell.mark("import_jax")

    place_compile_cache(root)
    device = device_info(require_tpu, cell.workload["chips"])
    cell.mark("devices")
    monitoring.register_event_duration_secs_listener(cell.count_compile)
    try:
        out = cell.driver().run(cell)
    finally:
        monitoring.unregister_event_duration_listener(cell.count_compile)
    log(f"compilations inside the window: {cell.compiles_in_window} "
        f"({cell.compile_s_in_window:.3f} s)")
    setup_s = cell.window_start - t_start
    device["memory_peak_bytes"] = int(out.memory_peak_bytes)
    metrics = {}
    if trace:
        red = cell.reduced_trace()
        if red.cut_s:
            log(f"the device trace ends {red.cut_s:.3f} s before the "
                f"window: readings cover its first {red.window_s:.3f} s")
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for m in cell.per_layer():
            reader = load_module(os.path.join(cell.dir, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(red, out.facts, device)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out.end_to_end, setup_s=setup_s)
        for name in cell.end_to_end_names():
            unit = next(m["unit"] for m in cell.bench["end_to_end"]
                        if m["name"] == name)
            metrics[name] = {"value": values[name], "unit": unit}
    checks = out.checks
    result = {"correct": bool(checks) and all(c.ok for c in checks)
              and out.failed == 0,
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = red.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"{c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    return result
