"""Runs one cell: set-up, warm-up, the measured window, the comparison.

Everything that belongs to one cell, configuration, traffic kind or
metric is found by name, so that a cell, a mix or a metric is added as
files alone:

    BENCHMARK.json                     the cell: configuration + traffic
    bench/workloads/<cell>.json        traffic kind, parameters, limits
    bench/configs/<config>.json        the deployment (file named there)
    bench/systems/<system>.py          how the system under test is built
    bench/programs/<program>.py        its views, inputs and reference
    bench/traffic/<kind>.py            warm(), drive(), finish()
    bench/metrics/<metric>.py          read(record) -> number or None

A metric split by the end-to-end metric it moves (``device_idle_pct.engine``)
is read by ``bench/metrics/device_idle_pct.engine.py`` where that file
exists, and otherwise by the reader of its base name,
``bench/metrics/device_idle_pct.py``.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# spans the firing's device time is attributed by, per system
FIRING_SPANS = ("apply_update", "apply_updates", "wait_views")


class NoDevice(RuntimeError):
    """JAX found no accelerator of the kind, or fewer than the cell asks."""


def load_module(path: Path, name: str):
    """Import ``path`` once per process under ``name`` (file names may
    hold dots, which ``import`` cannot take)."""
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_program(name: str):
    return load_module(BENCH / "programs" / f"{name}.py",
                       f"bench.programs.{name}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    workload: dict
    system: object
    traffic: object
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end

    bench_dir: Path = BENCH

    def reader(self, metric: str):
        name = metric
        if not (self.bench_dir / "metrics" / f"{name}.py").is_file():
            name = metric.split(".")[0]
        return load_module(self.bench_dir / "metrics" / f"{name}.py",
                           f"bench.metrics.{name}")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / "bench"
    workload = json.loads((bench_dir / "workloads" /
                           f"{name}.json").read_text())
    for key in ("config", "traffic"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: workload file says {key} "
                             f"{workload[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    cfg = json.loads((root / configs[entry["config"]]["file"]).read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in moved]
    cell = Cell(
        name=name, chips=int(entry["chips"]), cfg=cfg, workload=workload,
        system=load_module(bench_dir / "systems" / f"{cfg['system']}.py",
                           f"bench.systems.{cfg['system']}"),
        traffic=load_module(bench_dir / "traffic" / f"{workload['kind']}.py",
                            f"bench.traffic.{workload['kind']}"),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
    for m in e2e + per_layer:
        cell.reader(m["name"])
    return cell


def use_compile_cache(root: Path = ROOT) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names, so that two checkouts share
    nothing and only a cell's first run there compiles; every program is
    cached, however fast it compiled, so that a warm set-up compiles
    nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def judge(readings: dict, limits: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(readings[k] <= limits[k] for k in limits)


class CompileCounter:
    """Counts lowerings and backend compiles while ``active``."""

    _instance = None

    def __init__(self):
        import jax
        from jax._src import dispatch
        self._events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowerings",
                        dispatch.BACKEND_COMPILE_EVENT: "compiles"}
        self.active = False
        self.counts = {"lowerings": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event: str, duration: float, **_) -> None:
        kind = self._events.get(event)
        if self.active and kind is not None:
            self.counts[kind] += 1

    def start(self) -> None:
        self.counts = {"lowerings": 0, "compiles": 0}
        self.active = True

    def stop(self) -> dict:
        self.active = False
        return dict(self.counts)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, platform: str = "tpu",
        control: bool = False, log=print) -> dict:
    """One run of ``cell``.  Returns the result line's object; its
    ``checks`` (the numbers compared, with their limits) come last.
    ``control`` also puts the control in the program's place: its
    readings go under ``control`` and its verdict, by the same ``judge``
    as ``correct``, under ``control_correct``."""
    import jax
    from bench import record, trace_reduce as tracing
    from bench.spans import Spans

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        raise NoDevice(f"{cell.name} needs {cell.chips} {platform} "
                       f"device(s); JAX found {len(devices)} "
                       f"{devices[0].platform}")
    devices = devices[:cell.chips]
    params = cell.workload["params"]
    spans = Spans(annotate=trace)
    system = cell.system.System(cell.cfg, seed, spans)
    rng = record.rng(seed, 2)
    state = cell.traffic.warm(system, params, rng)
    counter = CompileCounter.get()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = system.counters()
    counter.start()
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    try:
        with spans.span(tracing.WINDOW_SPAN):
            window = cell.traffic.drive(system, params, rng, seconds, state)
        compiles = counter.stop()
        after = system.counters()
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    t_end = time.perf_counter()
    peak = memory_peak_bytes(devices)
    window.update(cell.traffic.finish(system, params, window))
    t_finish = time.perf_counter()
    got, ctl = system.readings(control=control)
    del system
    log(f"info: set-up {setup_s:.1f} s, window {t_end - t_window:.1f} s, "
        f"finish {t_finish - t_end:.1f} s, reference "
        f"{time.perf_counter() - t_finish:.1f} s")
    # the compared number: the largest relative error over every view the
    # window produced (a cell's workload file may also hold single views)
    got["views"] = max(got.values())
    if ctl is not None:
        ctl["views"] = max(ctl.values())
    limits = cell.workload["limits"]
    unknown = set(limits) - set(got)
    if unknown:
        raise ValueError(f"{cell.name}: no reading for limits "
                         f"{sorted(unknown)}")
    correct = judge(got, limits)
    reduced = None
    if trace:
        try:
            ops, host = tracing.extract(
                trace_dir, platform,
                {tracing.WINDOW_SPAN, *(s[0] for s in spans.records)})
            reduced = tracing.reduce(ops, host, busy_spans=FIRING_SPANS)
            log(f"info: trace window {reduced['window_s']:.3f} s, busy "
                f"{reduced['busy_s']:.3f} s, in firing spans "
                f"{reduced['busy_in_spans_s']:.3f} s, {len(host)} spans")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rec = {
        "cell": cell.name, "cfg": cell.cfg, "params": params,
        "setup_s": setup_s, "window": window,
        "counters": {"before": before, "after": after},
        "spans": spans.between(t_window, t_end), "trace": reduced,
        "memory_peak_bytes": peak, "device_kind": devices[0].device_kind,
    }
    metrics = {}
    for m in cell.metrics(trace):
        value = cell.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = device_info(devices) | {"memory_peak_bytes": peak}
    log(f"info: window {window['window_s']:.3f} s, "
        f"{window.get('updates')} updates, compiles in window {compiles}")
    result = {"correct": correct, "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["readings"] = got
    if ctl is not None:
        result["control"] = ctl
        result["control_correct"] = judge(ctl, limits)
    result["info"] = {k: v for k, v in window.items()
                      if isinstance(v, (int, float, str))} | {
        "compiles_in_window": compiles}
    result["checks"] = {k: {"value": got[k], "limit": limits[k]}
                        for k in limits}
    return result
