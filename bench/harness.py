"""Finds a cell's parts by name, runs it once, and prints its result line.

BENCHMARK.json names every cell, configuration and metric.  A cell's
configuration is the JSON file its entry names, its traffic mix is
`<bench>/traffic/<mix>.json`, and each per-layer metric is read by
`<bench>/metrics/<metric>.py`, whose `read(run)` returns a number or
None when it finds nothing to read.  So a later change adds a
configuration, a mix or a metric as new files plus new entries, and
edits no file that is here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Spec:
    """A resolved cell: its entry and the parts it names."""
    cell: dict
    config: dict
    traffic: dict
    end_to_end: list      # entries of BENCHMARK.json's end_to_end
    per_layer: list       # entries of per_layer, with their readers


def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(name: str, root: pathlib.Path = ROOT) -> Spec:
    """The cell `name` of `<root>/BENCHMARK.json`, with its parts loaded."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    home = root / bench["paths"][0]
    traffic = json.loads(
        (home / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    layer = []
    for m in bench["per_layer"]:
        if _applies(m, name, names):
            layer.append(dict(m, read=load_reader(
                home / "metrics" / f"{m['name']}.py")))
    return Spec(cell, config, traffic, e2e, layer)


class Run:
    """One run of a cell: what the mode's runner and the metric readers
    share."""

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 t_start: float, device_kind: str = "", chips: int = 1):
        from bench.trace import Tracer
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.config, self.traffic = spec.config, spec.traffic
        self.t_start = t_start
        self.device_kind, self.chips = device_kind, chips
        self.tracer = Tracer(trace, chips)
        self.spans = []               # [name, start, end], host clock
        self.counters = {}
        self.setup_s = self.memory_peak_bytes = self.trace = None
        self.compiles_in_window = 0
        self.events = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        s = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append([name, s, time.perf_counter()])

    _EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
               "/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}

    def _on_event(self, event, *a, **kw):
        if event in self._EVENTS:
            self.events[self._EVENTS[event]] += 1

    def begin_window(self) -> float:
        """The end of set-up: every shape the window uses is warm."""
        self.tracer.open()
        self._compiles_before = self.events["compiles"]
        t0 = time.perf_counter()
        if self.setup_s is None:
            self.setup_s = t0 - self.t_start
        return t0

    def end_window(self) -> None:
        import jax
        self.compiles_in_window += (self.events["compiles"]
                                    - self._compiles_before)
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:self.chips])
        self.tracer.close()
        self.trace = self.tracer.trace


def execute(spec: Spec, seed: int, seconds: float, trace: bool,
            t_start: float, device: dict) -> dict:
    """Run the cell once; -> the result line as a dict ("checks" last)."""
    from bench import check, drive
    mode = spec.traffic["mode"]
    run = Run(spec, seed, seconds, trace, t_start, device.get("kind", ""),
              spec.cell["chips"])
    out = drive.RUNNERS[mode](run)
    correct, checks = check.verdict(out["numbers"], spec.config["limits"])
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    if trace:
        for m in spec.per_layer:
            v = m["read"](run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
    else:
        have = dict(out["metrics"], setup_s=run.setup_s,
                    recall_at_10=out["numbers"]["recall_at_10"])
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": float(have[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        from bench.trace import breakdown
        dev.update(busy_s=run.trace.busy_s(), window_s=run.trace.window_s)
        line["breakdown"] = breakdown(run.trace, {s[0] for s in run.spans})
    line.update(out.get("extra", {}))
    line["compiles_in_window"] = run.compiles_in_window
    line["compile_events"] = run.events
    line["setup_s"] = run.setup_s
    line["checks"] = checks
    return line


def report(line: dict) -> None:
    """The result line last on stdout; the compared numbers last on
    stderr, each beside its limit."""
    for name, c in line["checks"].items():
        op = ">=" if name == "recall_at_10" else "<="
        print(f"check {name} {c['value']!r} {op} {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
