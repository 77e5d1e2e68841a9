"""Profiler capture and the reduction from a device trace to numbers.

A traced run turns the profiler on before its window opens (so that
starting it costs the window nothing), wraps the part of the window it
reads in the host span `bench.traced`, and turns the profiler off and
reads the trace after the window has closed.  The Python tracer stays
off: it would time every Python call of the host loop.

`extract` keeps three things from the trace: the device operations of
each chip (the "XLA Ops" line of each `/device:TPU:<i>` plane, named by
their HLO instruction, e.g. `sort.54` or `rng_round_pallas.3`, the
Pallas kernels by the function that calls them), the host spans
(`jax.profiler.TraceAnnotation` names, among them the benchmark's own,
and the runtime's), and the traced window.  The reductions below work on
those plain lists, so they are tested on hand-built traces.

Device and host events share the profiler's clock.  The ops line nests
a loop's or a conditional's body inside its event; busy time is the
union of all intervals inside the window, and idle the rest.  A
kernel's time is the summed duration of the ops whose name matches its
pattern; the breakdown counts leaf ops only.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str         # HLO instruction name of a device op, or span name
    start: int        # ns
    end: int          # ns
    text: str = ""    # the op's HLO text (its first type is shown)


@dataclasses.dataclass
class Trace:
    devices: list      # per chip: list[Event] of device operations
    host: list         # list[Event] of host spans
    window: tuple      # (start, end) ns of the traced window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, chip: int = 0) -> list:
        lo, hi = self.window
        return [e for e in self.devices[chip] if e.end > lo and e.start < hi]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(union_ns(d, *self.window) for d in self.devices) * 1e-9 \
            / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern: str, chip: int = 0) -> tuple[float, int]:
        """(seconds, count) of the device ops whose name matches."""
        rx = re.compile(pattern)
        hits = [e for e in self.ops(chip) if rx.search(e.name)]
        return sum(e.end - e.start for e in hits) * 1e-9, len(hits)

    def host_spans(self, name: str) -> list:
        lo, hi = self.window
        return [e for e in self.host
                if e.name == name and e.end > lo and e.start < hi]


def _clip(events, lo, hi):
    return sorted((max(e.start, lo), min(e.end, hi))
                  for e in events if e.end > lo and e.start < hi)


def union_ns(events, lo: int, hi: int) -> int:
    """Length of the union of the events' intervals inside [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in _clip(events, lo, hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(events, lo: int, hi: int) -> list:
    """The idle intervals [s, e) inside [lo, hi) between the events."""
    out, t = [], lo
    for s, e in _clip(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def leaves(events) -> list:
    """The events that hold no other event: the ops a loop, a conditional
    or a call runs, not the loop itself."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def _label(e: Event) -> str:
    """`sort.54 s32[9600000]`: the instruction and its first type."""
    kind = e.text.split(" = ", 1)[1].split(" ", 1)[0] if " = " in e.text \
        else ""
    return f"{e.name} {kind.split('{')[0]}".strip()[:80]


def breakdown(trace: Trace, bench_spans=(), top: int = 10) -> dict:
    """The leaf device ops that took most time, and the longest idle gaps,
    each named by the benchmark span and the innermost host event that
    cover its middle."""
    by_name: dict = {}
    for e in leaves(trace.ops(0)):
        key = _label(e)
        by_name[key] = by_name.get(key, 0) + (e.end - e.start)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps_ns(trace.devices[0], *trace.window),
                  key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        cover = sorted((h for h in trace.host
                        if h.start <= mid < h.end and h.name != WINDOW_SPAN),
                       key=lambda h: h.end - h.start)
        mine = [h.name for h in cover if h.name in bench_spans]
        name = "/".join(dict.fromkeys(mine[:1] + [h.name for h in cover[:1]]))
        idle.append([name or "host:untraced", (e - s) * 1e-9])
    return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": idle}


def _op(ev) -> Event:
    text = ev.name
    name = text.split(" = ", 1)[0].lstrip("%")
    return Event(name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 text)


def extract(profile, chips: int) -> Trace:
    """A Trace from a `jax.profiler.ProfileData`."""
    devices = [[] for _ in range(chips)]
    host = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.search(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] += [
                        _op(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                         for e in line.events]
    win = [h for h in host if h.name == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not all(devices):
        raise RuntimeError("the trace holds no device operations of chips "
                           f"{[i for i, d in enumerate(devices) if not d]}")
    return Trace(devices, host, (win[0].start, win[0].end))


class Tracer:
    """The profiler of one run.  `open()` turns it on, `start()`/`stop()`
    bound the traced window (the span `bench.traced`), `close()` turns it
    off and keeps the extracted Trace in `.trace`.  Disabled, all four
    do nothing and `.trace` stays None."""

    def __init__(self, enabled: bool, chips: int):
        self.enabled, self.chips = enabled, chips
        self._dir = self._span = None
        self._done = False
        self.trace = None

    def open(self) -> None:
        if not self.enabled or self._dir is not None:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)

    def start(self) -> None:
        if self._dir is None or self._span is not None or self._done:
            return
        import jax
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    @property
    def active(self) -> bool:
        return self._span is not None

    def stop(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span, self._done = None, True

    def close(self) -> None:
        if self._dir is None:
            return
        import jax
        self.stop()
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            self.trace = extract(jax.profiler.ProfileData.from_file(files[0]),
                                 self.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
