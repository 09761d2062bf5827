"""Reduce a JAX profiler trace (.xplane.pb) to the benchmark's device
numbers: busy and idle time inside a window, device time per XLA module
and per op, and the idle gaps labelled by what the host was doing.

Planes named /device:TPU:<n> are devices. On each, busy time is the
union of the intervals of the "XLA Ops" line (the "XLA Modules" line
where a trace has no op line). The window is the host span named by
`window` (a jax.profiler.TraceAnnotation the benchmark opens around the
part it traces); without one, the span of the device events. Times are
nanoseconds. The device's events are put on the host's clock by the
profiler, which leaves them up to about a millisecond early (a device
program seen to start before the host dispatched it, in the v5e trace
kept in testdata/), so the window is widened by CLOCK_TOL on each side
for the device's events and counted with that width.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
CLOCK_TOL = 2e6  # ns
# control flow holds other ops: counted in busy time, not as an op
CONTAINER = re.compile(r"^(while|cond|conditional|call)(\.|$)")

Interval = Tuple[float, float]


def module_name(name: str) -> str:
    """'jit__schedule_round(123)' -> 'jit__schedule_round'."""
    return _ID_SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...), ...' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] around a merged busy list."""
    out = []
    t = lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_spans(pd, names: Optional[set] = None) -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) of host events, those named in `names`."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or ev.name in names:
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def read(path, window: str = "bench_window") -> Optional[dict]:
    """The raw reduction of one trace file, or None if it holds no
    device plane. Keys: window (start_ns, end_ns), busy_s (mean over the
    devices), window_s, chips, modules {name: device s}, ops {name:
    device s, control-flow ops left out}, idle [(start_ns, end_ns)] of
    the first device. `window`
    is the host span; the device's window is CLOCK_TOL wider each side
    and window_s is its width."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    wins = [(s, e) for _n, s, e in host_spans(pd, {window})]
    devices = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: [(ev.name, ev.start_ns, ev.end_ns)
                           for ev in ln.events] for ln in plane.lines}
        devices.append((lines.get(OPS_LINE, []), lines.get(MODULES_LINE, [])))
    if not devices:
        return None
    if wins:
        host = (min(s for s, _ in wins), max(e for _, e in wins))
        lo, hi = host[0] - CLOCK_TOL, host[1] + CLOCK_TOL
    else:
        every = [(s, e) for ops, mods in devices for _n, s, e in ops + mods]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
        host = (lo, hi)
    busy_total = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    idle: List[Interval] = []
    for k, (op_evs, mod_evs) in enumerate(devices):
        src = op_evs or mod_evs
        busy = union(clip([(s, e) for _n, s, e in src], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in mod_evs:
            for cs, ce in clip([(s, e)], lo, hi):
                modules[module_name(n)] = modules.get(module_name(n), 0.0) \
                    + (ce - cs) * 1e-9
        for n, s, e in op_evs:
            name = op_name(n)
            if CONTAINER.match(name):
                continue
            for cs, ce in clip([(s, e)], lo, hi):
                ops[name] = ops.get(name, 0.0) + (ce - cs) * 1e-9
        if k == 0:
            idle = gaps(busy, lo, hi)
    return {"window": host, "window_s": (hi - lo) * 1e-9,
            "busy_s": busy_total * 1e-9 / len(devices),
            "chips": len(devices), "modules": modules, "ops": ops,
            "idle": idle}


def label_idle(idle: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle seconds by the host span open at each gap's midpoint — the
    shortest (innermost) span holding it — or "untraced" where none is."""
    out: Dict[str, float] = {}
    for s, e in idle:
        mid = (s + e) / 2
        held = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
        label = min(held)[1] if held else "untraced"
        out[label] = out.get(label, 0.0) + (e - s) * 1e-9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
