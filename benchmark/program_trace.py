"""The program's own marks in a benchmark trace, read from the newest
.bench_trace/**/*.xplane.pb of a traced run:

- host spans that utils/trace.Trace opens on the profiler's clock, named
  "<phase>/<step>" as the step profiler keys the step (pipeline/executed
  and the rest); a span ended by another step than its name (`ended_by`)
  or left open (`abandoned`) is left out;
- device time under the round program's named scopes (ops/kernel.py
  jax.named_scope): each op the device ran inside an XLA module named
  `jit__schedule_round` is put under the first of SCOPES in its
  framework op path, which xprof reads from the HLO the trace carries; a
  fusion with no op path of its own takes the scope of the ops fused in
  it (_op_scopes).

A program that emits no such span or scope yields nothing here: every
function returns None rather than raising. Times are nanoseconds on the
profiler's clock; durations returned are seconds.
"""

from __future__ import annotations

import functools
import json
import sys
from collections import Counter
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import trace_reduce

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"
ROUND_MODULE = "jit__schedule_round"
SCOPES = ("taint_ports", "wave_dense", "pod_scan", "stage_placements",
          "pad_wave")
UNSCOPED = "unscoped"
_SKIP = ("ended_by", "abandoned")

Span = Tuple[str, float, float, dict]


def newest(root: Optional[Path] = None) -> Optional[Path]:
    root = TRACE_DIR if root is None else root
    files = sorted(root.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns)
    return files[-1] if files else None


def _key(path) -> Tuple[str, int]:
    # a cache key that a file written anew at the same path changes
    return str(path), Path(path).stat().st_mtime_ns


@functools.lru_cache(maxsize=2)
def _profile(key: Tuple[str, int]):
    from jax.profiler import ProfileData

    return ProfileData.from_file(key[0])


def host_spans(path, prefix: str = "pipeline/") -> List[Span]:
    """(name, start_ns, end_ns, metadata) of the program's spans whose
    name starts with `prefix`, in start order."""
    out = []
    for plane in _profile(_key(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(prefix):
                    continue
                meta = dict(ev.stats)
                if any(k in meta for k in _SKIP):
                    continue
                out.append((ev.name, ev.start_ns, ev.end_ns, meta))
    return sorted(out, key=lambda s: s[1])


def _tool(key: Tuple[str, int], tool: str, **options):
    """xprof's `tool` on the trace, parsed; None where xprof cannot read
    it."""
    try:
        from xprof.convert import raw_to_tool_data

        # the tool also writes its op stats beside the trace; a result
        # saved there by an earlier read is not taken for this one
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [key[0]], tool, {"use_saved_result": False, **options})
    except Exception as e:  # noqa: BLE001
        # a metric that cannot be read is left out, not the run
        print(f"program_trace: no {tool} from {key[0]}: {e!r}",
              file=sys.stderr)
        return None
    return json.loads(data) if data else None


@functools.lru_cache(maxsize=2)
def _op_scopes(key: Tuple[str, int]) -> Dict[Tuple[str, str], str]:
    """(program id, HLO op name) -> scope of every op, from the op's own
    framework op path (xprof's hlo_stats tool, which lists every op). XLA
    gives a fusion the metadata of its root instruction, which may carry
    none (a layout copy or bitcast at the root): such a fusion takes the
    scope of the ops fused in it (fused_scope), which only xprof's
    op_profile tool lists, for the ops it ranks. Empty where xprof
    cannot read the trace."""
    table = _tool(key, "hlo_stats")
    if table is None:
        return {}
    col = {c["id"]: k for k, c in enumerate(table["cols"])}
    paths = {}
    for row in table["rows"]:
        v = [c.get("v") for c in row["c"]]
        paths[(str(v[col["program_id"]]), v[col["hlo_op_name"]])] = \
            v[col["tf_op_name"]] or ""
    out = {k: scope_of(p) for k, p in paths.items()}
    if any(not p for p in paths.values()):
        profile = _tool(key, "op_profile", group_by="program") or {}
        for prog in profile.get("byProgram", {}).get("children", []):
            pid = _program_id(prog.get("name", ""))
            for op in _ops(prog):
                if paths.get((pid, op["name"])) == "":
                    out[(pid, op["name"])] = fused_scope(op)
    return out


def _ops(node):
    """The ops under a node of the op_profile tree: its first nodes that
    carry an op (`xla`), below the program and category nodes."""
    for child in node.get("children", ()):
        if "xla" in child:
            yield child
        else:
            yield from _ops(child)


def fused_scope(op: dict) -> str:
    """The scope that most of the ops fused in an op_profile op node
    name, nested fusions included; the first of SCOPES among equals;
    UNSCOPED where none names one."""
    votes = Counter(_fused_scopes(op))
    if not votes:
        return UNSCOPED
    return max(SCOPES, key=lambda s: (votes[s], -SCOPES.index(s)))


def _fused_scopes(node):
    for child in node.get("children", ()):
        scope = scope_of(child.get("xla", {}).get("provenance", ""))
        if scope != UNSCOPED:
            yield scope
        yield from _fused_scopes(child)


def scope_of(op_path: str) -> str:
    """The first of SCOPES among the components of a framework op path
    ("jit(_schedule_round)/while/body/.../pod_scan/while/body/add")."""
    for part in op_path.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def _program_id(module_event_name: str) -> str:
    # 'jit__schedule_round(6469...)' -> '6469...'
    return module_event_name.rsplit("(", 1)[-1].rstrip(")")


def scope_seconds(path, window: Tuple[float, float],
                  module: str = ROUND_MODULE) -> Optional[Dict[str, float]]:
    """Device seconds per scope (and UNSCOPED) of the ops run inside
    `module`, summed over the devices, clipped to `window` (the host
    span trace_reduce takes) widened by trace_reduce.CLOCK_TOL, control
    flow left out as trace_reduce leaves it out of its op times. None
    when no op of the module falls under a scope."""
    return _scope_seconds(_key(path), tuple(window), module)


@functools.lru_cache(maxsize=4)
def _scope_seconds(key, window, module):
    scopes = _op_scopes(key)
    if not scopes:
        return None
    lo = window[0] - trace_reduce.CLOCK_TOL
    hi = window[1] + trace_reduce.CLOCK_TOL
    out: Dict[str, float] = {}
    for plane in _profile(key).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if (trace_reduce.OPS_LINE not in lines
                or trace_reduce.MODULES_LINE not in lines):
            continue
        mods = sorted(
            (ev.start_ns, ev.end_ns, _program_id(ev.name))
            for ev in lines[trace_reduce.MODULES_LINE].events
            if trace_reduce.module_name(ev.name) == module)
        if not mods:
            continue
        k = 0
        for ev in sorted(lines[trace_reduce.OPS_LINE].events,
                         key=lambda e: e.start_ns):
            s, e = ev.start_ns, ev.end_ns
            while k < len(mods) and mods[k][1] < s:
                k += 1
            if k == len(mods):
                break
            if s < mods[k][0]:
                continue  # an op of another module
            name = trace_reduce.op_name(ev.name)
            if trace_reduce.CONTAINER.match(name):
                continue
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            scope = scopes.get((mods[k][2], name), UNSCOPED)
            out[scope] = out.get(scope, 0.0) + (e - s) * 1e-9
    if not any(s in out for s in SCOPES):
        return None
    return dict(out)


def overlap_s(intervals: Sequence[Tuple[float, float]],
              spans: Sequence[Tuple[float, float]]) -> float:
    """Seconds of `intervals` that lie inside the union of `spans`."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    total = 0.0
    for a, b in trace_reduce.union(spans):
        total += float(np.sum(np.clip(
            np.minimum(iv[:, 1], b) - np.maximum(iv[:, 0], a), 0.0, None)))
    return total * 1e-9


# what the readers call: the traced run's observations in, one number
# (or None) out, the trace file read once for all of them

def round_scopes(r) -> Optional[Dict[str, float]]:
    """scope_seconds of the newest trace, over the traced run's window."""
    path = newest() if r.trace is not None else None
    if path is None:
        return None
    return scope_seconds(path, r.trace["window"])


def step_idle_s(r, step: str = "pipeline/executed") -> Optional[float]:
    """Device idle seconds (trace_reduce's idle of the first device)
    inside the program's `step` spans; None without such a span."""
    path = newest() if r.trace is not None else None
    if path is None:
        return None
    spans = [(s, e) for n, s, e, _m in host_spans(path) if n == step]
    if not spans:
        return None
    return overlap_s(r.trace["idle"], spans)
