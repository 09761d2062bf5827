"""program_trace and its readers against a small trace recorded on the
v5e (testdata/program.xplane.pb, made by record_program_trace.py): a
program named like the round with a `wave_dense` and a `pod_scan`
scope, run twice inside `bench_window`, once inside a pipeline/executed
span; and a program of another module, scoped `pod_scan` too, inside a
pipeline/executed span that carries `ended_by`. The expected numbers
were worked out by hand from the events the script printed (ns):

- bench_window 46703936 to 91699862; the spans pipeline/executed
  46706416-58841715 and, with ended_by, 80461523-91697802.
- jit__schedule_round runs at 56915309-56922452 and 78682387-78689694;
  jit__other at 89962115-89969792.
- op paths (xprof hlo_stats): fusion.3 under wave_dense; slice.0,
  constant_dynamic-slice_fusion.2, fusion.7 and dynamic_update_slice.3
  under pod_scan; copy-start, copy-done, custom-call(.1), reduce_sum.22
  and .23 under none; while.1 is control flow.
- wave_dense: fusion.3, 1824 + 1825 = 3649.
- pod_scan: run 1 slice.0 2, the four dynamic-slice fusions 8 + 8 + 8 +
  7, fusion.7 341 + 340 + 342 + 341, dynamic_update_slice.3 480 + 482
  + 482 + 480: 3321; run 2 4 + (7 + 8 + 7 + 8) + (342 + 341 + 342 +
  341) + (482 + 482 + 647 + 481) = 3492; 6813 in all. jit__other's
  fusion (7655 under pod_scan) is another module's and left out.
- unscoped: run 1 copy-start 13, copy-done 3, custom-call 1,
  reduce_sum.22 326, .23 331 = 674; run 2 13 + 2 + 1 + 325 + 328 = 669
  (custom-call.1 of run 1 and custom-call of run 2 last 0 ns): 1343.
- device busy inside the executed span is run 1's union of ops: 13 + 3
  + 1824 + 2 + 1 + (56917166 to 56920500: while.1 and the ops in it)
  3334 + 326 + 331 = 5834 of the span's 12135299, so the device idles
  12129465 inside it. The span with ended_by is left out.
"""

import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_trace  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

NS = 1e-9
DATA = BENCH / "testdata"


def _copy(tmp_path, name):
    # xprof writes its op stats beside the trace it reads: read a copy
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(DATA / name, d / "host.xplane.pb")
    return d / "host.xplane.pb"


@pytest.fixture
def trace(tmp_path, monkeypatch):
    path = _copy(tmp_path, "program.xplane.pb")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    return path


def _obs(red, binds=1000, step_delta=None, window_binds=2000):
    return SimpleNamespace(
        cell={"traffic": {"loop": "closed"}}, trace=red,
        traced_binds=binds, step_delta=step_delta or {},
        window_binds=lambda: window_binds)


def test_newest_finds_the_trace(trace):
    assert program_trace.newest() == trace


def test_scope_seconds(trace):
    red = trace_reduce.read(trace)
    by = program_trace.scope_seconds(trace, red["window"])
    assert set(by) == {"wave_dense", "pod_scan", program_trace.UNSCOPED}
    assert by["wave_dense"] == pytest.approx(3649 * NS, rel=1e-9)
    assert by["pod_scan"] == pytest.approx(6813 * NS, rel=1e-9)
    assert by[program_trace.UNSCOPED] == pytest.approx(1343 * NS, rel=1e-9)


def test_spans_with_ended_by_left_out(trace):
    assert program_trace.host_spans(trace) == [
        ("pipeline/executed", 46706416.0, 58841715.0, {})]


def test_executed_idle(trace):
    red = trace_reduce.read(trace)
    assert program_trace.step_idle_s(_obs(red)) == pytest.approx(
        12129465 * NS, rel=1e-9)


@pytest.mark.parametrize("metric, ns", [
    ("round_scan_ms_per_kpod.drain", 6813),
    ("round_dense_ms_per_kpod.drain", 3649),
    ("round_wait_idle_ms_per_kpod.drain", 12129465),
])
def test_device_readers(trace, metric, ns):
    red = trace_reduce.read(trace)
    # 1,000 traced binds: ms per 1,000 pods is the seconds times 1,000
    assert run.reader(metric)(_obs(red)) == pytest.approx(ns * 1e-6,
                                                          rel=1e-9)


@pytest.mark.parametrize("metric", [
    "round_scan_ms_per_kpod.drain", "round_dense_ms_per_kpod.drain",
    "round_wait_idle_ms_per_kpod.drain"])
def test_device_readers_silent_without_marks(tmp_path, monkeypatch, metric):
    """A program with no scopes and no spans (small.xplane.pb, the v5e
    trace test_trace_reduce.py reads), or a run with no device trace: no
    value, no error."""
    path = _copy(tmp_path, "small.xplane.pb")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path)
    assert run.reader(metric)(_obs(trace_reduce.read(path))) is None
    assert run.reader(metric)(_obs(None)) is None


@pytest.mark.parametrize("part", ["recheck", "assume", "bind"])
def test_commit_readers(part):
    read = run.reader(f"commit_{part}_ms_per_kpod.drain")
    r = _obs(None, step_delta={f"commit/{part}": 0.25})
    assert read(r) == pytest.approx(125.0)  # 250 ms over 2,000 binds
    assert read(_obs(None)) is None


def test_scope_of():
    assert program_trace.scope_of(
        "jit(_schedule_round)/while/body/closed_call/cond/branch_1_fun/"
        "pod_scan/while/body/add:") == "pod_scan"
    assert program_trace.scope_of(
        "jit(_schedule_round)/reduce_sum:") == program_trace.UNSCOPED


def _op(path, *fused):
    return {"name": "op", "xla": {"provenance": path},
            "children": list(fused)}


def test_fused_scope():
    """A fusion with no op path of its own takes the scope that most of
    its fused ops (nested fusions included) name; the first of SCOPES
    among equals; none when no fused op names one."""
    dense = "jit(_schedule_round)/wave_dense/jit(take_along_axis)/gather:"
    scan = "jit(_schedule_round)/pod_scan/while/body/add:"
    assert program_trace.fused_scope(
        _op("", _op(""), _op(dense), _op(":", _op(dense)), _op(scan))
    ) == "wave_dense"
    assert program_trace.fused_scope(_op("", _op(scan), _op(dense))) == \
        "wave_dense"
    assert program_trace.fused_scope(
        _op("", _op("jit(_schedule_round)/reduce_sum:"))) == \
        program_trace.UNSCOPED


def test_op_scopes_of_the_trace(trace):
    """Every op of the recorded round has the scope its own path names:
    no fusion there lacks a path, and ops with none (copy-start, the
    AllocateBuffer custom-calls) fuse nothing."""
    scopes = program_trace._op_scopes(program_trace._key(trace))
    rnd = {op: s for (pid, op), s in scopes.items()
           if pid == "5800261216367294547"}
    assert rnd["fusion.3"] == "wave_dense"
    assert rnd["fusion.7"] == "pod_scan"
    assert rnd["reduce_sum.23"] == program_trace.UNSCOPED
    assert rnd["copy-start"] == program_trace.UNSCOPED
    assert set(rnd.values()) == {
        "wave_dense", "pod_scan", program_trace.UNSCOPED}


def test_pathless_fusion_takes_its_fused_scope(monkeypatch):
    """hlo_stats gives each op its own path; only a fusion with none is
    looked up in op_profile's tree, and one op_profile does not list
    stays unscoped."""
    dense = "jit(_schedule_round)/wave_dense/gather:"
    scan = "jit(_schedule_round)/pod_scan/while/body/add:"
    cols = ["program_id", "hlo_op_name", "tf_op_name"]
    rows = [("7", "fusion.1", scan), ("7", "fusion.111", ""),
            ("7", "fusion.112", ""), ("7", "copy-done", "")]
    tables = {
        "hlo_stats": {
            "cols": [{"id": c} for c in cols],
            "rows": [{"c": [{"v": v} for v in r]} for r in rows]},
        "op_profile": {"byProgram": {"children": [{
            "name": "jit__schedule_round(7)", "children": [{
                "name": "loop fusion", "children": [
                    dict(_op(dense, _op(scan)), name="fusion.1"),
                    dict(_op("", _op(":"), _op(dense), _op(dense)),
                         name="fusion.111"),
                    dict(_op(""), name="copy-done")]}]}]}},
    }
    monkeypatch.setattr(program_trace, "_tool",
                        lambda key, tool, **kw: tables[tool])
    program_trace._op_scopes.cache_clear()
    try:
        scopes = program_trace._op_scopes(("x.xplane.pb", 0))
    finally:
        program_trace._op_scopes.cache_clear()
    assert scopes == {("7", "fusion.1"): "pod_scan",
                      ("7", "fusion.111"): "wave_dense",
                      ("7", "fusion.112"): program_trace.UNSCOPED,
                      ("7", "copy-done"): program_trace.UNSCOPED}
