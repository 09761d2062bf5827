"""The round's stages on the JAX profiler's clock.

- utils/trace.Trace puts every interval between its steps on the
  profiler's host plane as a span named "<phase>/<step>", the key the
  step profiler gives the step, inside one "<phase>" span per trace;
- ops/kernel.py names the round program's parts with jax.named_scope;
- Scheduler._commit times its recheck, assume and bind per pipeline
  round while the step profiler is on ("commit/<part>").

The benchmark reads all three (benchmark/program_trace.py and the
round_*/commit_* metrics).
"""

import numpy as np
import pytest

from helpers import make_node, make_pod
from kubernetes_tpu.runtime.store import ObjectStore
from kubernetes_tpu.sched.scheduler import (
    COMMIT_PARTS, HOST_WAVE_STEPS, PIPELINE_STEPS, PREEMPT_HOST_STEPS,
    PREEMPT_STEPS, WAVE_STEPS, Scheduler)
from kubernetes_tpu.utils import profiling
from kubernetes_tpu.utils.trace import Trace

SCOPES = ("taint_ports", "wave_dense", "pod_scan", "stage_placements",
          "pad_wave")


@pytest.fixture(autouse=True)
def _profiler_off():
    profiling.disable()
    yield
    profiling.disable()


def _session(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def _host_events(tmp_path, prefix=""):
    """(name, start_ns, end_ns, stats) of the host events of the trace
    written under tmp_path, in start order."""
    from jax.profiler import ProfileData

    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _cluster(wave_size=8, nodes=4, pods=12):
    store = ObjectStore()
    sched = Scheduler(store, wave_size=wave_size)
    for i in range(nodes):
        store.create("nodes", make_node(f"n{i}", cpu="4"))
    for i in range(pods):
        store.create("pods", make_pod(f"p{i}", cpu="100m"))
    return store, sched


@pytest.fixture(scope="module")
def traced_round(tmp_path_factory):
    """One pipeline round (12 pods, 2 waves) scheduled under a profiler
    session with the step profiler on: the trace's program spans and the
    step profiler's seconds for the same round."""
    import jax

    tmp = tmp_path_factory.mktemp("trace")
    profiling.disable()
    prof = profiling.enable()
    _, sched = _cluster()
    _session(tmp)
    try:
        assert sched.schedule_pending() == 12
    finally:
        jax.profiler.stop_trace()
        sched.close()
        profiling.disable()
    return _host_events(tmp, "pipeline"), prof.step_totals()


def _round(events):
    rounds = [e for e in events if e[0] == "pipeline"]
    assert len(rounds) == 1, rounds
    return rounds[0]


def _step_spans(events):
    return [e for e in events if e[0].startswith("pipeline/")]


# ---------------------------------------------------------------------------
# pipeline spans


@pytest.mark.parametrize("step", PIPELINE_STEPS)
def test_step_span_matches_step_profiler(traced_round, step):
    events, totals = traced_round
    spans = [e for e in _step_spans(events) if e[0] == f"pipeline/{step}"]
    assert len(spans) == 1, spans
    _, s, e, _ = spans[0]
    assert abs((e - s) * 1e-9 - totals[f"pipeline/{step}"]) < 1e-3


def test_step_spans_in_order_and_contiguous(traced_round):
    events, _ = traced_round
    spans = _step_spans(events)
    assert [n for n, *_ in spans] == [f"pipeline/{s}"
                                      for s in PIPELINE_STEPS]
    for (_, _, end, _), (_, start, _, _) in zip(spans, spans[1:]):
        assert 0 <= start - end < 1e6  # ns: the next opens as one closes
    _, rs, re_, _ = _round(events)
    assert rs <= spans[0][1] and spans[-1][2] <= re_
    assert not any("ended_by" in m or "abandoned" in m
                   for *_, m in spans)


def test_round_span_carries_the_round_shape(traced_round):
    *_, meta = _round(traced_round[0])
    assert meta["pods"] == 12
    assert meta["waves"] == 2
    assert meta["bucket"] >= 2


def test_committed_span_carries_commit_parts(traced_round):
    events, totals = traced_round
    (*_, meta), = [e for e in events if e[0] == "pipeline/committed"]
    for part in COMMIT_PARTS:
        assert meta[f"{part}_s"] == pytest.approx(totals[f"commit/{part}"])


def test_step_totals_keys_unchanged_by_a_session(traced_round):
    """The step profiler keys the same steps with or without a profiler
    session: the pipeline's six and the commit parts."""
    _, traced = traced_round
    prof = profiling.enable()
    _, sched = _cluster()
    assert sched.schedule_pending() == 12
    sched.close()
    want = ({f"pipeline/{s}" for s in PIPELINE_STEPS}
            | {f"commit/{p}" for p in COMMIT_PARTS})
    assert set(prof.step_totals()) == want
    assert set(traced) == want


def _wave():
    _, sched = _cluster(pods=4)
    return sched, sched.run_once


def _host_wave():
    _, sched = _cluster(pods=4)
    for _ in range(sched.breaker.threshold):
        sched.breaker.record_failure()  # open: the round goes to the host
    return sched, sched.schedule_pending


def _preempt(device):
    """Two nodes full of low-priority pods and one pod that must evict."""
    store = ObjectStore()
    sched = Scheduler(store, wave_size=4)
    sched.device_preemption = device
    for i in range(2):
        store.create("nodes", make_node(f"n{i}", cpu="2"))
        store.create("pods", make_pod(f"hog-{i}", cpu="2", priority=1))
    assert sched.schedule_pending() == 2
    store.create("pods", make_pod("vip", cpu="2", priority=100))
    return sched, sched.schedule_pending


@pytest.mark.parametrize("phase, plan, setup", [
    ("wave", WAVE_STEPS, _wave),
    ("host wave", HOST_WAVE_STEPS, _host_wave),
    ("preempt chunk", PREEMPT_STEPS, lambda: _preempt(True)),
    ("preempt chunk", PREEMPT_HOST_STEPS, lambda: _preempt(False)),
], ids=["wave", "host-wave", "preempt", "preempt-host"])
def test_traced_path_follows_its_plan(tmp_path, phase, plan, setup):
    """Each traced path takes exactly the steps its plan declares, so each
    span is named by the step that ends it: one "<phase>" span, then one
    span per planned step in order, none flagged ended_by or abandoned."""
    import jax

    sched, run = setup()
    _session(tmp_path)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
        sched.close()
    spans = [(n, m) for n, _, _, m in _host_events(tmp_path)
             if n == phase or n.startswith(phase + "/")]
    assert [n for n, _ in spans if n != phase] == [f"{phase}/{s}"
                                                   for s in plan]
    assert [n for n, _ in spans].count(phase) == 1
    assert not any("ended_by" in m or "abandoned" in m for _, m in spans)


# ---------------------------------------------------------------------------
# Trace on its own


def test_no_session_opens_no_span():
    t = Trace("pipeline of 3", steps=("a", "b"))
    assert t._span is None and t._whole is None
    t.step("a")
    t.step("b")
    assert [m for _, m in t.steps] == ["a", "b"]


def test_spans_named_by_phase_and_step(tmp_path):
    import jax

    _session(tmp_path)
    t = Trace("host wave of 17", steps=("featurized", "host wave"))
    t.step("featurized")
    t.step("host wave")
    jax.profiler.stop_trace()
    names = [n for n, *_ in _host_events(tmp_path, "host wave")]
    assert names == ["host wave", "host wave/featurized",
                     "host wave/host wave"]


def test_off_plan_step_flags_its_span(tmp_path):
    """A retry re-enters the plan at the step it takes: the span that
    step ends keeps the name it opened with and says which step ended
    it; the spans after it are named right again."""
    import jax

    _session(tmp_path)
    t = Trace("pipeline of 1", steps=("a", "b", "c"))
    t.step("a")
    t.step("b")
    t.step("a")  # a retry from the top
    t.step("b")
    t.step("c")
    jax.profiler.stop_trace()
    spans = [(n, m) for n, _, _, m in _host_events(tmp_path, "pipeline/")]
    assert spans == [("pipeline/a", {}), ("pipeline/b", {}),
                     ("pipeline/c", {"ended_by": "a"}),
                     ("pipeline/b", {}), ("pipeline/c", {})]


def test_dropped_trace_marks_its_open_spans(tmp_path):
    import jax

    _session(tmp_path)
    t = Trace("wave of 2", steps=("featurized", "device wave"))
    t.step("featurized")
    del t  # an early return: the trace never reaches its last step
    jax.profiler.stop_trace()
    spans = {n: m for n, _, _, m in _host_events(tmp_path, "wave")}
    assert spans["wave/featurized"] == {}
    assert spans["wave/device wave"] == {"abandoned": 1}
    assert spans["wave"] == {"abandoned": 1}


# ---------------------------------------------------------------------------
# named scopes of the round program


@pytest.fixture(scope="module")
def round_hlo():
    """The round program lowered at tiny caps, Pallas pass included
    (interpreted), with the source locations that carry scope names."""
    from kubernetes_tpu.ops import encoding as enc
    from kubernetes_tpu.ops.kernel import Weights, _schedule_round
    from kubernetes_tpu.ops.scores import stack_weights
    from kubernetes_tpu.state.cache import SchedulerCache
    from kubernetes_tpu.state.featurize import PodFeaturizer
    from kubernetes_tpu.state.snapshot import Snapshot

    N, P, W = 16, 8, 4
    snap = Snapshot(caps=enc.Caps(N=N, P=P))
    cache = SchedulerCache()
    for i in range(4):
        n = make_node(f"n{i}")
        cache.add_node(n)
        snap.set_node(cache.node_infos[n.name])
    pb = PodFeaturizer(snap).featurize([make_pod(f"p{i}") for i in range(P)])
    nt, pm, tt = snap.host_tensors()
    pbs = enc.PodBatch(*[np.stack([a] * W) for a in pb])
    usage = (nt.requested, nt.nonzero, nt.pod_count)
    rows = np.full((W, P), -1, np.int32)
    trows = np.full((W, P, 2), -1, np.int32)
    lowered = _schedule_round.lower(
        nt, pm, tt, pbs, usage, np.int32(0), rows, trows,
        weights=Weights(), num_zones=snap.caps.Z,
        num_label_values=snap.num_label_values, has_ipa=True,
        use_pallas=True, pallas_interpret=True,
        weight_vec=stack_weights(Weights()))
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_round_program_names_scope(round_hlo, scope):
    assert f"/{scope}/" in round_hlo


# ---------------------------------------------------------------------------
# commit counters


@pytest.fixture(scope="module")
def commit_round():
    profiling.disable()
    prof = profiling.enable()
    _, sched = _cluster()
    try:
        assert sched.schedule_pending() == 12
    finally:
        sched.close()
        profiling.disable()
    return prof.step_totals()


@pytest.mark.parametrize("part", COMMIT_PARTS)
def test_commit_part_recorded_with_profiler(commit_round, part):
    assert commit_round[f"commit/{part}"] > 0.0


def test_commit_parts_within_committed_step(commit_round):
    parts = sum(commit_round[f"commit/{p}"] for p in COMMIT_PARTS)
    assert parts <= commit_round["pipeline/committed"]


@pytest.mark.parametrize("on", [False, True])
def test_commit_timed_only_with_profiler(monkeypatch, on):
    """Off, the round hands _commit no accumulator, so nothing is timed;
    on, every commit of the round adds into the round's one list."""
    seen = []
    orig = Scheduler._commit

    def spy(self, pod, node_name):
        seen.append(self._commit_parts)
        return orig(self, pod, node_name)

    monkeypatch.setattr(Scheduler, "_commit", spy)
    if on:
        profiling.enable()
    _, sched = _cluster()
    assert sched.schedule_pending() == 12
    assert sched._commit_parts is None  # cleared at the round's end
    sched.close()
    assert len(seen) == 12
    if on:
        assert all(p is seen[0] for p in seen)
        assert len(seen[0]) == len(COMMIT_PARTS)
    else:
        assert all(p is None for p in seen)
