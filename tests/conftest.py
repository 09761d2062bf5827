import os

# Tests run on the CPU with 8 virtual devices, so multi-chip sharding
# paths are exercised without TPU hardware; the chip itself is driven
# by chip_smoke.py. Backends initialize lazily, so setting the platform
# here works as long as no device op ran yet.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tier-2 tests (tier-1 runs -m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: fault-injection / robustness suite (make chaos)")
    config.addinivalue_line(
        "markers", "chaos: component-kill / control-plane resilience suite "
                   "(make chaos)")
    config.addinivalue_line(
        "markers", "autoscale: cluster-autoscaler suite (NodeGroup "
                   "scale-up/scale-down what-ifs on the device path)")
    config.addinivalue_line(
        "markers", "partition: zone disruption / eviction storm-control "
                   "suite (mass node failure; make chaos)")
    config.addinivalue_line(
        "markers", "observability: flight-recorder / metrics-exposition "
                   "suite (/debug/trace, /metrics, round ledger)")
    config.addinivalue_line(
        "markers", "hostpath: vectorized numpy host twin suite "
                   "(device==host parity, breaker-open degraded waves; "
                   "make chaos)")
    config.addinivalue_line(
        "markers", "mesh: mesh-sharded scheduling plane suite "
                   "(sharded==unsharded parity on the forced 8-device "
                   "CPU mesh; make multichip)")
    config.addinivalue_line(
        "markers", "telemetry: decision observatory / cluster-state "
                   "telemetry suite (score decomposition parity, "
                   "/debug/score, telemetry plane device==twin; "
                   "make obs / make chaos)")
    config.addinivalue_line(
        "markers", "analysis: ktpu-lint static-analysis rule engine "
                   "suite (per-rule historical-bug fixtures + the live "
                   "tree gate behind make lint)")
    config.addinivalue_line(
        "markers", "racecheck: runtime lock-order watcher suite incl. "
                   "the runtime-edges ⊆ static-lock-graph bridge "
                   "(make chaos)")
    config.addinivalue_line(
        "markers", "storm: overload control / storm survival suite "
                   "(priority-aware load shedding, device-dispatch "
                   "watchdog, clock-driven burst SLO gates; tier-1 + "
                   "make chaos)")
    config.addinivalue_line(
        "markers", "shadow: shadow-scoring observatory suite (live "
                   "WeightProfile hot swap/rollback, counterfactual "
                   "divergence, /debug/shadow; make obs / make chaos)")
    config.addinivalue_line(
        "markers", "meshfault: mesh fault-tolerance suite (device-loss "
                   "detection, quarantine/probe, reform ladder "
                   "8->4->2->1->heal, twin salvage parity; make chaos + "
                   "make multichip)")
    config.addinivalue_line(
        "markers", "poison: poison-work isolation suite (input-fault "
                   "attribution vs device faults, wave bisection, pod "
                   "quarantine/re-probe, numeric-integrity sentinels; "
                   "make chaos)")
    config.addinivalue_line(
        "markers", "autopilot: autopilot suite (ledger dataset + ridge "
                   "trainer, shadow/replay promotion gates, regression "
                   "watch auto-rollback, /debug/autopilot; make chaos)")
    config.addinivalue_line(
        "markers", "campaign: chaos-campaign suite (cluster-invariant "
                   "checker, seeded fault-schedule sampling/replay, "
                   "failing-schedule shrinking, KTPU_FAULTPOINTS "
                   "reproducers; make chaos — full budgeted run behind "
                   "make chaos-campaign)")
    config.addinivalue_line(
        "markers", "topology: topology & heterogeneity suite "
                   "(PodTopologySpread kernels, dense rack/superpod/"
                   "accel-gen columns, gang compactness scoring, "
                   "device==twin parity; make chaos + make obs)")
    config.addinivalue_line(
        "markers", "outage: control-plane outage survival suite "
                   "(store-path breaker, disconnected-mode bind spool, "
                   "durable intent journal, crash-restart replay; "
                   "make chaos)")
    config.addinivalue_line(
        "markers", "soak: resource-exhaustion survival suite (HBM "
                   "budget governor, vocab & row compaction, "
                   "capacity-fault OOM recovery, churn-plateau "
                   "regression gates; make chaos + make soak)")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_faultpoints():
    """Fault points are process-global; never let one test's armed
    faults leak into the next."""
    from kubernetes_tpu.utils import faultpoints

    faultpoints.reset()
    yield
    faultpoints.reset()


@pytest.fixture(autouse=True)
def _reset_dispatch_watchdog():
    """A Scheduler with wave_deadline_s > 0 registers its dispatch
    watchdog process-globally; clear it after the test, so a later
    test's first compile is not held to that test's deadline."""
    yield
    from kubernetes_tpu.ops import kernel

    kernel.set_watchdog(None)
