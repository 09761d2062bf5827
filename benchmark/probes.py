"""Compile counter, device memory and device-path checks: copies of
chip_smoke.py's CompileStats, hbm_in_use and device_path_checks, kept
with the yardstick so that a later change to chip_smoke.py cannot move
them. The checks here count fallbacks instead of raising."""

from __future__ import annotations


class CompileStats:
    """Backend compile seconds/count and persistent-cache hits/misses,
    from JAX's own monitoring events, cumulative over the process."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_writes)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, where the backend
    reports it (0 where it does not, as on the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def device_path_fallbacks(sched, expect_path):
    """Names of the ways the run left the device path: chip_smoke's
    device_path_checks, one name per check that failed."""
    from kubernetes_tpu.sched.breaker import CLOSED

    m = sched.metrics
    out = []
    if sched.wave_path() != expect_path:
        out.append(f"wave_path={sched.wave_path()}")
    if m.scheduling_errors.value(stage="pallas"):
        out.append("pallas_demoted")
    if m.scheduling_errors.total():
        out.append("scheduling_errors")
    if m.waves_total.value(path="host"):
        out.append("host_waves")
    if not m.waves_total.value(path="device"):
        out.append("no_device_wave")
    if m.degraded_golden_pods.total():
        out.append("degraded_golden_pods")
    if sched.breaker.state != CLOSED or sched.breaker.trips:
        out.append("breaker")
    if m.capacity_faults.value:
        out.append("capacity_faults")
    return out
