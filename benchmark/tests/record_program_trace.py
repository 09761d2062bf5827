"""Record the small device trace that test_program_trace.py reads.

    python benchmark/tests/record_program_trace.py   # on the chip

A program named like the round (`_schedule_round`, so its XLA module is
jit__schedule_round) with a `wave_dense` and a `pod_scan` named scope,
and a scoped program of another module, run inside a `bench_window`
annotation with program spans on the host as utils/trace.Trace opens
them:

- pipeline/executed (10 ms of host sleep, then the round and its wait);
- 20 ms of sleep and the round again, outside any program span;
- pipeline/executed carrying ended_by (an off-plan span readers skip)
  around 10 ms of sleep and the other program.

The .xplane.pb is copied to benchmark/testdata/program.xplane.pb, and
every device event, every program span and the scope xprof's op paths
give each op are printed, so the expected numbers of the test can be worked by hand.
"""

import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
OUT = BENCH / "testdata" / "program.xplane.pb"
sys.path.insert(0, str(BENCH))


def _schedule_round(x):
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.named_scope("wave_dense"):
        y = jnp.tanh(x @ x)

    def step(c, row):
        c = c * 0.5 + row
        return c, jnp.max(c)

    with jax.named_scope("pod_scan"):
        c, m = lax.scan(step, jnp.zeros(x.shape[1], x.dtype), y[:4])
    return y.sum() + c.sum() + m.sum()


def _other(x):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("pod_scan"):
        return jnp.sin(x @ x).sum()


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    import program_trace

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    rnd, other = jax.jit(_schedule_round), jax.jit(_other)
    x = jnp.ones((512, 512), jnp.float32) * 1e-3
    rnd(x).block_until_ready()
    other(x).block_until_ready()
    tmp = BENCH.parent / ".bench_trace" / "testdata"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with TraceAnnotation("bench_window"):
        with TraceAnnotation("pipeline/executed"):
            time.sleep(0.01)
            rnd(x).block_until_ready()
        time.sleep(0.02)
        rnd(x).block_until_ready()
        with TraceAnnotation("pipeline/executed", ended_by="fetched"):
            time.sleep(0.01)
            other(x).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    OUT.parent.mkdir(exist_ok=True)
    shutil.copy(src, OUT)
    pd = ProfileData.from_file(str(OUT))
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                print("line", line.name)
                for e in line.events:
                    print("   ", e.name[:90], e.start_ns, e.end_ns)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (e.name == "bench_window"
                            or e.name.startswith("pipeline/")):
                        print("span", e.name, e.start_ns, e.end_ns,
                              dict(e.stats))
    # read from the recorded copy: xprof writes its op stats beside the
    # file it reads
    for k, v in sorted(program_trace._op_scopes(
            program_trace._key(src)).items()):
        print("op", k, v)
    print("bytes", OUT.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
