"""Record the small device trace that test_trace_reduce.py reads.

    python benchmark/tests/record_trace.py   # on the chip

Three runs of a small jitted program, 20 ms of host sleep between them,
inside a `bench_window` annotation, traced; the .xplane.pb is copied to
benchmark/testdata/small.xplane.pb and every device event is printed,
so the expected numbers of the test can be checked by hand.
"""

import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE.parent / "testdata" / "small.xplane.pb"


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = HERE.parent.parent / ".bench_trace" / "testdata"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = sorted(tmp.glob("plugins/profile/*/*.xplane.pb"))[-1]
    OUT.parent.mkdir(exist_ok=True)
    shutil.copy(src, OUT)
    pd = ProfileData.from_file(str(OUT))
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", line.name, len(evs))
            if plane.name.startswith("/device:TPU:0") or any(
                    e.name == "bench_window" for e in evs):
                for e in evs:
                    print("    ", e.name, e.start_ns, e.end_ns)
    print("bytes", OUT.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
