"""Record the small trace that test_trace.py reads (run on the chip):

    python3 -m benchmark.tests.record_trace <out.xplane.pb>

Three ``bench:step`` spans, each one 512x512 f32 matmul program
(``jit_small_gram``) and one elementwise program (``jit_small_scale``),
with a host sleep of 20 ms between steps, so the busy share, the time by
name and the gap attribution are known roughly beforehand.
"""

import shutil
import sys
import tempfile
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import trace as trace_lib

    @jax.jit
    def small_gram(x):
        return jnp.matmul(x.T, x, precision="highest")

    @jax.jit
    def small_scale(g):
        return g * 0.5 + 1.0

    x = jnp.ones((512, 512), jnp.float32)
    small_scale(small_gram(x)).block_until_ready()
    d = tempfile.mkdtemp(prefix="trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:step"):
            small_scale(small_gram(x)).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(trace_lib.find_xplane(d), sys.argv[1])
    shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
