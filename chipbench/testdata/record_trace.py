"""Record ``cpu_trace.xplane.pb``, the small trace the reduction's test reads.

    JAX_PLATFORMS=cpu python3 chipbench/testdata/record_trace.py

Two "requests", each a prefill-like and two segment-like programs with
sleeps between them, under the harness's span names, inside a window span.
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    big = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    small = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((384, 384))
    big(x).block_until_ready()
    small(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        time.sleep(0.02)
        for rid in range(2):
            with jax.profiler.TraceAnnotation("chipbench.generate",
                                              plen=12, max_new=33):
                with jax.profiler.TraceAnnotation("chipbench.prefill",
                                                  tokens=12):
                    y = small(x)
                y.block_until_ready()
                time.sleep(0.01)
                for k in range(2):
                    with jax.profiler.TraceAnnotation(
                            "chipbench.segment", plen=12, first_step=16 * k,
                            steps=16):
                        y = big(x)
                    y.block_until_ready()
                    time.sleep(0.005)
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(HERE, "cpu_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
