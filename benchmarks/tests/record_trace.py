"""How benchmarks/tests/data/small_trace.xplane.pb was recorded (on the
chip, PR 22): three rounds of {a chain of matmuls under `bench:work`, a
10 ms sleep under `bench:sleep`}, inside `bench:window`.

    python benchmarks/tests/record_trace.py <out_dir>
"""
import glob
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp

    x = jnp.ones((2048, 2048), jnp.bfloat16)

    @jax.jit
    def work(a):
        for _ in range(4):
            a = jnp.dot(a, a) * 0.0 + 1.0
        return a

    work(x).block_until_ready()
    tmp = os.path.join(out_dir, "tmp_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:work"):
                work(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
