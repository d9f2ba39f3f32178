#!/usr/bin/env python3
"""Records the small trace the reduction's test reads
(tests/perfbench/sample.xplane.pb): four steps of a jitted matmul on one
TPU, a host pause between them, the benchmark's window annotation around
steps 1-3.  Run once on the chip:

    chiprun -- python3 tests/perfbench/record_sample_trace.py chiprun_out/sample
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir):
    import jax
    import jax.numpy as jnp

    from perfbench.harness import trace as T

    assert jax.devices()[0].platform == "tpu", "records on the chip only"

    @jax.jit
    def sample_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    sample_step(x).block_until_ready()
    # the profiler by hand, not T.capture(): the first step has to lie
    # outside the window annotation, which capture() opens at once
    log = os.path.join(out_dir, "log")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=options)
    sample_step(x).block_until_ready()
    with jax.profiler.TraceAnnotation(T.WINDOW_MARK):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("sample/pause"):
                time.sleep(0.002)
            float(sample_step(x))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "sample.xplane.pb"))
    print(path, os.path.getsize(path))
    shutil.rmtree(log)


if __name__ == "__main__":
    main(sys.argv[1])
