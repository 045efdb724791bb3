"""Record ``data/small.xplane.pb``, the trace ``test_trace.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py   # on a machine with a TPU

A window span holding a tool span ``/busy`` (a few matmuls) and a tool
span ``/sleep`` (50 ms with the chip idle).
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))
from benchmarks.chip import trace as tr  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "small.xplane.pb")


@jax.jit
def busy_matmul(x):
    for _ in range(4):
        x = jnp.tanh(x @ x)
    return x


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    busy_matmul(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation(tr.TOOL_PREFIX + "/busy"):
            for _ in range(3):
                x = busy_matmul(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation(tr.TOOL_PREFIX + "/sleep"):
            time.sleep(0.05)
        busy_matmul(x).block_until_ready()
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(tr.find_xplane(d), OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))


if __name__ == "__main__":
    main()
