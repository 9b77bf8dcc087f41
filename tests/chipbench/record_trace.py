"""Record the small TPU trace the reduction's tests read.

    python3 tests/chipbench/record_trace.py tests/chipbench/data/small

On a TPU: two small programs under host spans, three times, with a 10 ms
host-side pause between them that leaves the chip idle, traced by
``jax.profiler``. Writes ``<out>.xplane.pb`` and ``<out>.json`` (the traced
window's length on the host clock)."""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


@jax.jit
def matmul_tanh(x):
    return jnp.tanh(x @ x)


@jax.jit
def sum_squares(x):
    return jnp.sum(x * x)


def main(out: str) -> None:
    x = jnp.ones((1024, 1024), jnp.float32)
    matmul_tanh(x).block_until_ready()
    sum_squares(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    t0 = time.perf_counter()
    for _ in range(3):
        with TraceAnnotation("bench/phase/matmul"):
            matmul_tanh(x).block_until_ready()
        with TraceAnnotation("bench/phase/pause"):
            time.sleep(0.01)
        with TraceAnnotation("bench/phase/reduce"):
            sum_squares(x).block_until_ready()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(src[0], out + ".xplane.pb")
    with open(out + ".json", "w") as f:
        json.dump({"window_s": window_s, "runs": 3, "pause_s": 0.01}, f)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
