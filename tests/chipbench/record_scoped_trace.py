"""Record the small scoped TPU trace the scope reduction's tests read.

    python3 tests/chipbench/record_scoped_trace.py tests/chipbench/data/scoped

On a TPU: one jitted program whose forward runs under the named scope
``trunk`` inside ``jax.vjp`` (so its backward carries
``transpose(jvp(trunk))``), whose loss and cotangent run under ``head``
(each with a matmul of its own, so that the compiler cannot fuse them
into the trunk's), and which adds one op under no scope; run three times,
traced by ``jax.profiler``. Writes ``<out>.xplane.pb``, ``<out>.hlo.txt``
(the compiled module's text) and ``<out>.json`` (the number of runs)."""
import glob
import json
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

RUNS = 3


@jax.jit
def scoped_step(w, v, x):
    def forward(w):
        with jax.named_scope("trunk"):
            return jnp.tanh(x @ w)

    h, backward = jax.vjp(forward, w)
    with jax.named_scope("head"):
        s = h @ v
        loss = jnp.sum(jnp.square(s))
        dh = (2.0 * s) @ v.T
    (dw,) = backward(dh)
    return loss, w - 1e-3 * dw


def main(out: str) -> None:
    w = jnp.full((1024, 1024), 1e-3, jnp.float32)
    v = jnp.full((1024, 1024), 2e-3, jnp.float32)
    x = jnp.ones((2048, 1024), jnp.float32)
    text = scoped_step.lower(w, v, x).compile().as_text()
    jax.block_until_ready(scoped_step(w, v, x))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(RUNS):
        jax.block_until_ready(scoped_step(w, v, x))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(src[0], out + ".xplane.pb")
    with open(out + ".hlo.txt", "w") as f:      # source paths relative
        f.write(text.replace(os.getcwd() + os.sep, ""))
    with open(out + ".json", "w") as f:
        json.dump({"runs": RUNS, "module": "jit_scoped_step"}, f)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
