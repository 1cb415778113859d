"""Record ``data/scoped_tpu.xplane.pb``: three calls of a jitted, vmapped
gradient under two ``fl.`` scopes, each call inside an ``fl.`` host
annotation. Run on a TPU from the root of a checkout:

    python3 tests/bench/record_scoped_trace.py <output .xplane.pb>
"""
import glob
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp


def loss(w, x):
    with jax.named_scope("fl.loss"):
        return jnp.sum(jnp.tanh(x @ w) ** 2)


@jax.jit
def step(w, x):
    with jax.named_scope("fl.private"):
        return jax.vmap(jax.grad(loss))(w, x)


def main(out: str) -> int:
    w = jnp.ones((4, 256, 256), jnp.float32)
    x = jnp.ones((4, 128, 256), jnp.float32)
    step(w, x).block_until_ready()   # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            g = step(w, x)
        g.block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0], out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
