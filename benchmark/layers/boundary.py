"""Layer: host boundary (``byteps_tpu/jax/ps.py``).

A probe before the window, from outside the program (it has no spans of its
own yet): ``device_get`` then ``device_put`` of a float32 tree with the
configuration's own gradient leaf shapes — what a PS step moves each way —
with a fresh device tree per repetition, because a ``jax.Array`` keeps its
host copy after the first ``device_get``. Median of 5. After
``bench_ps.host_boundary_microbench``, which moves one contiguous buffer."""

import statistics
import time

LAYER = "host boundary"
METRICS = {
    "boundary.tree_roundtrip_ms": {"unit": "ms", "better": "lower",
                                   "source": "host_clock",
                                   "moves": "step_ms_p50"},
}
REPEATS = 5


def setup(run):
    import jax
    import jax.numpy as jnp

    shapes = [s for s, _ in run.param_shapes]

    @jax.jit
    def make(key):
        return [jnp.full(s, 1.0, jnp.float32) + jax.random.uniform(key, ())
                for s in shapes]

    times = []
    for i in range(REPEATS):
        tree = jax.block_until_ready(make(jax.random.PRNGKey(i)))
        t = time.perf_counter()
        host = jax.device_get(tree)
        back = jax.block_until_ready(jax.device_put(host))
        times.append(time.perf_counter() - t)
        del tree, host, back
    run.probes["boundary.tree_roundtrip_ms"] = 1e3 * statistics.median(times)


def read(run):
    return {"boundary.tree_roundtrip_ms":
            run.probes.get("boundary.tree_roundtrip_ms")}
