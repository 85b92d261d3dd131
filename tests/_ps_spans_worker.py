"""Worker of tests/test_ps_spans.py: two PS-mode training steps under a
``jax.profiler`` capture; prints the capture's ``bps.*`` events and the C
core's round rows as one JSON line for the test to judge.
``BPS_SPANS_BUILDER`` picks the step design (``serial``, the default:
``make_train_step``; ``bucketed``).
``BPS_SPANS_WORKER_ROUNDSTATS`` sets ``BYTEPS_ROUNDSTATS_ON`` for this process
alone, where the fleet's other roles got another value."""

import functools
import glob
import json
import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import byteps_tpu.jax as bps  # noqa: E402
from byteps_tpu.jax.bucketed import make_bucketed_overlap_step  # noqa: E402
from byteps_tpu.jax.training import make_train_step  # noqa: E402

BUILDERS = {"serial": make_train_step,
            "bucketed": functools.partial(make_bucketed_overlap_step,
                                          n_buckets=2)}


def main() -> int:
    trace_dir = os.environ["BPS_SPANS_DIR"]
    if "BPS_SPANS_WORKER_ROUNDSTATS" in os.environ:
        os.environ["BYTEPS_ROUNDSTATS_ON"] = os.environ[
            "BPS_SPANS_WORKER_ROUNDSTATS"]
    bps.init()
    try:
        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean(
                ((x @ params["a"]) @ params["w"] + params["b"] - y) ** 2)

        tx = optax.sgd(0.05)
        step = BUILDERS[os.environ.get("BPS_SPANS_BUILDER", "serial")](
            loss_fn, tx)
        # three leaves, 4096 + 32 + 512 bytes: of two buckets [a] and [b, w]
        params = {"a": jnp.full((64, 16), 0.01, jnp.float32),
                  "b": jnp.zeros((8,), jnp.float32),
                  "w": jnp.full((16, 8), 0.01, jnp.float32)}
        opt_state = tx.init(params)
        prng = np.random.default_rng(3)

        def batch():
            x = jnp.asarray(prng.standard_normal((16, 64)), jnp.float32)
            return x, x[:, :8] * 0.5

        params, opt_state, _ = step(params, opt_state, batch())  # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        mono = [time.monotonic_ns()]
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(2):
                params, opt_state, loss = step(params, opt_state, batch())
            float(loss)
        finally:
            jax.profiler.stop_trace()
        mono.append(time.monotonic_ns())
        from byteps_tpu.core import ffi
        rounds = ffi.round_summary()["rounds"]
    finally:
        bps.shutdown()
    (xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(xplane)
    events = [{"plane": plane.name, "line": i, "name": ev.name,
               "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns),
               "stats": {k: v for k, v in ev.stats}}
              for plane in data.planes for i, line in enumerate(plane.lines)
              for ev in line.events if ev.name.startswith("bps.")]
    print(json.dumps({"events": events, "mono_ns": mono, "rounds": rounds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
