"""Regenerate tools/model_shapes.json — the exact gradient-leaf size
lists for the benchmark model families (ResNet-50, GPT-2 124M).

The scaling bench (tools/bench_scaling.py) pushes synthetic gradients
with the REAL models' leaf-size distribution through the PS fleet, so
partitioning, key routing, and priority scheduling see the true shape of
the load without every fleet process paying a JAX import + model init.

Run: PYTHONPATH=. python tools/dump_model_shapes.py
"""

import json
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from byteps_tpu import models as M  # noqa: E402


def leaf_sizes(model, *init_args):
    params = model.init(jax.random.PRNGKey(0), *init_args)
    # Keep declaration order (tree order), not sorted: priority follows
    # declaration order in the real plugin, so the bench must declare in
    # the same order training would.
    return [int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params)]


def main():
    out = {
        "resnet50": leaf_sizes(
            M.ResNet50(), jnp.zeros((1, 224, 224, 3), jnp.float32)),
        "gpt2_124m": leaf_sizes(
            M.GPT2Small(), jnp.zeros((1, 64), jnp.int32)),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "model_shapes.json")
    with open(path, "w") as f:
        json.dump(out, f)
    for k, v in out.items():
        print(f"{k}: {len(v)} leaves, {sum(v) / 1e6:.1f}M params")


if __name__ == "__main__":
    main()
