"""The plain reference step, and the comparison that decides ``correct``.

The reference is ``value_and_grad`` → optimizer → ``apply_updates`` under one
``jax.jit`` on one device, nothing of ``byteps_tpu`` in it, over the
configuration's plain forward pass (``benchmark/lib/plain_transformer.py``).
Its loss is a weighted sum over positions, the weights worked out on the
host, so the global batch of a four-chip cell can be accumulated over
micro-batches on one chip and still be the exact quantity the program
reports (the mean over chips of each chip's own mean).
"""

from __future__ import annotations

import math
from functools import partial

# |loss - reference loss| at each compared step. PR 21 saw <= 2.5e-4 on the
# chip between the program's step and a plain one in every mode (different
# XLA programs over bf16 matmuls, another summation order across chips). At
# a loss near ln(vocab) ~ 10.6, 2e-3 passes that rounding and fails a lost
# or doubled gradient (steps 1 and 2 move by > 1e-2) or a bf16 wire.
LOSS_TOL = 2e-3
COMPARED_STEPS = 3


def make_reference_step(weighted_loss, tx, micro_batches: int):
    """``step(params, opt_state, batch) -> (params, opt_state, loss)``;
    ``batch`` carries a ``weight`` leaf and ``weighted_loss(params, batch)``
    is a sum over its rows, so micro-batch losses and gradients add."""
    import jax
    import jax.numpy as jnp
    import optax

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        if micro_batches == 1:
            loss, grads = jax.value_and_grad(weighted_loss)(params, batch)
        else:
            chunks = jax.tree_util.tree_map(
                lambda x: x.reshape(micro_batches, -1, *x.shape[1:]), batch)

            def body(acc, chunk):
                out = jax.value_and_grad(weighted_loss)(params, chunk)
                return jax.tree_util.tree_map(jnp.add, acc, out), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(jnp.zeros_like, params))
            (loss, grads), _ = jax.lax.scan(body, zero, chunks)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def reference_losses(step, params, opt_state, batches) -> list:
    """Run the reference over ``batches`` from ``params``; only the losses
    are kept, the state is donated step to step and dropped at the end."""
    import jax

    losses = []
    for batch in batches:
        params, opt_state, loss = step(params, opt_state,
                                       jax.device_put(batch))
        losses.append(float(loss))
    del params, opt_state
    return losses


def compare_losses(losses, ref) -> dict:
    """``ok`` only if both lists have ``COMPARED_STEPS`` finite entries that
    agree within ``LOSS_TOL``."""
    n = COMPARED_STEPS
    usable = (len(losses) >= n and len(ref) >= n
              and all(math.isfinite(x) for x in [*losses[:n], *ref[:n]]))
    diff = (max(abs(a - b) for a, b in zip(losses[:n], ref[:n]))
            if usable else math.inf)
    return {"ok": diff <= LOSS_TOL, "max_loss_diff": diff, "tol": LOSS_TOL,
            "losses": list(losses[:n]), "reference_losses": list(ref[:n])}
