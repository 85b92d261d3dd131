"""The step builders that cross the host boundary their own way — the async
step (``training.py``) and the bucketed step (``bucketed.py``) — cross it
through ``ps.py``'s binding: the tensors are declared once, shape-signed, on
the bridge thread; leaves are staged into the pool; an error leaves after
every handle in flight has settled. No fleet: ``tests/ps_recording.py``'s
client, whose "sum" is twice what was pushed. The loopback fleet checks the
numbers (``tests/test_ps_core.py``, ``-m ps``)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.jax import ps
from byteps_tpu.jax.bucketed import make_bucketed_overlap_step
from byteps_tpu.jax.training import make_async_train_step
from tests.ps_recording import bridge  # noqa: F401
from tests.ps_utils import REPO

PARAMS = {"a": np.full((6,), 1.0, np.float32),
          "b": np.full((1, 2, 3), 2.0, np.float32),  # crosses flat: ps_grad_step
          "c": np.full((5,), 3.0, np.float32)}
NBYTES = 4 * (6 + 6 + 5)
LR = 0.5


def loss_fn(params, batch):
    """Gradients 1·x̄, 2·x̄ and 3·x̄ (x̄ the batch mean, 1 here)."""
    x = jnp.mean(batch)
    return x * (jnp.sum(params["a"]) + 2 * jnp.sum(params["b"])
                + 3 * jnp.sum(params["c"]))


def one_chip_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("dcn", "ici"))


def on_the_bridge_only(client):
    return client.threads and all(name.startswith("bps_bridge")
                                  for name in client.threads)


def test_async_step_pushes_to_what_the_broadcast_declared(bridge):
    """(i) The broadcast declares the parameters' tensors; the steps declare
    nothing more and push every delta to those ids, sum not mean, async, on
    the bridge thread; what comes back is the tree the "server" holds."""
    _, client, _ = bridge([], real_uploads=True)
    tx = optax.sgd(LR)
    params, step = make_async_train_step(loss_fn, tx, dict(PARAMS))
    seeded = list(client.declared)
    assert len(seeded) == 3 and not client.pushed
    opt_state = tx.init(params)
    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert client.declared == seeded and ps.declare_steps >= 1
    assert client.pushed == [(tid, False, True) for tid in (0, 1, 2)] * 2
    assert on_the_bridge_only(client)
    for k, grad in zip("abc", (1, 2, 3)):  # twice the delta, leaf by leaf
        assert params[k].shape == PARAMS[k].shape
        np.testing.assert_array_equal(params[k], 2 * -LR * grad)


def test_async_steps_second_round_reuses_every_slot(bridge):
    """(i) The async step pulls into the pool: nothing reused in step 1,
    every byte in step 2, into the same memory — and pushes every byte
    from where it landed."""
    _, client, _ = bridge([], real_uploads=True)
    tx = optax.sgd(LR)
    params, step = make_async_train_step(loss_fn, tx, dict(PARAMS))
    opt_state = tx.init(params)
    params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert ps.stage_stats == {"direct_bytes": NBYTES, "reused_bytes": 0,
                              "bytes": NBYTES}
    params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert ps.stage_stats == {"direct_bytes": NBYTES,
                              "reused_bytes": NBYTES, "bytes": NBYTES}
    pushes = client.buffers[3:]  # after the broadcast's three
    for first, second in zip(pushes[:3], pushes[3:]):
        assert first.ctypes.data == second.ctypes.data
    # pushed from the deltas' landed arrays, pulled into the slots
    for source, dest in zip(client.sources[3:], pushes):
        assert not source.flags.writeable and source is not dest


def bucketed(bridge, **client_kwargs):
    """Three leaves in two buckets, [a] and [b, c]: the program of b and c
    is pushed first (handles 0 and 1), then a's (handle 2)."""
    log, client, _ = bridge([], real_uploads=True, mesh=one_chip_mesh(),
                            **client_kwargs)
    tx = optax.sgd(LR)
    step = make_bucketed_overlap_step(loss_fn, tx, n_buckets=2,
                                      donate=False)
    params = jax.tree_util.tree_map(jnp.asarray, PARAMS)
    return log, client, step, params, tx.init(params)


def test_bucketed_step_declares_each_leaf_once_shape_signed(bridge):
    """(ii) One declare per leaf, in tree order under one shape signature,
    on the bridge thread as every push and wait; none in the second step."""
    _, client, step, params, opt_state = bucketed(bridge)
    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert len(client.declared) == 3
    found = [re.fullmatch(r"bgrad_([0-9a-f]{8})_(\d)", n)
             for n in client.declared]
    assert all(found) and len({m.group(1) for m in found}) == 1
    assert [m.group(2) for m in found] == ["0", "1", "2"]
    # bucket [b, c] first, then [a]; each to its own tensor, twice
    assert [tid for tid, _, _ in client.pushed] == [1, 2, 0, 1, 2, 0]
    assert on_the_bridge_only(client)


def test_bucketed_steps_second_round_reuses_every_slot(bridge):
    """(ii) The pieces of a round add up in ``stage_stats``; step 2 pulls
    every leaf into the buffer step 1 left for it, and both push every
    leaf from where it landed."""
    _, client, step, params, opt_state = bucketed(bridge)
    params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert ps.stage_stats == {"direct_bytes": NBYTES, "reused_bytes": 0,
                              "bytes": NBYTES}
    params, opt_state, _ = step(params, opt_state, np.ones((4,)))
    assert ps.stage_stats == {"direct_bytes": NBYTES,
                              "reused_bytes": NBYTES, "bytes": NBYTES}
    for first, second in zip(client.buffers[:3], client.buffers[3:]):
        assert first.ctypes.data == second.ctypes.data
    for source, dest in zip(client.sources, client.buffers):
        assert not source.flags.writeable and source is not dest
    # b, of three axes, left its program flat and has its shape again
    assert [s.shape for s in client.sources[:3]] == [(6,), (5,), (6,)]
    assert params["b"].shape == (1, 2, 3)


@pytest.mark.parametrize("refused", [1, 2])
def test_bucketed_refusal_settles_the_earlier_buckets_handles(bridge,
                                                              refused):
    """(ii) An enqueue is refused, in the first bucket or (2) the second's
    only one: what had been enqueued — the whole first bucket — is waited
    before the error leaves, even when one of those fails too, nothing is
    put, and the next step runs."""
    log, client, step, params, opt_state = bucketed(
        bridge, refuse_enqueue=refused, fail_wait=[0])
    with pytest.raises(RuntimeError, match=f"enqueue {refused} refused"):
        step(params, opt_state, np.ones((4,)))
    assert [h for kind, h in log if kind == "wait"] == list(range(refused))
    assert not [e for e in log if e[0] == "put"]
    client._refuse, client._fail_wait = None, set()
    params, _, _ = step(params, opt_state, np.ones((4,)))
    np.testing.assert_array_equal(params["a"], 1.0 - LR * 2 * 1)


def test_bucketed_step_returns_the_leaves_in_tree_order(bridge):
    """(ii) Pushed b, c, a; waited and put a, b, c; each leaf updated by
    twice its own gradient in its own shape."""
    log, _, step, params, opt_state = bucketed(bridge)
    params, opt_state, loss = step(params, opt_state, np.ones((4,)))
    assert float(loss) == 6 * 1 + 2 * 6 * 2 + 3 * 5 * 3
    settle = [e for e in log if e[0] in ("wait", "put")]
    assert settle == [("wait", 2), ("put", 24), ("wait", 0), ("put", 24),
                      ("wait", 1), ("put", 20)]
    for k, grad in zip("abc", (1, 2, 3)):
        assert params[k].shape == PARAMS[k].shape
        np.testing.assert_array_equal(params[k], PARAMS[k] - LR * 2 * grad)


@pytest.mark.parametrize("where", ["byteps_tpu", "tools"])
def test_no_file_borrows_a_private_name_of_ps(where):
    """(iii) How a tree crosses the host boundary is ``ps.py``'s alone: no
    ``from byteps_tpu.jax.ps import _…`` and no ``ps._…`` elsewhere."""
    borrowed = re.compile(r"ps import .*\b_|\bps\._[a-z]")
    hits = []
    for root, _, names in os.walk(os.path.join(REPO, where)):
        for name in names:
            path = os.path.join(root, name)
            if not name.endswith(".py") or path.endswith("jax/ps.py"):
                continue
            with open(path) as f:
                hits += [f"{path}:{n}: {line.strip()}"
                         for n, line in enumerate(f, 1)
                         if borrowed.search(line)]
    assert not hits, hits
