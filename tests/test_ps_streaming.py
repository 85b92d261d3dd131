"""The PS leg streams leaf by leaf (``byteps_tpu/jax/ps.py``): a leaf is
enqueued into the C core as soon as it is on the host and put back to the
device as soon as its handle has settled, and every enqueued handle is
settled before an error leaves. No fleet here: a recording client stands in
for ``st.ps_client``, a recording function for ``jax.device_put``, and leaves
that record when their host array is taken stand in for device arrays. The
loopback fleet checks the numbers (``tests/_ps_worker.py``, ``jax_stream``).
"""

import types

import jax
import numpy as np
import pytest

from byteps_tpu.jax import ps


class Leaf:
    """What ``ps.py`` sees of a device array: ``dtype`` / ``size`` / ``shape``,
    ``is_ready``, ``copy_to_host_async`` and ``__array__``, which hands back
    a read-only host copy as ``jax.Array`` does."""

    def __init__(self, log, index, value, fail=False, ready=True):
        self._log, self._index, self._fail = log, index, fail
        self._ready = ready
        self._value = np.asarray(value)
        self._value.flags.writeable = False
        self.dtype, self.size = self._value.dtype, self._value.size
        self.shape = self._value.shape

    def is_ready(self):
        return self._ready

    def copy_to_host_async(self):
        self._log.append(("d2h", self._index))

    def __array__(self, dtype=None, copy=None):
        self._log.append(("take", self._index))
        if self._fail:
            raise RuntimeError(f"leaf {self._index} lost")
        return self._value


class Client:
    """Handles are 0, 1, 2, ... in enqueue order. The "sum" of two equal
    workers lands in the staged buffer when its handle is waited — a buffer
    put to the device before that would carry the unsummed values."""

    def __init__(self, log, fail_wait=(), refuse_enqueue=None):
        self._log, self._fail_wait = log, set(fail_wait)
        self._refuse, self.buffers, self.wire_dtypes = refuse_enqueue, [], []

    def declare(self, name, nelem, dtype, compression=None):
        self.wire_dtypes.append(np.dtype(dtype).name)
        return len(self.wire_dtypes) - 1

    def push_pull(self, tid, arr, average=True, async_mode=False):
        h = len(self.buffers)
        if h == self._refuse:
            raise RuntimeError(f"enqueue {h} refused")
        assert arr.flags.writeable and arr.flags.c_contiguous
        assert arr.dtype.name == self.wire_dtypes[tid]
        self._log.append(("enqueue", h))
        self.buffers.append(arr)
        return h

    def wait(self, h):
        self._log.append(("wait", h))
        if h in self._fail_wait:
            raise RuntimeError(f"handle {h} failed")
        self.buffers[h] *= 2


@pytest.fixture
def bridge(monkeypatch):
    """``bridge(sizes, **client)`` → (log, client, tree): the program state
    of a worker in PS mode whose client and ``device_put`` record into
    ``log``; leaf ``i`` holds ``sizes[i]`` float32 of value ``i + 1``."""
    log = []
    monkeypatch.delenv("BYTEPS_COMPRESSOR", raising=False)

    def device_put(x):  # one array or a list of them: the order is the point
        log.extend(("put", a.nbytes) for a in (x if isinstance(x, list)
                                               else [x]))
        return x

    monkeypatch.setattr(jax, "device_put", device_put)
    ps.reset_declare_cache()

    def make(sizes, *, compressor="", dtype=np.float32, lost_leaf=None,
             ready=True, **client_kwargs):
        client = Client(log, **client_kwargs)
        monkeypatch.setattr(ps.bps, "_st", lambda: types.SimpleNamespace(
            ps_client=client, config=types.SimpleNamespace(
                enable_async=False, compressor=compressor)))
        tree = [Leaf(log, i, np.full((n,), i + 1, dtype), fail=i == lost_leaf,
                     ready=ready)
                for i, n in enumerate(sizes)]
        return log, client, tree

    yield make
    ps.reset_declare_cache()


SIZES = {"two": [3, 5], "five": [1, 2, 3, 4, 50], "gpt2-like": [768] * 195
         + [50257]}


@pytest.mark.parametrize("ready", [True, False], ids=["ready", "running"])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_first_enqueue_precedes_last_landing(bridge, sizes, ready):
    """(a) Every D2H is started before any leaf is taken — in declaration
    order for a tree that is ready, in reverse while its program still runs
    (the runtime then starts the newest first) — and the first enqueue is
    issued before the last leaf's host array is taken: the round starts
    with the first leaf, not after the last."""
    log, _, tree = bridge(sizes, ready=ready)
    out = ps.ps_push_pull(tree, average=False)
    last = len(sizes) - 1
    order = range(len(sizes)) if ready else reversed(range(len(sizes)))
    assert log[:len(sizes)] == [("d2h", i) for i in order]
    assert log.index(("enqueue", 0)) < log.index(("take", last))
    # in declaration order, each leaf enqueued right after it was taken
    stage = [e for e in log if e[0] in ("take", "enqueue")][1:]
    assert stage == [(kind, i) for i in range(len(sizes))
                     for kind in ("take", "enqueue")]
    for i, leaf in enumerate(out):
        np.testing.assert_array_equal(leaf, np.full((sizes[i],), 2 * (i + 1),
                                                    np.float32))


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_first_put_precedes_last_settle(bridge, sizes):
    """(b) The first ``device_put`` is issued before the last handle is
    waited, and no leaf is put before its own handle has settled."""
    log, _, tree = bridge(sizes)
    ps.ps_push_pull(tree, average=False)
    last = len(sizes) - 1
    settle = [e for e in log if e[0] in ("wait", "put")]
    assert settle.index(("put", 4 * sizes[0])) < settle.index(("wait", last))
    assert settle == [e for i in range(len(sizes))
                      for e in (("wait", i), ("put", 4 * sizes[i]))]
    assert log.index(("enqueue", last)) < log.index(("wait", 0))


@pytest.mark.parametrize("failed", [[0], [2, 3], [4]],
                         ids=["first", "middle-two", "last"])
def test_failed_handle_settles_all_and_puts_nothing_more(bridge, failed):
    """(c) A handle fails: every handle is still waited, nothing is put
    after the failure, and the first error is the one raised."""
    sizes = SIZES["five"]
    log, _, tree = bridge(sizes, fail_wait=failed)
    with pytest.raises(RuntimeError, match=f"handle {failed[0]} failed"):
        ps.ps_push_pull(tree, average=False)
    assert [h for kind, h in log if kind == "wait"] == list(range(len(sizes)))
    after = log[log.index(("wait", failed[0])):]
    assert not [e for e in after if e[0] == "put"]
    assert [e for e in log if e[0] == "put"] == [
        ("put", 4 * n) for n in sizes[:failed[0]]]


@pytest.mark.parametrize("k", [0, 1, 3])
@pytest.mark.parametrize("where", ["enqueue", "landing"])
def test_error_while_enqueueing_settles_what_is_in_flight(bridge, where, k):
    """(d) An exception in the enqueue loop with k leaves already in flight:
    those k handles are waited before it leaves (their staging buffers are
    the C core's until then) — even when one of them fails too — nothing is
    put, and the loop's own error is the one raised."""
    sizes = SIZES["five"]
    kwargs = ({"refuse_enqueue": k} if where == "enqueue"
              else {"lost_leaf": k})
    log, _, tree = bridge(sizes, fail_wait=[0], **kwargs)
    message = f"enqueue {k} refused" if where == "enqueue" else f"leaf {k} lost"
    with pytest.raises(RuntimeError, match=message):
        ps.ps_push_pull(tree, average=False)
    assert [h for kind, h in log if kind == "enqueue"] == list(range(k))
    assert [h for kind, h in log if kind == "wait"] == list(range(k))
    assert not [e for e in log if e[0] == "put"]


@pytest.mark.parametrize("sizes,early", [
    ([7], 0), ([3, 5], 3), ([1, 2, 3, 4, 50], 10), (SIZES["gpt2-like"],
                                                    195 * 768)],
    ids=["one-leaf", "two", "five", "gpt2-like"])
def test_put_early_bytes(bridge, sizes, early):
    """(e) ``put_stats``: all but the last leaf's bytes were put before the
    last handle settled; for a tree whose last leaf is the largest that is
    the share the round hides."""
    _, _, tree = bridge(sizes)
    ps.ps_push_pull(tree, average=False)
    assert ps.put_stats == {"put_early_bytes": 4 * early,
                            "bytes": 4 * sum(sizes)}


@pytest.mark.parametrize("dtype,compressor,wire", [
    ("float32", "", "float32"), ("bfloat16", "", "bfloat16"),
    ("bfloat16", "onebit", "float32"), ("int32", "onebit", "int32")])
def test_wire_dtype_and_put_dtype(bridge, dtype, compressor, wire):
    """Half precision with a codec is upcast on stage and downcast before
    the put; everything else crosses in its own dtype."""
    dtype = jax.numpy.dtype(dtype)
    log, client, tree = bridge([4, 6], dtype=dtype, compressor=compressor)
    out = ps.ps_push_pull(tree, average=False)
    assert client.wire_dtypes == [wire, wire]
    assert [e for e in log if e[0] == "put"] == [
        ("put", 4 * dtype.itemsize), ("put", 6 * dtype.itemsize)]
    for i, leaf in enumerate(out):
        assert leaf.dtype == dtype
        np.testing.assert_array_equal(leaf, np.full((len(leaf),), 2 * (i + 1),
                                                    dtype))


def test_host_scalars_take_the_same_path(bridge):
    """Python and numpy scalars (a metric average) have no transfer to
    start and go through the same two loops."""
    log, client, _ = bridge([])
    out = ps.ps_push_pull({"a": 1.5, "b": np.float32(2.0), "c": 3},
                          average=False)
    assert [e[0] for e in log] == ["enqueue"] * 3 + ["wait", "put"] * 3
    assert {k: float(v) for k, v in out.items()} == {"a": 3.0, "b": 4.0,
                                                     "c": 6.0}
    assert all(np.shape(v) == () for v in out.values())
