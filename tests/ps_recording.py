"""A PS worker's host boundary without a fleet, for tests of what crosses
it and when (``tests/test_ps_streaming.py``, ``tests/test_ps_steps.py``): a
recording client stands in for ``st.ps_client``, a recording function for
``jax.device_put``, and leaves that record when their host array is taken
stand in for device arrays."""

import threading
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
    put to the device before that would carry the unsummed values (a
    broadcast leaves the root's, this worker's, as they are). ``declared``
    are the wire names in declaration order, ``pushed`` the tensor id and
    the options of every enqueue, ``threads`` the names of the threads that
    have called the client."""

    def __init__(self, log, fail_wait=(), refuse_enqueue=None):
        self._log, self._fail_wait = log, set(fail_wait)
        self._refuse, self.buffers, self.wire_dtypes = refuse_enqueue, [], []
        self.declared, self.pushed, self.threads = [], [], set()
        self._broadcasts = set()

    def declare(self, name, nelem, dtype, compression=None):
        self.threads.add(threading.current_thread().name)
        self.declared.append(name)
        self.wire_dtypes.append(np.dtype(dtype).name)
        return len(self.wire_dtypes) - 1

    def push_pull(self, tid, arr, average=True, async_mode=False):
        self.threads.add(threading.current_thread().name)
        h = len(self.buffers)
        if h == self._refuse:
            raise RuntimeError(f"enqueue {h} refused")
        assert arr.flags.writeable and arr.flags.c_contiguous
        assert arr.dtype.name == self.wire_dtypes[tid]
        self._log.append(("enqueue", h))
        self.buffers.append(arr)
        self.pushed.append((tid, average, async_mode))
        return h

    def broadcast(self, tid, arr, root_rank=0):
        self.threads.add(threading.current_thread().name)
        assert arr.flags.writeable and arr.dtype.name == self.wire_dtypes[tid]
        self.buffers.append(arr)
        self._broadcasts.add(len(self.buffers) - 1)
        return len(self.buffers) - 1

    def wait(self, h):
        self.threads.add(threading.current_thread().name)
        self._log.append(("wait", h))
        if h in self._fail_wait:
            raise RuntimeError(f"handle {h} failed")
        if h not in self._broadcasts:
            self.buffers[h] *= 2


class Aliased(np.ndarray):
    """What ``jax.device_put`` returns where the device's memory is the
    host's and the buffer is aligned (the CPU backend): the host buffer
    itself, under an array's name."""

    def devices(self):
        return [types.SimpleNamespace(platform="cpu")]

    def unsafe_buffer_pointer(self):
        return self.ctypes.data


class Uploaded:
    """What ``jax.device_put`` returns on a device with memory of its own: a
    copy of the host buffer as it was at the call, in the order of the puts
    in ``uploads``. ``ready`` False stands for an upload still reading the
    host buffer: ``block_until_ready`` is then logged."""

    def __init__(self, log, uploads, host):
        self._log, self.index, self.ready = log, len(uploads), True
        self.value, self.source = np.array(host), host
        log.append(("put", host.nbytes))
        uploads.append(self)

    def devices(self):
        return [types.SimpleNamespace(platform="tpu")]

    def reshape(self, shape):
        assert shape == self.value.shape
        return self

    def astype(self, dtype):
        assert dtype == self.value.dtype
        return self

    def is_deleted(self):
        return False

    def block_until_ready(self):
        if not self.ready:
            self._log.append(("block", self.index))
            self.ready = True
        return self


def retake(tree, scale):
    """The same tree signature with other values (leaf i: scale × (i + 1))."""
    return [Leaf(l._log, l._index, np.full(l.shape, scale * (l._index + 1),
                                           l.dtype)) for l in tree]


@pytest.fixture
def bridge(monkeypatch):
    """``bridge(sizes, **client)`` → (log, client, tree): the program state
    of a worker in PS mode whose client and ``device_put`` record into
    ``log``; leaf ``i`` holds ``sizes[i]`` float32 of value ``i + 1``.
    ``real_uploads`` puts a copy of the host buffer on the CPU device, a
    ``jax.Array`` a program can take; ``mesh`` is the state's, for a step
    builder."""
    log = []
    real_put = jax.device_put
    monkeypatch.delenv("BYTEPS_COMPRESSOR", raising=False)

    def device_put(x):  # one array or a list of them: the order is the point
        log.extend(("put", a.nbytes) for a in (x if isinstance(x, list)
                                               else [x]))
        return x if isinstance(x, list) else x.view(Aliased)

    monkeypatch.setattr(jax, "device_put", device_put)
    ps.reset_declare_cache()

    def put_copy(x):
        log.extend(("put", a.nbytes) for a in (x if isinstance(x, list)
                                               else [x]))
        return real_put(jax.tree_util.tree_map(np.array, x))

    def make(sizes, *, compressor="", dtype=np.float32, lost_leaf=None,
             ready=True, uploads=None, real_uploads=False, mesh=None,
             **client_kwargs):
        client = Client(log, **client_kwargs)
        monkeypatch.setattr(ps.bps, "_st", lambda: types.SimpleNamespace(
            ps_client=client, mesh=mesh, config=types.SimpleNamespace(
                enable_async=False, compressor=compressor, dcn_axis="dcn",
                ici_axis="ici")))
        if uploads is not None:  # a device that copies, as the TPU does
            monkeypatch.setattr(jax, "device_put", lambda x: Uploaded(
                log, uploads, x))
        if real_uploads:
            monkeypatch.setattr(jax, "device_put", put_copy)
        tree = [Leaf(log, i, np.full((n,), i + 1, dtype), fail=i == lost_leaf,
                     ready=ready)
                for i, n in enumerate(sizes)]
        return log, client, tree

    yield make
    ps.reset_declare_cache()
