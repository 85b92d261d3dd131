"""A PS worker's host boundary without a fleet, for tests of what crosses
it and when (``tests/test_ps_streaming.py``, ``tests/test_ps_steps.py``): a
recording client stands in for ``st.ps_client``, a recording function for
``jax.device_put``, and leaves that record when their host array is taken
stand in for device arrays."""

import threading
import types
import weakref

import jax
import numpy as np
import pytest

from byteps_tpu.jax import ps


class Leaf:
    """What ``ps.py`` sees of a device array: ``dtype`` / ``size`` / ``shape``,
    ``is_ready``, ``copy_to_host_async`` and ``__array__``, which hands back
    a read-only host copy as ``jax.Array`` does — the one it keeps, or with
    ``fresh`` a new one every time, which lives only as long as its taker
    holds it."""

    def __init__(self, log, index, value, fail=False, ready=True,
                 fresh=False):
        self._log, self._index, self._fail = log, index, fail
        self._ready, self._fresh = ready, fresh
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
        if not self._fresh:
            return self._value
        taken = self._value.copy()
        taken.flags.writeable = False
        return taken


class Client:
    """Handles are 0, 1, 2, ... in enqueue order. The "sum" of two equal
    workers — twice the source as it is THEN — lands in the destination
    when its handle is waited: a buffer put to the device before that
    would carry what it held, a source let go or rewritten before that
    would be missed (a broadcast leaves the root's, this worker's, as they
    are). ``sources`` are the arrays pushed from and ``buffers`` the
    destinations, one of each a handle, the same array for an in-place
    call; with ``weak_sources`` the client keeps no source alive, as the C
    core keeps none, and a wait that finds its source gone says so in
    ``lost``. ``declared`` are the wire names in declaration order,
    ``pushed`` the tensor id and the options of every enqueue, ``threads``
    the names of the threads that have called the client."""

    def __init__(self, log, fail_wait=(), refuse_enqueue=None,
                 weak_sources=False):
        self._log, self._fail_wait = log, set(fail_wait)
        self._refuse, self.buffers, self.wire_dtypes = refuse_enqueue, [], []
        self._weak, self.sources, self.lost = weak_sources, [], []
        self.declared, self.pushed, self.threads = [], [], set()
        self._broadcasts = set()

    def _record(self, source, dest):
        self.sources.append(weakref.ref(source) if self._weak else source)
        self.buffers.append(dest)

    def declare(self, name, nelem, dtype, compression=None):
        self.threads.add(threading.current_thread().name)
        self.declared.append(name)
        self.wire_dtypes.append(np.dtype(dtype).name)
        return len(self.wire_dtypes) - 1

    def push_pull(self, tid, arr, average=True, async_mode=False, out=None):
        self.threads.add(threading.current_thread().name)
        h = len(self.buffers)
        if h == self._refuse:
            raise RuntimeError(f"enqueue {h} refused")
        out = arr if out is None else out
        assert arr.flags.c_contiguous and out.flags.c_contiguous
        assert out.flags.writeable and out.shape == arr.shape
        assert arr.dtype.name == out.dtype.name == self.wire_dtypes[tid]
        assert out is arr or not np.shares_memory(out, arr)
        self._log.append(("enqueue", h))
        self._record(arr, out)
        self.pushed.append((tid, average, async_mode))
        return h

    def broadcast(self, tid, arr, root_rank=0):
        self.threads.add(threading.current_thread().name)
        assert arr.flags.writeable and arr.dtype.name == self.wire_dtypes[tid]
        self._record(arr, arr)
        self._broadcasts.add(len(self.buffers) - 1)
        return len(self.buffers) - 1

    def wait(self, h):
        self.threads.add(threading.current_thread().name)
        self._log.append(("wait", h))
        source = self.sources[h]() if self._weak else self.sources[h]
        if source is None:  # failed or not, the core may read it till now
            self.lost.append(h)
            raise RuntimeError(f"the source of handle {h} was let go")
        if h in self._fail_wait:
            raise RuntimeError(f"handle {h} failed")
        if h not in self._broadcasts:
            np.multiply(source, 2, out=self.buffers[h])


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
                                           l.dtype), fresh=l._fresh)
            for l in tree]


@pytest.fixture
def bridge(monkeypatch):
    """``bridge(sizes, **client)`` → (log, client, tree): the program state
    of a worker in PS mode whose client and ``device_put`` record into
    ``log``; leaf ``i`` holds ``sizes[i]`` float32 of value ``i + 1``
    (``fresh``: a new host copy at every take, see ``Leaf``).
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
             ready=True, fresh=False, uploads=None, real_uploads=False,
             mesh=None, **client_kwargs):
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
                     ready=ready, fresh=fresh)
                for i, n in enumerate(sizes)]
        return log, client, tree

    yield make
    ps.reset_declare_cache()
