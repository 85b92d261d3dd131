"""The PS leg streams leaf by leaf (``byteps_tpu/jax/ps.py``): a leaf is
enqueued into the C core as soon as it is on the host and put back to the
device as soon as its handle has settled, and every enqueued handle is
settled before an error leaves. A leaf that lands in its wire form is pushed
from where it landed and pulled into its tensor's slot; any other is copied
into the slot first. No fleet here: a recording client stands in
for ``st.ps_client``, a recording function for ``jax.device_put``, and leaves
that record when their host array is taken stand in for device arrays. The
loopback fleet checks the numbers (``tests/_ps_worker.py``, ``jax_stream``).
"""

import gc
import types
import weakref

import jax
import numpy as np
import pytest

from byteps_tpu.core import ffi
from byteps_tpu.jax import ps
from tests.ps_recording import Client, Leaf, bridge, retake  # noqa: F401

SIZES = {"two": [3, 5], "five": [1, 2, 3, 4, 50], "gpt2-like": [768] * 195
         + [50257]}
# How a leaf reaches the wire: pushed from its landed array as it stands, or
# copied into its slot first (here upcast: bfloat16 under a codec's float32
# wire) and pushed from there in place. Leaves land ``fresh`` and the client
# keeps no source alive, so only ``ps.py`` can.
BF16 = jax.numpy.dtype("bfloat16")
PATHS = {"direct": {"dtype": np.dtype(np.float32)},
         "copied": {"dtype": BF16, "compressor": "onebit"}}
HELD = {"fresh": True, "weak_sources": True}
by_path = pytest.mark.parametrize("path", PATHS.values(), ids=PATHS.keys())


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


@by_path
@pytest.mark.parametrize("failed", [[0], [2, 3], [4]],
                         ids=["first", "middle-two", "last"])
def test_failed_handle_settles_all_and_puts_nothing_more(bridge, failed,
                                                         path):
    """(c) A handle fails: every handle is still waited — each with its
    source still held, the failed ones and those after them too — nothing
    is put after the failure, and the first error is the one raised."""
    sizes = SIZES["five"]
    log, client, tree = bridge(sizes, fail_wait=failed, **path, **HELD)
    with pytest.raises(RuntimeError, match=f"handle {failed[0]} failed"):
        ps.ps_push_pull(tree, average=False)
    assert [h for kind, h in log if kind == "wait"] == list(range(len(sizes)))
    assert not client.lost
    after = log[log.index(("wait", failed[0])):]
    assert not [e for e in after if e[0] == "put"]
    assert [e for e in log if e[0] == "put"] == [
        ("put", path["dtype"].itemsize * n) for n in sizes[:failed[0]]]


@by_path
@pytest.mark.parametrize("k", [0, 1, 3])
@pytest.mark.parametrize("where", ["enqueue", "landing"])
def test_error_while_enqueueing_settles_what_is_in_flight(bridge, where, k,
                                                          path):
    """(d) An exception in the enqueue loop with k leaves already in flight:
    those k handles are waited before it leaves (their sources and slots
    are the C core's until then, and every source is still there) — even
    when one of them fails too — nothing is put, and the loop's own error
    is the one raised."""
    sizes = SIZES["five"]
    kwargs = ({"refuse_enqueue": k} if where == "enqueue"
              else {"lost_leaf": k})
    log, client, tree = bridge(sizes, fail_wait=[0], **kwargs, **path, **HELD)
    message = f"enqueue {k} refused" if where == "enqueue" else f"leaf {k} lost"
    with pytest.raises(RuntimeError, match=message):
        ps.ps_push_pull(tree, average=False)
    assert [h for kind, h in log if kind == "enqueue"] == list(range(k))
    assert [h for kind, h in log if kind == "wait"] == list(range(k))
    assert not client.lost
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


# --- a push has a source and a destination ----------------------------------

@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_a_wire_form_tree_is_pushed_from_where_it_landed(bridge, sizes):
    """(direct a) A float32 tree with no codec: every byte is enqueued from
    the landed array itself — the client's source IS that array, read-only
    and bit for bit what it was after the round — and pulled into the
    slot's buffer, which the next call's destination is again; held only
    by ``ps.py`` while in flight, no source is lost."""
    uploads = []
    _, client, tree = bridge(sizes, uploads=uploads, weak_sources=True)
    n, nbytes = len(sizes), 4 * sum(sizes)
    for call, scale in enumerate((1, 7)):
        leaves = retake(tree, scale)
        out = ps.ps_push_pull(leaves, average=False)
        assert ps.stage_stats == {"direct_bytes": nbytes, "bytes": nbytes,
                                  "reused_bytes": nbytes * call}
        assert not client.lost
        for i, leaf in enumerate(leaves):
            source, slot = client.sources[call * n + i](), ps._slots[i].buf
            assert source is leaf._value and not source.flags.writeable
            np.testing.assert_array_equal(source, np.full(
                (sizes[i],), scale * (i + 1), np.float32))
            dest = client.buffers[call * n + i]
            assert dest.ctypes.data == slot.ctypes.data
            assert dest.nbytes == slot.nbytes == source.nbytes
            assert not np.shares_memory(dest, source)
            np.testing.assert_array_equal(out[i].value, 2 * scale * (i + 1))
            assert out[i].source is dest


def test_under_a_codec_only_wire_form_leaves_are_pushed_direct(bridge):
    """(direct b) One tree, one codec: the float32 leaves and the integer
    one (its own wire, no codec) are pushed from where they landed, the
    half-precision ones are upcast into their float32 slots and pushed from
    there in place; ``direct_bytes`` counts the former only."""
    dtypes = [np.float32, BF16, np.float32, jax.numpy.dtype("float16"),
              np.int32]
    sizes = [4, 6, 8, 10, 3]
    uploads = []
    log, client, _ = bridge([], compressor="onebit", uploads=uploads)
    tree = [Leaf(log, i, np.full((n,), i + 1, d))
            for i, (n, d) in enumerate(zip(sizes, dtypes))]
    out = ps.ps_push_pull(tree, average=False)
    assert client.wire_dtypes == ["float32"] * 4 + ["int32"]
    assert ps.stage_stats == {"direct_bytes": 4 * (4 + 8 + 3),
                              "reused_bytes": 0, "bytes": 4 * sum(sizes)}
    for i, leaf in enumerate(tree):
        if i in (1, 3):  # copied: the slot is source and destination
            assert client.sources[i] is client.buffers[i]
        else:
            assert client.sources[i] is leaf._value
        assert client.buffers[i].ctypes.data == ps._slots[i].buf.ctypes.data
        assert out[i].value.dtype == leaf.dtype
        np.testing.assert_array_equal(out[i].value, np.full(
            (sizes[i],), 2 * (i + 1), leaf.dtype))


def test_host_values_not_in_wire_form_are_copied(bridge):
    """(direct c) Host values: a C-contiguous array of the caller's is the
    source as it stands and is not written; a transposed one, a strided
    one, one in the other byte order, a 0-d array and a Python scalar are
    copied into their slots, in C order and the wire's bytes, and come
    back in their own shapes."""
    _, client, _ = bridge([])
    matrix = np.arange(24, dtype=np.float32).reshape(4, 6)
    tree = {"a": 1.5, "b": np.asarray(2.0, np.float32), "c": matrix.T,
            "d": matrix[:, ::2], "e": matrix.astype(">f4"), "f": matrix}
    kept = matrix.copy()
    out = ps.ps_push_pull(tree, average=False)
    assert ps.stage_stats["direct_bytes"] == matrix.nbytes
    assert ps.stage_stats["bytes"] == 8 + 4 + 4 * (24 + 12 + 24 + 24)
    for h in range(5):
        assert client.sources[h] is client.buffers[h]
    assert client.sources[5] is matrix
    assert not np.shares_memory(client.buffers[5], matrix)
    np.testing.assert_array_equal(matrix, kept)
    for k, v in tree.items():
        assert np.shape(out[k]) == np.shape(v)
        np.testing.assert_array_equal(out[k], 2 * np.asarray(v))


@pytest.mark.parametrize("value", [
    np.float32(3.0),
    np.asfortranarray(np.arange(24, dtype=np.float32).reshape(4, 6))],
    ids=["zero-d", "column-major"])
def test_a_device_leaf_that_lands_out_of_wire_form_is_copied(bridge, value):
    """(direct d) What decides is the array that landed, not the leaf: the
    TPU runtime lands a matrix in its device layout, which may be
    column-major, and the wire is row-major, so that leaf is copied (the
    pass that transposes it) — as is a scalar that lives on the device (a
    loss, a step count): 4 bytes are not worth a second kind of source."""
    log, client, _ = bridge([])
    leaf = Leaf(log, 0, value)
    assert not (leaf.shape and leaf._value.flags.c_contiguous)
    out = ps.ps_push_pull([leaf], average=False)
    assert ps.stage_stats == {"direct_bytes": 0, "reused_bytes": 0,
                              "bytes": leaf._value.nbytes}
    assert client.sources[0] is client.buffers[0]
    assert client.buffers[0].flags.c_contiguous
    assert out[0].shape == leaf.shape
    np.testing.assert_array_equal(out[0], 2 * leaf._value)


class _Lib:
    """``bps_push_pull`` of the C library, recording what it is handed."""

    def __init__(self):
        self.calls = []

    def bps_push_pull(self, tid, src, dst, nelem, dtype, average, async_mode):
        self.calls.append((tid, src.value, dst.value, nelem, dtype, average,
                           async_mode))
        return len(self.calls) - 1


@pytest.mark.parametrize("dtype,code", [("float32", 0), ("float16", 2),
                                        ("int32", 4)])
def test_the_ffi_hands_the_core_a_source_and_a_destination(dtype, code):
    """``Worker.push_pull``: one array is the in-place call, the same
    pointer twice; with ``out`` the core gets the source's pointer — a
    read-only array's too — and the destination's."""
    worker = types.SimpleNamespace(_lib=_Lib())
    src = np.arange(12, dtype=dtype).reshape(3, 4)
    assert ffi.Worker.push_pull(worker, 7, src) == 0
    src.flags.writeable = False
    dst = np.empty(12, dtype)
    assert ffi.Worker.push_pull(worker, 8, src, average=False,
                                async_mode=True, out=dst) == 1
    assert worker._lib.calls == [
        (7, src.ctypes.data, src.ctypes.data, 12, code, 1, 0),
        (8, src.ctypes.data, dst.ctypes.data, 12, code, 0, 1)]


@pytest.mark.parametrize("out", [
    np.empty(11, np.float32), np.empty(12, np.float64),
    np.empty((12, 2), np.float32)[:, 0], np.empty(24, np.float32)[::2]],
    ids=["short", "other-dtype", "strided-2d", "strided"])
def test_the_ffi_refuses_a_destination_the_core_would_overrun(out):
    """The core writes the source's byte count through the destination's
    pointer, so a destination of another size, dtype or layout, or a
    read-only one, is refused before the library is called."""
    worker = types.SimpleNamespace(_lib=_Lib())
    src = np.ones(12, np.float32)
    with pytest.raises(ValueError, match="destination"):
        ffi.Worker.push_pull(worker, 0, src, out=out)
    frozen = np.empty(12, np.float32)
    frozen.flags.writeable = False
    with pytest.raises(ValueError, match="destination"):
        ffi.Worker.push_pull(worker, 0, src, out=frozen)
    assert not worker._lib.calls


# --- the pool: one buffer per declared tensor, reused across calls -----------

@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_second_call_stages_into_the_first_calls_buffers(bridge, sizes):
    """(pool a) Two calls on one tree signature: every buffer the client
    sees in call 2 is call 1's memory holding call 2's values, call 1's
    results keep theirs, and ``stage_stats`` reads 0 of all, then all."""
    uploads = []
    _, client, tree = bridge(sizes, uploads=uploads)
    first = ps.ps_push_pull(tree, average=False)
    assert ps.stage_stats == {"direct_bytes": 4 * sum(sizes),
                              "reused_bytes": 0, "bytes": 4 * sum(sizes)}
    second = ps.ps_push_pull(retake(tree, 10), average=False)
    assert ps.stage_stats == {"direct_bytes": 4 * sum(sizes),
                              "reused_bytes": 4 * sum(sizes),
                              "bytes": 4 * sum(sizes)}
    n = len(sizes)
    assert len(client.buffers) == 2 * n and len(ps._slots) == n
    for i in range(n):
        a, b = client.buffers[i], client.buffers[n + i]
        assert a.ctypes.data == b.ctypes.data and np.shares_memory(a, b)
        np.testing.assert_array_equal(b, np.full((sizes[i],), 20 * (i + 1),
                                                 np.float32))
        np.testing.assert_array_equal(first[i].value, 2 * (i + 1))
        np.testing.assert_array_equal(second[i].value, 20 * (i + 1))


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_another_tree_under_the_prefix_gets_its_own_slots(bridge, sizes):
    """(pool b) A tree of other leaf sizes under the same prefix declares
    its own tensors and stages into its own buffers; the first tree's are
    not touched and are found again by its next call."""
    uploads = []
    _, client, tree = bridge(sizes, uploads=uploads)
    ps.ps_push_pull(tree, average=False)
    kept = [b.copy() for b in client.buffers]
    other = [Leaf([], i, np.full((n + 1,), 7.0, np.float32))
             for i, n in enumerate(sizes)]
    ps.ps_push_pull(other, average=False)
    assert ps.stage_stats == {"direct_bytes": 4 * (sum(sizes) + len(sizes)),
                              "reused_bytes": 0,
                              "bytes": 4 * (sum(sizes) + len(sizes))}
    n = len(sizes)
    assert len(ps._slots) == 2 * n
    for i in range(n):
        assert not np.shares_memory(client.buffers[i], client.buffers[n + i])
        np.testing.assert_array_equal(client.buffers[i], kept[i])
    ps.ps_push_pull(tree, average=False)
    assert ps.stage_stats["reused_bytes"] == 4 * sum(sizes)
    assert all(np.shares_memory(client.buffers[i], client.buffers[2 * n + i])
               for i in range(n))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_half_wire_with_codec_fills_a_float32_slot(bridge, sizes, dtype):
    """(pool c) Half-precision leaves under a codec: the slot is float32
    and is filled straight from the half-precision leaf — the only arrays
    of the leaf's size that a call allocates are the downcast copies it
    puts, as before — the put is half precision, the slot is reused."""
    dtype = jax.numpy.dtype(dtype)
    uploads = []
    _, client, tree = bridge(sizes, dtype=dtype, compressor="onebit",
                             uploads=uploads)
    for call, scale in enumerate((1, 3)):
        out = ps.ps_push_pull(retake(tree, scale), average=False)
        assert ps.stage_stats == {
            "direct_bytes": 0, "reused_bytes": 4 * sum(sizes) * call,
            "bytes": 4 * sum(sizes)}
        for i, leaf in enumerate(out):
            slot = client.buffers[call * len(sizes) + i]
            assert slot.dtype == np.float32
            assert slot.ctypes.data == client.buffers[i].ctypes.data
            assert leaf.value.dtype == dtype and leaf.source.dtype == dtype
            assert not np.shares_memory(leaf.source, slot)
            np.testing.assert_array_equal(
                leaf.value, np.full((sizes[i],), 2 * scale * (i + 1), dtype))


@by_path
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_a_live_unfinished_upload_is_waited_for_a_dead_one_is_not(bridge,
                                                                  sizes,
                                                                  path):
    """(pool d) Before a slot is written again — by the copy, or by the
    core, which has it from the enqueue on — the previous upload from it is
    waited for, if the caller still holds its result and it is not ready.
    A result that is ready costs nothing, one the caller dropped costs no
    call, and the pool keeps none alive."""
    uploads = []
    log, client, tree = bridge(sizes, uploads=uploads, **path)
    first = ps.ps_push_pull(tree, average=False)
    last = len(sizes) - 1
    first[0].ready = first[last].ready = False
    del log[:]
    ps.ps_push_pull(retake(tree, 2), average=False)
    blocks = [e for e in log if e[0] == "block"]
    assert blocks == [("block", 0), ("block", last)]
    # each wait comes before that slot is handed to the client again, and
    # after the leaf has landed: no sooner than it has to
    assert log.index(("block", 0)) < log.index(("enqueue", len(sizes)))
    takes = [k for k, e in enumerate(log) if e == ("take", last)]
    assert takes[-1] < log.index(("block", last))
    assert log.index(("block", last)) < log.index(
        ("enqueue", len(sizes) + last))
    # the caller drops call 2's results while they are unfinished: no wait
    refs = [weakref.ref(u) for u in uploads]
    for u in uploads:
        u.ready = False
    del first, u
    uploads.clear()
    gc.collect()
    assert all(r() is None for r in refs), "the pool holds a put result"
    del log[:]
    ps.ps_push_pull(retake(tree, 3), average=False)
    assert not [e for e in log if e[0] == "block"]
    assert ps.stage_stats["reused_bytes"] == 4 * sum(sizes)


@pytest.mark.parametrize("fault", ["refused-enqueue", "failed-first-wait",
                                   "failed-last-wait", "lost-leaf"])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_the_pool_is_usable_after_an_error(bridge, sizes, fault):
    """(pool e) A call that fails mid-stage or mid-wait settles what it had
    enqueued, as before, and the next call on the same tree succeeds in
    the slots that call left."""
    last = len(sizes) - 1
    kwargs = {"refused-enqueue": {"refuse_enqueue": 1},
              "failed-first-wait": {"fail_wait": [0]},
              "failed-last-wait": {"fail_wait": [last]},
              "lost-leaf": {"lost_leaf": 1}}[fault]
    uploads = []
    log, client, tree = bridge(sizes, uploads=uploads, **kwargs)
    with pytest.raises(RuntimeError):
        ps.ps_push_pull(tree, average=False)
    enqueued = [h for kind, h in log if kind == "enqueue"]
    assert [h for kind, h in log if kind == "wait"] == enqueued
    # a refused leaf was staged before it was refused; a lost one never was
    slots = len(ps._slots)
    assert slots == {"refused-enqueue": 2, "lost-leaf": 1}.get(fault,
                                                               len(sizes))
    failed = list(client.buffers)
    client._refuse, client._fail_wait = None, set()
    out = ps.ps_push_pull(retake(tree, 5), average=False)
    assert ps.stage_stats["reused_bytes"] == 4 * sum(sizes[:slots])
    for i, leaf in enumerate(out):
        np.testing.assert_array_equal(leaf.value, np.full(
            (sizes[i],), 10 * (i + 1), np.float32))
    for a, b in zip(failed, client.buffers[len(failed):]):
        assert a.ctypes.data == b.ctypes.data


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_reset_declare_cache_empties_the_pool(bridge, sizes):
    """(pool f) ``reset_declare_cache()`` — ``bps.init()`` and
    ``bps.shutdown()`` — drops every slot with the tensor ids: a restarted
    fleet's first call stages into new buffers."""
    uploads = []
    _, client, tree = bridge(sizes, uploads=uploads)
    ps.ps_push_pull(tree, average=False)
    assert len(ps._slots) == len(sizes)
    ps.reset_declare_cache()
    assert not ps._slots and not ps._tid_cache
    ps.ps_push_pull(retake(tree, 2), average=False)
    assert ps.stage_stats == {"direct_bytes": 4 * sum(sizes),
                              "reused_bytes": 0, "bytes": 4 * sum(sizes)}


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_a_result_that_is_the_buffer_takes_the_buffer_with_it(bridge, sizes):
    """Hazard 2 with a ``device_put`` that hands back the host buffer itself
    (the fixture's default; the CPU backend for an aligned buffer): the
    slot is given away, call 2 stages into new memory, and call 1's
    results are not rewritten."""
    _, client, tree = bridge(sizes)
    first = ps.ps_push_pull(tree, average=False)
    assert not ps._slots
    ps.ps_push_pull(retake(tree, 10), average=False)
    assert ps.stage_stats == {"direct_bytes": 4 * sum(sizes),
                              "reused_bytes": 0, "bytes": 4 * sum(sizes)}
    n = len(sizes)
    for i in range(n):
        assert not np.shares_memory(client.buffers[i], client.buffers[n + i])
        np.testing.assert_array_equal(first[i], 2 * (i + 1))


@pytest.mark.parametrize("sizes", [[3, 5], [16, 1000, 1 << 18]],
                         ids=["two", "to-1MB"])
def test_real_cpu_device_put_never_rewrites_an_earlier_result(monkeypatch,
                                                              sizes):
    """Hazard 2 through the real ``jax.device_put`` of the CPU backend,
    device arrays in and out: the tree call 1 returned is bit for bit what
    it was after call 2 pushed other values on the same signature, and
    after a third. What this jax (0.9.0) showed: the CPU backend ALIASES a
    host buffer that is 64-byte aligned (the result's
    ``unsafe_buffer_pointer()`` is the numpy pointer and follows a later
    write) and copies any other; ``np.empty`` is aligned so about every
    other time, at 4 bytes as at 4 MB, and ``may_alias=False`` changes
    nothing for a numpy source. So both branches run here, by the
    allocator's choice: an aliased slot is given away, a copied one is
    reused."""
    monkeypatch.delenv("BYTEPS_COMPRESSOR", raising=False)
    ps.reset_declare_cache()
    client = Client([])
    monkeypatch.setattr(ps.bps, "_st", lambda: types.SimpleNamespace(
        ps_client=client, config=types.SimpleNamespace(
            enable_async=False, compressor="")))
    cpu = jax.devices("cpu")[0]
    try:
        results, wants, reused = [], [], 0
        for call in range(6):
            tree = [jax.device_put(np.full((n,), 10.0 * call + i, np.float32),
                                   cpu) for i, n in enumerate(sizes)]
            results.append(ps.ps_push_pull(tree, average=False))
            wants.append([np.full((n,), 2 * (10.0 * call + i), np.float32)
                          for i, n in enumerate(sizes)])
            reused += ps.stage_stats["reused_bytes"]
            for got, want in zip(results, wants):  # every call so far
                for g, w in zip(got, want):
                    assert isinstance(g, jax.Array)
                    np.testing.assert_array_equal(np.asarray(g), w)
        # a slot is in the pool exactly when its newest result is a copy
        for tid, got in zip(range(len(sizes)), results[-1]):
            aliased = any(got.unsafe_buffer_pointer() == b.ctypes.data
                          for b in client.buffers)
            assert (tid in ps._slots) != aliased
    finally:
        ps.reset_declare_cache()
