"""``ops/sparse_flash.py`` — the masked, grouped-query flash kernels of one
block of queries — against the masked XLA form on the same inputs (tier-1,
CPU: the kernels run in Pallas interpret mode), and
``parallel/sparse_attention.py``'s choice between the two forms.

Two levels. The op alone, against the masked form written out here as
``sparse_attention._block`` has it (logits, ``where``, softmax, PV, the
head-mean): output, ``target`` and the gradients of q, k and v. And
``sparse_attention`` itself told to take the kernel form (``attend_form``
steered in the test: on this backend it answers ``"masked"``) against
itself in the masked form: everything it returns and all six gradients, on
inputs whose selection has ties at the threshold, spans that end before the
last key and a first span that selects nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.parallel.sparse_attention as sa
from byteps_tpu.monitor import metrics
from byteps_tpu.ops import sparse_flash
from byteps_tpu.ops.sparse_flash import masked_attention, renormalised
from test_sparse_attention import _eqns, _inputs, _scalar


def _masked_form(q, k, v, keep, scale):
    """``_block``'s masked form over the keys the mask spans."""
    rows, heads, d = q.shape
    n, kv_heads = keep.shape[1], k.shape[1]
    grouped = q.reshape(rows, kv_heads, heads // kv_heads, d)
    logits = jnp.einsum("qcgd,scd->cgqs", grouped, k[:n],
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(
        jnp.where(keep, logits, jnp.finfo(jnp.float32).min), axis=-1)
    out = jnp.einsum("cgqs,scd->qcgd", probs.astype(v.dtype), v[:n],
                     preferred_element_type=jnp.float32)
    return (out.reshape(rows, heads, d),
            jax.lax.stop_gradient(probs.sum(axis=(0, 1)) / heads))


def _err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# rows, query heads, key-value heads, head width, keys, keys the mask
# spans, the block's first position, dtype, tolerance
OP_CASES = {
    "eight_heads_a_kv_head": (32, 8, 1, 128, 256, 256, 224, "float32", 3e-6),
    "keys_not_whole_tiles": (32, 8, 2, 128, 300, 300, 200, "float32", 3e-6),
    "span_ends_before_the_last_key": (32, 8, 2, 128, 512, 256, 96,
                                      "float32", 3e-6),
    "narrow_heads": (32, 8, 2, 16, 96, 64, 32, "float32", 3e-6),
    "bf16_operands": (64, 16, 2, 128, 512, 384, 64, "bfloat16", 1e-2),
}


@pytest.mark.parametrize("case", sorted(OP_CASES))
def test_kernels_are_the_masked_form(case, monkeypatch):
    """Tiles of 128 keys, so every case walks several, the last of them
    past the block's last query (not computed) or past the keys (padded);
    every third row keeps nothing in the first tile, where the running
    maximum has nothing to hold yet."""
    rows, heads, kv_heads, d, s, n, first, dtype, tol = OP_CASES[case]
    monkeypatch.setattr(sparse_flash, "KEY_TILE", 128)
    rng = np.random.default_rng(sorted(OP_CASES).index(case))

    def normal(*shape, dtype=dtype):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    q, k, v = normal(rows, heads, d), normal(s, kv_heads, d), normal(
        s, kv_heads, d)
    position = first + np.arange(rows)
    keep = ((np.arange(n)[None, :] <= position[:, None])
            & (rng.random((rows, n)) < 0.4))
    keep[::3, :128] = False
    keep[np.arange(rows), position] = True
    keep = jnp.asarray(keep)
    cot = normal(rows, heads, d, dtype="float32")
    weights = normal(rows, n, dtype="float32")
    scale = d ** -0.5

    def kernel(q, k, v):
        out, lse, target = masked_attention(
            q.reshape(rows, -1), k, v, keep, jnp.int32(first), scale)
        return renormalised(out, lse).reshape(q.shape), target

    def masked(q, k, v):
        return _masked_form(q, k, v, keep, scale)

    def loss(fn):
        def scalar(q, k, v):
            out, target = fn(q, k, v)
            return ((out.astype(jnp.float32) * cot).sum()
                    + (target * weights).sum())
        return scalar

    out, target = kernel(q, k, v)
    want_out, want_target = masked(q, k, v)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert target.dtype == jnp.float32 and target.shape == (rows, n)
    assert _err(out, want_out) <= tol
    assert _err(target, want_target) <= min(tol, 1e-6)
    assert not np.asarray(target)[~np.asarray(keep)].any()
    # ``weights`` reaches no gradient: ``target`` is detached in both
    for got, want in zip(jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
                         jax.grad(loss(masked), (0, 1, 2))(q, k, v)):
        assert got.dtype == want.dtype and _err(got, want) <= tol


def _as_kernel(monkeypatch):
    monkeypatch.setattr(sa, "attend_form", lambda *shape: "kernel")


# (sequence, topk, block, query heads, key-value heads, tied scores)
FORM_CASES = [
    (32, 64, 16, 8, 1, False),      # T < topk: one span, no selection
    (96, 32, 16, 8, 1, False),      # three spans of keys, eight heads a group
    (96, 32, 16, 4, 2, True),       # most index scores equal the threshold
    (64, 16, 32, 4, 2, False),      # topk under the block
]


@pytest.mark.parametrize("s,topk,block,heads,kv_heads,tied", FORM_CASES)
def test_both_forms_of_the_op_agree(s, topk, block, heads, kv_heads, tied,
                                    monkeypatch):
    args = _inputs(s, heads, kv_heads, tied)
    op = lambda *a: sa.sparse_attention(*a, topk=topk, block=block)  # noqa: E731
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        args[0].shape).astype(np.float32))
    want = op(*args)
    want_grads = jax.grad(_scalar(op, cot), argnums=range(6))(*args)
    _as_kernel(monkeypatch)
    got = op(*args)
    assert (np.asarray(got[2]) == np.asarray(want[2])).all()
    for a, b in zip(got[:2] + jax.grad(_scalar(op, cot), argnums=range(6))(
            *args), want[:2] + want_grads):
        assert a.shape == b.shape and _err(a, b) <= 1e-5


def _calls(jaxpr, name):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
            and e.params["name"] == name]


def test_the_recomputation_does_not_run_the_forward_kernel_again(monkeypatch):
    """In the gradient the forward kernel and the backward kernel appear
    once a span of keys (the body of its ``lax.map``), the pass that forms
    ``target`` twice (the KL term's gradient reads ``target``, which is not
    kept); of the attention a block keeps its logsumexp alone."""
    _as_kernel(monkeypatch)
    s, topk, block = 96, 32, 16
    args = _inputs(s, 4, 2, False)

    def loss(*a):
        out, index_loss, _ = sa.sparse_attention(*a, topk=topk, block=block)
        return (out ** 2).sum() + index_loss

    jaxpr = jax.make_jaxpr(jax.grad(loss, range(6)))(*args).jaxpr
    spans = 3
    assert len(_calls(jaxpr, sparse_flash.FWD_NAME)) == spans
    assert len(_calls(jaxpr, sparse_flash.BWD_NAME)) == spans
    assert len(_calls(jaxpr, sparse_flash.PROBS_NAME)) == 2 * spans
    forward = jax.make_jaxpr(loss)(*args).jaxpr
    assert len(_calls(forward, sparse_flash.FWD_NAME)) == spans
    assert len(_calls(forward, sparse_flash.PROBS_NAME)) == spans


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("backend,dtype,head_dim,group,block,form", [
    ("tpu", BF16, 128, 8, 512, "kernel"),      # the Keye cell
    ("tpu", BF16, 128, 1, 32, "kernel"),
    ("cpu", BF16, 128, 8, 512, "masked"),
    ("gpu", BF16, 128, 8, 512, "masked"),
    ("tpu", F32, 128, 8, 512, "masked"),       # the float32 rehearsal
    ("tpu", BF16, 64, 8, 512, "masked"),       # half a row of lanes
    ("tpu", BF16, 256, 8, 512, "masked"),
    ("tpu", BF16, 128, 8, 16, "masked"),       # under an int8 sublane group
    ("tpu", BF16, 128, 8, 1024, "masked"),     # accumulators over VMEM
    ("tpu", BF16, 128, 16, 512, "masked"),
])
def test_the_form_follows_backend_dtype_and_shape(backend, dtype, head_dim,
                                                  group, block, form):
    assert sa.attend_form(backend, dtype, head_dim, group, block) == form


def test_on_this_backend_the_op_takes_the_masked_form():
    """No kernel library call in what a CPU run traces, bf16 or not."""
    args = tuple(a.astype(BF16) if i < 3 else a
                 for i, a in enumerate(_inputs(32, 4, 2, False)))
    jaxpr = jax.make_jaxpr(
        lambda *a: sa.sparse_attention(*a, topk=16, block=16))(*args).jaxpr
    assert not [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]


def test_the_counters_count_sites_and_those_that_took_the_kernel(monkeypatch):
    def counts():
        return tuple(metrics._py_counters.get(name, 0.0)
                     for name in (sa.ATTEND_SITES, sa.KERNEL_SITES))

    args = _inputs(32, 4, 2, False)
    before = counts()
    sa.sparse_attention(*args, topk=16, block=16)
    sa.sparse_attention(*args, topk=16, block=16)
    assert counts() == (before[0] + 2, before[1])
    _as_kernel(monkeypatch)
    sa.sparse_attention(*args, topk=16, block=16)
    assert counts() == (before[0] + 3, before[1] + 1)
    assert (sa.ATTEND_SITES, sa.KERNEL_SITES) == (
        "bps_dsa_attend_sites_total", "bps_dsa_kernel_sites_total")
