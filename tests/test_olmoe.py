"""The dropless top-k expert layer and OlmoeModel (tier-1, CPU, seeded).

Two yardsticks, both written out here or in the benchmark and sharing no
code with ``parallel/moe.py``: a dense computation in float32 — every
expert applied to every token, masked by the top-k router probabilities —
for the layer, and ``benchmark/lib/plain_olmoe.py`` for the model.

Tolerance. In float32 on the CPU both sides compute every product exactly
alike and differ only by the order sums are taken in: a relative 1e-5 of
the largest entry. Rounding the router or the grouped matmuls' accumulation
to bfloat16 moves entries by up to 2**-8 = 4e-3 of their size;
``test_the_tolerance_fails_a_bf16_router_or_accumulation`` shows both fail it
by more than ten times.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.models import Olmoe1B7B, OlmoeTiny, olmoe_loss
from byteps_tpu.parallel.moe import dropless_moe_ffn, publish_moe_stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

RTOL = 1e-5          # of the largest entry; see the module's docstring
SEQS, SEQ, D, M = 3, 16, 32, 24     # tokens = SEQS * SEQ


def _inputs(experts, skew, seed=0):
    """Seeded weights and tokens. ``skew``: expert 1 receives every token of
    sequence 0 (a feature only that sequence carries, which only its router
    column reads) and expert 0 receives no token (all-positive features, an
    all-negative router column)."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((SEQS * SEQ, D)).astype(np.float32)
    wr = rng.standard_normal((D, experts)).astype(np.float32) * 0.5
    if skew:
        x = np.abs(x)
        x[:, 0] = 0.0
        x[:SEQ, 0] = 8.0
        wr[0, :] = 0.0
        wr[0, 1] = 4.0
        wr[:, 0] = -1.0
    scale = 1.0 / math.sqrt(D)
    return tuple(jnp.asarray(a) for a in (
        x, wr,
        rng.standard_normal((experts, D, M)).astype(np.float32) * scale,
        rng.standard_normal((experts, D, M)).astype(np.float32) * scale,
        rng.standard_normal((experts, M, D)).astype(np.float32) * scale))


def _dense(x, wr, wg, wu, wd, top_k):
    """All experts on all tokens, masked: (y, load_balance, z_loss)."""
    hi = jax.lax.Precision.HIGHEST
    t, e = x.shape[0], wr.shape[1]
    logits = jnp.dot(x, wr, precision=hi)
    probs = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, e - top_k]
    weight = jnp.where(probs >= kth[:, None], probs, 0.0)       # [T, E]
    hidden = (jax.nn.silu(jnp.einsum("td,edm->etm", x, wg, precision=hi))
              * jnp.einsum("td,edm->etm", x, wu, precision=hi))
    out = jnp.einsum("etm,emd->etd", hidden, wd, precision=hi)
    y = jnp.einsum("etd,te->td", out, weight, precision=hi)
    counts = (weight > 0).sum(axis=0)
    load_balance = (counts * probs.mean(axis=0)).sum() * e / (t * top_k)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return y, load_balance, jnp.mean(lse * lse)


def _scalar(fn, cot):
    """One scalar through which every output reaches every input."""
    def loss(*args):
        y, load_balance, z_loss = fn(*args)[:3]
        return (y * cot).sum() + 0.3 * load_balance + 0.7 * z_loss
    return loss


def _close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) <= rtol * max(scale, 1e-30)


CASES = [(k, e, skew) for e in (8, 64) for k in (1, 2, 8)
         for skew in (False, True)]


@pytest.mark.parametrize("top_k,experts,skew", CASES)
def test_dropless_layer_is_the_dense_masked_computation(top_k, experts, skew):
    args = _inputs(experts, skew)
    sparse = lambda *a: dropless_moe_ffn(*a, top_k=top_k,  # noqa: E731
                                         dtype=jnp.float32)
    y, load_balance, z_loss, counts = sparse(*args)
    want = _dense(*args, top_k)
    assert _close(y, want[0])
    assert abs(float(load_balance) - float(want[1])) <= RTOL * float(want[1])
    assert abs(float(z_loss) - float(want[2])) <= RTOL * float(want[2])
    # dropless: every token reaches its k experts, whatever the load
    counts = np.asarray(counts)
    assert counts.sum() == SEQS * SEQ * top_k and counts.dtype == np.int32
    if skew:
        assert counts[1] >= SEQ                    # all of sequence 0
        assert counts[0] == (0 if top_k < experts else SEQS * SEQ)
    cot = jnp.asarray(np.random.default_rng(1).standard_normal(
        (SEQS * SEQ, D)).astype(np.float32))
    got_g = jax.grad(_scalar(sparse, cot), argnums=(0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(_scalar(lambda *a: _dense(*a, top_k), cot),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for name, got, want in zip(("x", "router", "gate", "up", "down"),
                               got_g, want_g):
        assert _close(got, want), name
    if skew and top_k < experts:                   # the idle expert's
        assert not np.asarray(got_g[2][0]).any()   # weights get no gradient


@pytest.mark.parametrize("what", ("router", "accumulation"))
def test_the_tolerance_fails_a_bf16_router_or_accumulation(what):
    args = _inputs(8, False)
    want = _dense(*args, 2)
    if what == "router":        # what a bf16 router would see
        rounded = [a.astype(jnp.bfloat16).astype(jnp.float32)
                   for a in args[:2]]
        y, _, z_loss, _ = dropless_moe_ffn(*rounded, *args[2:], top_k=2,
                                           dtype=jnp.float32)
        assert abs(float(z_loss) - float(want[2])) > 10 * RTOL * float(
            want[2])
    else:                       # bf16 operands and results
        y, _, _, _ = dropless_moe_ffn(*args, top_k=2, dtype=jnp.bfloat16)
    assert not _close(y, want[0], rtol=10 * RTOL)


def test_top_k_out_of_range_is_refused():
    args = _inputs(8, False)
    for top_k in (0, 9):
        with pytest.raises(ValueError, match="top_k"):
            dropless_moe_ffn(*args, top_k=top_k)


def _config():
    """The benchmark's configuration: its sizes and its module."""
    from benchmark.lib import cell as cell_lib

    path = os.path.join(REPO, "benchmark", "configs", "olmoe-1b-7b")
    return (cell_lib.load_json(path + ".json"),
            cell_lib.load_module(path + ".py", "cfg_olmoe"))


def _rehearsal_config():
    cfg, module = _config()
    return ({**cfg, **cfg["rehearsal_sizing"], "compute_dtype": "float32"},
            module)


@pytest.mark.parametrize("rows", (1, 4))
def test_model_loss_and_gradients_are_the_plain_reference_s(rows):
    """OlmoeModel + olmoe_loss against benchmark/lib/plain_olmoe.py at the
    rehearsal size (2 layers, 8 experts, top-2), float32."""
    cfg, module = _rehearsal_config()
    init, loss_fn = module.build(cfg)
    params = init(jax.random.PRNGKey(3))
    batch = module.make_batch(cfg, np.random.default_rng(3), rows)
    weighted = {**batch, "weight": module.reference_weights(cfg, batch, 1)}
    got, got_g = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    want, want_g = jax.jit(jax.value_and_grad(module.reference_loss(cfg)))(
        params, weighted)
    assert abs(float(got) - float(want)) <= 2e-6 * float(want)
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    assert len(flat) == len(jax.tree_util.tree_leaves(got_g)) == 27
    for (path, want_leaf), got_leaf in zip(
            flat, jax.tree_util.tree_leaves(got_g)):
        # sums over 32 x rows positions in another order, two layers deep
        assert _close(got_leaf, want_leaf, rtol=1e-4), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("layers,want", [
    # embedding and untied head 2 x 50304 x 2048, final norm 2048; a layer:
    # Q, K, V, O 4 x 2048^2, four norms of 2048 (attn, moe, q, k), router
    # 2048 x 64, experts 64 x 3 x 2048 x 1024 = 419,569,664
    (1, 2 * 50304 * 2048 + 2048 + 419_569_664),          # 625,616,896
    (16, 2 * 50304 * 2048 + 2048 + 16 * 419_569_664),    # 6,919,161,856
])
def test_parameter_count_by_hand(layers, want):
    assert 4 * 2048 ** 2 + 4 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024 \
        == 419_569_664
    model = Olmoe1B7B(num_layers=layers)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    assert list(shapes) == ["params"]
    got = sum(math.prod(leaf.shape)
              for leaf in jax.tree_util.tree_leaves(shapes))
    assert got == want
    assert (want == 625_616_896) == (layers == 1)


def test_flops_per_token_by_hand():
    cfg, module = _config()
    per_layer = 4 * 2048 ** 2 + 2048 * 64 + 8 * 3 * 2048 * 1024  # active
    assert per_layer == 67_239_936
    matmul = per_layer + 2048 * 50304
    attention = 12 * 1 * 4096 * 2048 // 2
    assert module.flops_per_token(cfg) == 6 * matmul + attention \
        == 1_071_906_816                                 # ~1.07 GFLOP/token
    assert cfg["n_params"] == 625_616_896
    assert round(100 * 6 * 2048 * 50304 / 1_071_906_816) == 58   # the head
    assert round(100 * 6 * 8 * 3 * 2048 * 1024 / 1_071_906_816) == 28


def test_counts_are_sown_only_when_asked_for_and_published():
    from byteps_tpu.monitor import metrics

    model = OlmoeTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 32),
                                               dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert list(params) == ["params"]
    (logits, aux), stats = model.apply(params, tokens,
                                       mutable=["moe_stats"])
    assert logits.shape == (2, 32, 512) and logits.dtype == jnp.float32
    assert set(aux) == {"load_balance", "z_loss"}
    assert np.isfinite(float(olmoe_loss((logits, aux), tokens)))
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 2 and all(int(c.sum()) == 2 * 32 * 2
                                    for c in counts)
    before = metrics._py_counters.get("bps_moe_assignments_total", 0.0)
    published = publish_moe_stats(stats["moe_stats"])
    assert published["bps_moe_assignments_total"] == 2 * 2 * 32 * 2
    worst = max(float(c.max()) / float(c.mean()) for c in counts)
    assert published["bps_moe_max_expert_load"] == pytest.approx(worst)
    assert metrics._py_gauges["bps_moe_max_expert_load"] == pytest.approx(
        worst)
    assert metrics._py_counters["bps_moe_assignments_total"] == before + 256
    assert publish_moe_stats({}) == {}


def test_the_gate_s_new_arguments_leave_the_lowered_step_as_it_was():
    """PR 39 gave ``dropless_moe_ffn`` a scoring rule, a selection bias, an
    epsilon and a routed scale. At their defaults the gradient of
    OlmoeTiny's loss lowers to the text it lowered to at ``3f4a582``."""
    import hashlib

    model, tokens = OlmoeTiny(), np.zeros((2, 32), np.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    text = jax.jit(jax.grad(lambda p: olmoe_loss(
        model.apply(p, tokens), tokens))).lower(params).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "29589b7f081a55779b06f19f6d437629ccd485aeae93f00e1a98fdab1f98fb6d")
