"""KimiLinearModel and what it brought (tier-1, CPU, float32, seeded): the
chunked KDA scan, the flash kernel at two head widths, the sigmoid gate of
``dropless_moe_ffn``, the share with a shared expert.

Yardsticks that share no code with the program: the token-by-token
recurrence for the chunked scan and for the model (``delta_rule`` in
``benchmark/lib/plain_kimi_linear.py``), XLA's two einsums for
the kernel, a literal ``argsort`` gate over dense experts for the expert
layer. In float32 on the CPU both sides differ by the order sums are taken
in: a relative 1e-5 of the largest entry wherever nothing discrete can
flip (the tolerances below say where they are wider, and why).
"""

import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (KimiLinear48BA3B, KimiLinearTiny,
                               kimi_linear_loss)
from byteps_tpu.models.kimi_linear import (KimiSparseMoe, causal_conv,
                                           layer_kinds)
from byteps_tpu.monitor import metrics
from byteps_tpu.ops.flash_attention import flash_attention
import byteps_tpu.parallel.linear_attention as la
from byteps_tpu.parallel.linear_attention import (SCAN_SITES, kda_attention,
                                                  publish_kda_stats)
from byteps_tpu.parallel.moe import dropless_moe_ffn, publish_moe_stats
from byteps_tpu.parallel.ring_attention import (_single_device_attention,
                                                attention_form)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_kimi_linear as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs", "kimi-linear-48b-a3b")
PLAIN = dict(heads=4, kv_rank=32, v_dim=16, top_k=2, first_expert=0,
             routed_scale=2.446, eps=1e-5, dtype=jnp.float32, scan_block=16,
             query_block=16, head_rows=32)


def kda_recurrence(q, k, v, g, beta):
    """The plain reference's token-by-token scan, a sequence at a time."""
    return jax.vmap(lambda *row: plain.delta_rule(
        *row, scan_block=q.shape[1]))(q, k, v, g, beta)


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------------
# the chunked scan

def _kda_inputs(s, strength, b=2, h=3, d_k=8, d_v=6, seed=0):
    """q, k normalised as the model normalises them; ``strength`` scales the
    log-decay (Mamba's rule reaches 1.6 a token; 8 is there to overflow)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (b, s, h, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, d_k))),
            jax.random.normal(ks[2], (b, s, h, d_v)),
            -strength * jax.nn.softplus(jax.random.normal(ks[3],
                                                          (b, s, h, d_k))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
            jax.random.normal(ks[5], (b, s, h, d_v)))


@pytest.fixture(params=("xla", "kernel"))
def form(request, monkeypatch):
    """Both forms of a chunk's operands: the XLA form every CPU run takes,
    and the kernel of ``ops/kda_chunk.py`` in interpret mode (the rule is
    told it may: the kernel still sees the CPU and interprets)."""
    if request.param == "kernel":
        monkeypatch.setattr(la, "kda_form", lambda *shapes: "kernel")
    return request.param


@pytest.mark.parametrize("s,chunk,sub,strength", [
    (64, 16, 4, 0.1),      # the chunk divides s; a mild decay
    (50, 16, 4, 1.0),      # it does not: 14 zero tokens close the last chunk
    (48, 16, 16, 1.0),     # every pair one by one (sub = chunk)
    (64, 32, 8, 8.0),      # e^-G overflows float32
    (33, 8, 2, 8.0),
])
def test_chunked_scan_is_the_token_recurrence(form, s, chunk, sub, strength):
    """Values and all five gradients. 1e-5: nothing discrete; the chunked
    form sums a chunk's pairs in another order than 64 rank-one updates."""
    *args, weight = _kda_inputs(s, strength)
    before = metrics.counter(la.KERNEL_SITES)

    def chunked(*a):
        return kda_attention(*a, chunk=chunk, sub=sub, dtype=jnp.float32)

    assert _rel(chunked(*args), kda_recurrence(*args)) <= 1e-5
    got, want = (jax.jit(jax.grad(lambda *a, f=f: (f(*a) * weight).sum(),
                                  argnums=(0, 1, 2, 3, 4)))(*args)
                 for f in (chunked, kda_recurrence))
    for g, w in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        assert _rel(g, w) <= 1e-5
    assert (metrics.counter(la.KERNEL_SITES) > before) == (form == "kernel")


def test_a_decay_that_overflows_the_naive_form_is_exact_here(form):
    """The strong cases above are past float32: a chunk's cumulated
    log-decay goes under -88.7, so e^-G, which the product form ``(k e^G)(k
    e^-G)^T`` needs, is inf and that form NaN — the chunked scan above
    matched the recurrence on the same inputs."""
    q, k, v, g, beta, _ = _kda_inputs(64, 8.0)
    G = jnp.cumsum(g.reshape(2, 2, 32, 3, 8), axis=2)
    assert float(G.min()) < -88.8
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())
    naive = jnp.einsum("bnihc,bnjhc->bnhij",
                       k.reshape(G.shape) * jnp.exp(G),
                       k.reshape(G.shape) * jnp.exp(-G))
    assert not bool(jnp.isfinite(jnp.tril(naive)).all())
    assert bool(jnp.isfinite(kda_attention(
        q, k, v, g, beta, chunk=32, sub=8, dtype=jnp.float32)).all())


@pytest.mark.parametrize("what", ("gate", "operands"))
def test_the_tolerance_fails_a_bf16_gate_or_bf16_operands(what):
    """A log-decay rounded to bf16, or bf16 matmul operands where the test
    says float32, is 1e-3 away: a hundred times the tolerance."""
    q, k, v, g, beta, _ = _kda_inputs(64, 1.0)
    want = kda_recurrence(q, k, v, g, beta)
    if what == "gate":
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
    got = kda_attention(q, k, v, g, beta, chunk=16, sub=4, dtype=(
        jnp.float32 if what == "gate" else jnp.bfloat16))
    assert _rel(got, want) > 1e-4


def test_sub_chunk_must_divide_the_chunk_and_shapes_are_checked():
    q, k, v, g, beta, _ = _kda_inputs(16, 1.0)
    with pytest.raises(ValueError, match="must divide"):
        kda_attention(q, k, v, g, beta, chunk=16, sub=5)
    with pytest.raises(ValueError, match="beta"):
        kda_attention(q, k, v, g, beta[..., None], chunk=16, sub=4)


def test_causal_conv_is_numpy_s():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 9, 3)).astype(np.float32)
    w = rng.standard_normal((4, 3)).astype(np.float32)
    want = np.stack([np.stack([np.convolve(x[b, :, c], w[::-1, c])[:9]
                               for c in range(3)], axis=-1)
                     for b in range(2)])
    np.testing.assert_allclose(causal_conv(jnp.asarray(x), jnp.asarray(w)),
                               want, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the flash kernel at two widths

@pytest.mark.parametrize("d,d_v", [(24, 16), (192, 128)])
def test_flash_kernel_at_unequal_widths_is_the_xla_form(d, d_v):
    """Interpret mode, forward and the three gradients, several blocks a
    side so that the diagonal's clamp is walked. 2e-5: the online softmax
    rescales a running sum block by block."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(kk, (1, 256, 2, d)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, d_v))
    weight = jax.random.normal(ks[3], (1, 256, 2, d_v))
    scale = d ** -0.5

    def kernel(q, k, v):
        return flash_attention(q, k, v, True, scale, 64, 64)

    def xla(q, k, v):
        return _single_device_attention(q, k, v, causal=True, scale=scale)

    assert kernel(q, k, v).shape == (1, 256, 2, d_v)
    assert _rel(kernel(q, k, v), xla(q, k, v)) <= 2e-5
    got, want = (jax.grad(lambda *a: (f(*a) * weight).sum(),
                          argnums=(0, 1, 2))(q, k, v) for f in (kernel, xla))
    for g, w in zip(got, want):
        assert g.shape == w.shape and _rel(g, w) <= 2e-5


@pytest.mark.parametrize("args, form", [
    # the latent layer: 128 + 64 against 128, s 16384
    (("tpu", 16384, 16384, 192, True, jnp.bfloat16, 128), "kernel"),
    (("tpu", 16384, 16384, 128, True, jnp.bfloat16, 128), "kernel"),
    (("tpu", 16384, 16384, 192, True, jnp.bfloat16), "xla"),       # 192/192
    (("tpu", 16384, 16384, 128, True, jnp.bfloat16, 64), "xla"),
    (("tpu", 256, 256, 192, True, jnp.bfloat16, 128), "xla"),
    (("cpu", 16384, 16384, 192, True, jnp.bfloat16, 128), "xla"),
])
def test_the_rule_admits_the_latent_layer_s_two_widths(args, form):
    assert attention_form(*args) == form


# --------------------------------------------------------------------------
# the gate, and the share with a shared expert

T, D, M, E, K = 48, 32, 24, 8, 2


def _layer_inputs(seed=0, t=T):
    rng = np.random.default_rng(seed)
    scale = 1.0 / math.sqrt(D)
    arrays = (rng.standard_normal((t, D)), rng.standard_normal((D, E)) * 0.5,
              rng.standard_normal((E, D, M)) * scale,
              rng.standard_normal((E, D, M)) * scale,
              rng.standard_normal((E, M, D)) * scale)
    return tuple(jnp.asarray(a.astype(np.float32)) for a in arrays)


def _literal(x, wr, wg, wu, wd, bias, scale=2.446):
    """All E experts applied to every token, weighted by the literal gate."""
    weight = plain.gate_weights(x, wr, bias, K, scale)
    hidden = jax.nn.silu(jnp.einsum("td,edm->etm", x, wg)) * jnp.einsum(
        "td,edm->etm", x, wu)
    return jnp.einsum("etm,emd,te->td", hidden, wd, weight), weight


@pytest.mark.parametrize("biased", (False, True))
def test_sigmoid_gate_is_the_literal_argsort_form(biased):
    """Sigmoid scores, a bias that chooses and does not weigh, 1e-20 in the
    renormalisation, the routed scale — against ``argsort`` over dense
    experts. With the bias the chosen set is another one, and no weight
    holds the bias."""
    x, wr, wg, wu, wd = _layer_inputs()
    bias = jnp.where(jnp.arange(E) == 5, 10.0, 0.0) if biased else None
    zero = jnp.zeros((E,))
    y, _, _, counts = dropless_moe_ffn(
        x, wr, wg, wu, wd, top_k=K, dtype=jnp.float32, norm_topk=True,
        scoring="sigmoid", select_bias=bias, norm_eps=1e-20,
        routed_scale=2.446)
    want, weight = _literal(x, wr, wg, wu, wd, zero if bias is None else bias)
    assert _rel(y, want) <= 1e-5
    assert np.array_equal(np.asarray(counts),
                          np.asarray((weight > 0).sum(axis=0)))
    # the chosen weights are scores over their sum, times the scale
    np.testing.assert_allclose(weight.sum(axis=-1), 2.446, rtol=1e-6)
    if biased:
        assert int(counts[5]) == T         # the bias put expert 5 in every set
        unbiased = _literal(x, wr, wg, wu, wd, zero)[1]
        assert bool(((weight > 0) != (unbiased > 0)).any())
        scores = jax.nn.sigmoid(x @ wr)
        chosen = np.asarray(weight[:, 5] > 0)
        np.testing.assert_allclose(      # weighed by its score, not score + 10
            np.asarray(weight[:, 5] / weight.sum(-1))[chosen],
            np.asarray(scores[:, 5] / jnp.where(weight > 0, scores, 0).sum(-1)
                       )[chosen], rtol=1e-5)


def test_gate_defaults_are_the_softmax_gate_and_bad_scoring_is_refused():
    x, wr, wg, wu, wd = _layer_inputs()
    base = dropless_moe_ffn(x, wr, wg, wu, wd, top_k=K, dtype=jnp.float32,
                            norm_topk=True)
    same = dropless_moe_ffn(x, wr, wg, wu, wd, top_k=K, dtype=jnp.float32,
                            norm_topk=True, scoring="softmax",
                            select_bias=None, norm_eps=0.0, routed_scale=1.0)
    for a, b in zip(base, same):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="softmax|sigmoid"):
        dropless_moe_ffn(x, wr, wg, wu, wd, top_k=K, scoring="tanh")


@pytest.mark.parametrize("t,tilt", ((T, 0.0), (512, 0.0), (512, 10.0)))
def test_the_shares_parts_add_up_with_the_shared_expert_counted_once(t, tilt):
    """The model-configs guide's test: four chips hold two of eight experts
    each; each computes its experts' part and the shared expert whole. The
    four outputs less three copies of the shared expert's are the uncut
    layer's (``plain.experts`` holding all eight). At 48 tokens a share's
    pass is all 96 rows; at 512 it is 512 of the 1,024 rows, and with the
    selection bias tilted to experts 0 and 1 the first share receives all
    1,024 and takes two passes while the three others, which receive none,
    take none."""
    x, wr, wg, wu, wd = _layer_inputs(t=t)
    rng = np.random.default_rng(1)
    shared = {name: {"kernel": jnp.asarray(
        rng.standard_normal(shape).astype(np.float32) / math.sqrt(shape[0]))}
        for name, shape in (("gate", (D, M)), ("up", (D, M)),
                            ("down", (M, D)))}
    bias = jnp.asarray(rng.standard_normal(E).astype(np.float32) * 0.1
                       + tilt * (np.arange(E) < 2))
    total = 0.0
    for first in range(0, E, 2):
        layer = KimiSparseMoe(E, 2, first, K, M, 2.446, dtype=jnp.float32)
        total = total + layer.apply({"params": {
            "router": wr, "select_bias": bias, "shared": shared,
            **{name: w[first:first + 2] for name, w in
               (("gate", wg), ("up", wu), ("down", wd))}}}, x[None])[0]
    alone = plain._swiglu(x, shared, jnp.float32)
    uncut = plain.experts(
        x, {"router": wr, "select_bias": bias, "gate": wg, "up": wu,
            "down": wd, "shared": shared}, top_k=K, first_expert=0,
        routed_scale=2.446, dtype=jnp.float32)
    assert _rel(total - 3 * alone, uncut) <= 1e-5


# --------------------------------------------------------------------------
# the model

def _model_and_params(rows=2, s=64):
    model = KimiLinearTiny(dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, 512, (rows, s)).astype(np.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


@pytest.mark.parametrize("rows,pass_rows", ((1, None), (2, None), (2, 32)))
def test_model_loss_and_gradients_are_the_plain_reference_s(
        monkeypatch, rows, pass_rows):
    """Through a dense KDA layer, a KDA expert layer, an MLA expert layer
    (keys 24 wide, values 16) and another KDA expert layer. Loss 1e-6;
    gradients 5e-5 of a leaf's largest entry: four layers' sums in another
    order, and the chunked scan against the token recurrence. With
    ``pass_rows`` a share's pass is held to 32 rows, so that each of the
    three expert layers (2 of 8 experts, 256 rows, 64 of them a share's
    even part) walks its rows in several passes inside ``KimiBlock``'s
    ``nn.remat``, forward and backward."""
    import byteps_tpu.parallel.moe as moe

    if pass_rows:
        monkeypatch.setattr(moe, "held_row_bound", lambda *shape: pass_rows)
    model, params, tokens = _model_and_params(rows)
    got, want = (jax.jit(jax.value_and_grad(f))(params) for f in (
        lambda p: kimi_linear_loss(model.apply(p, tokens)),
        lambda p: plain.causal_lm_nll(p, tokens, **PLAIN).mean()))
    assert abs(float(got[0]) - float(want[0])) <= 1e-6 * float(want[0])
    flat = jax.tree_util.tree_leaves_with_path(got[1])
    reached = 0
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want[1])):
        name = jax.tree_util.keystr(path)
        if "select_bias" in name:       # the loss never reaches the bias
            assert not bool(g.any()) and not bool(w.any())
            continue
        assert _rel(g, w) <= 5e-5, name
        reached += bool(w.any())
    assert reached == len(flat) - 3     # every other leaf has a gradient
    _, stats = model.apply(params, tokens, mutable=["moe_stats"])
    # one pass everywhere, or no layer's held rows fit one
    assert publish_moe_stats(stats["moe_stats"], held=(0, 2))[
        "bps_moe_compact_share"] == (0 if pass_rows else 1)


def test_the_comparison_fails_a_bf16_state():
    """The reference with its state rounded to bf16 after every token is
    1e-4 of the loss away, a hundred times what the program is held to."""
    model, params, tokens = _model_and_params()
    loss = float(kimi_linear_loss(model.apply(params, tokens)))
    rounded = float(plain.causal_lm_nll(
        params, tokens, **{**PLAIN, "state_dtype": jnp.bfloat16}).mean())
    assert abs(loss - rounded) > 1e-5 * loss


def test_layer_kinds_follow_the_source_s_two_lists():
    cfg = cell_lib.load_json(CONFIG + ".json")["linear_attn_config"]
    kinds = layer_kinds(cfg["kda_layers"], cfg["full_attn_layers"], 27)
    assert kinds[:5] == ("kda", "kda", "kda", "mla", "kda")
    assert kinds.count("kda") == 20 and kinds.count("mla") == 7
    assert kinds == KimiLinear48BA3B().layer_kinds
    with pytest.raises(ValueError, match="layer_kinds"):
        KimiLinearTiny(layer_kinds=("kda", "ssm")).init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))


def _config():
    return (cell_lib.load_json(CONFIG + ".json"),
            cell_lib.load_module(CONFIG + ".py", "kimi_linear_config"))


def test_parameter_count_by_hand():
    """The docstring of the configuration's ``.py``, and the published
    model: 49.1 B with 3.5 B met by a token — the name's 48B-A3B."""
    kda = (3 * 2304 * 4096 + 3 * 4 * 4096 + 2 * (2304 * 128 + 128 * 4096)
           + 4096 + 32 + 4096 + 2304 * 32 + 128 + 4096 * 2304)
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    expert, router, norms = 3 * 2304 * 1024, 2304 * 256 + 256, 2 * 2304
    assert (kda, mla, expert) == (39_518_368, 29_114_880, 7_077_888)
    dense = kda + norms + 3 * 2304 * 9216
    ends = 2 * 20_480 * 2304 + 2304
    held = norms + router + 9 * expert
    assert (dense, kda + held, mla + held, ends) == (
        103_223_968, 103_814_048, 93_410_560, 94_374_144)
    cfg, module = _config()
    assert cfg["n_params"] == dense + 3 * (kda + held) + (mla + held) + ends \
        == 602_450_816
    init, _ = module.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in
               jax.tree_util.tree_leaves(shapes)) == cfg["n_params"]
    whole = norms + router + 257 * expert
    published = (dense + 19 * (kda + whole) + 7 * (mla + whole)
                 + 2 * 163_840 * 2304 + 2304)
    active = published - 26 * 248 * expert
    assert round(published / 1e9, 1) == 49.1 and round(active / 1e9, 1) == 3.5


def test_flops_per_token_by_hand():
    cfg, module = _config()
    kda = 28_311_552 + 1_638_400 + 73_728 + 9_437_184
    mla = 14_155_776 + 1_327_104 + 4_194_304 + 9_437_184
    moe = 589_824 + 1_769_472 + 7_077_888
    assert (kda, mla, moe) == (39_460_864, 29_114_368, 9_437_184)
    recurrence = 3 * 7 * 32 * 128 * 128
    assert recurrence == 11_010_048
    base = (6 * (4 * kda + mla + 63_700_992 + 4 * moe + 47_185_920)
            + 4 * recurrence)
    assert base == 2_057_601_024
    for s, attention, want in ((8_192, 251_688_960, 2_309_289_984),
                               (16_384, 503_347_200, 2_560_948_224)):
        assert attention == 6 * (192 + 128) * 32 * (s + 1) // 2
        assert module.flops_per_token({**cfg, "seq_len": s}) \
            == base + attention == want
    assert module.flops_per_token(cfg) == 2_309_289_984      # the cell's s


def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    assert isinstance(model.apply(params, tokens), jax.Array)
    _, stats = model.apply(params, tokens,
                           mutable=["moe_stats", "kda_stats"])
    counts = jax.tree_util.tree_leaves(stats["moe_stats"])
    assert len(counts) == 3 and all(int(c.sum()) == 2 * 64 * 2
                                    for c in counts)
    decays = jax.tree_util.tree_leaves(stats["kda_stats"])
    assert len(decays) == 3 and all(float(d) < 0 for d in decays)
    out = publish_kda_stats(stats["kda_stats"])
    assert out["bps_kda_min_chunk_log_decay"] == min(map(float, decays))
    assert publish_kda_stats({}) == {}
    held = publish_moe_stats(stats["moe_stats"], held=(0, 2))
    assert 0.0 < held["bps_moe_held_load"] < 4.0


def test_scopes_and_the_site_counter():
    """Each span of the tracing is in the lowered program, forward and
    backward, and a trace of the model counts its three KDA sites. A share's
    pass is one jitted function for every layer and both directions (PR 43):
    the lowered text names the experts' scope from that function's top, and
    only the compiler joins a callee's names to its callers', so that scope
    is looked for in the compiled program's ``op_name``s, which is what a
    device trace's ``tf_op`` holds."""
    import re

    model, params, tokens = _model_and_params(1)
    before = metrics.counter(SCAN_SITES)
    lowered = jax.jit(jax.grad(lambda p: kimi_linear_loss(
        model.apply(p, tokens)))).lower(params)
    text = lowered.as_text(debug_info=True)
    assert metrics.counter(SCAN_SITES) - before >= 3
    for scope in ("bps.kda.prep", "bps.kda.scan", "bps.kda.out",
                  "bps.mla.attend", "bps.moe.shared", "bps.moe.route"):
        assert f"/{scope}/" in text, scope
        assert any(scope in line and "transpose(" in line
                   for line in text.splitlines()), scope
    names = set(re.findall(r'op_name="([^"]*bps\.moe\.experts[^"]*)"',
                           lowered.compile().as_text()))
    assert any("/jvp(" in n and "transpose(" not in n for n in names)
    assert any("/transpose(jvp(" in n for n in names)


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return kimi_linear_loss(model.apply(p, batch["tokens"]))

    one = jax.jit(loss_fn)
    alone = float(np.mean([one(params, {"tokens": tokens[i:i + 1]})
                           for i in range(8)]))
    bps.init()
    tx = optax.adamw(1e-2)
    step = make_train_step(loss_fn, tx)
    state = (replicate(params), replicate(tx.init(params)))
    losses = []
    for _ in range(3):
        *state, loss = step(*state, shard_batch({"tokens": tokens}))
        losses.append(float(loss))
    assert abs(losses[0] - alone) <= 1e-5 * alone
    assert losses[-1] < losses[0] - 0.1
    bias = state[0]["params"]["layer_1"]["ffn"]["moe"]["select_bias"]
    assert not bool(np.asarray(bias).any())     # adamw leaves a zero at zero


def test_the_reference_imports_nothing_of_the_program():
    source = open(plain.__file__).read()
    assert "byteps_tpu" not in source.split('"""', 2)[2]
    assert importlib.import_module("benchmark.lib.plain_kimi_linear") is plain
