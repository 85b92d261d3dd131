"""Phi4FlashModel and what it brought (tier-1, CPU, float32, seeded): the
selective scan with one decay a channel *and* state entry
(``selective_scan``: Mamba-1's recurrence), attention as a difference of two
softmax maps (windowed, full, cross), the Gated Memory Unit, and a layer
loop that hands a memory and a key-value pair from two layers to all later
ones under ``nn.remat``.

Yardsticks that share no code with the program: the token-by-token
recurrence (``selective_scan`` in ``benchmark/lib/plain_phi4_flash.py``) for
the scan and for the model, two dense softmax maps written out here for the
attention. In float32 on the CPU both sides differ by the order sums are
taken in: a relative 1e-5 of the largest entry.
"""

import json
import math
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (Phi4FlashTiny, Phi4MiniFlash,
                               phi4_flash_loss)
from byteps_tpu.models.kimi_linear import CONV_SITES
from byteps_tpu.models.phi4_flash import (CROSS, FULL, GMU, MAMBA,
                                          PHI4FLASH_SITES, WINDOW,
                                          DifferentialAttention,
                                          GatedMemoryUnit, Mamba1Mixer,
                                          kind_sites, lambda_init,
                                          layer_kind)
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.linear_attention import (SCAN_SITES, SEL_CHUNK,
                                                  SEL_SCAN_SITES,
                                                  SSM_SCAN_SITES,
                                                  publish_kda_stats,
                                                  sel_chunk_log_decay,
                                                  selective_scan)
from byteps_tpu.parallel.ring_attention import (KERNEL_SITES, WINDOW_SITES,
                                                XLA_SITES)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_phi4_flash as plain  # noqa: E402

CONFIG = os.path.join(REPO, "benchmark", "configs",
                      "phi-4-mini-flash-reasoning")
CELL = "phi-4-mini-flash-reasoning.collective-sambay.1chip"
PLAIN = dict(depth=8, head_dim=8, window=8, eps=1e-5, dtype=jnp.float32,
             scan_block=16, query_block=16, head_rows=32)


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


# --------------------------------------------------------------------------
# the selective scan
def _scan_inputs(s, strength, b=2, channels=12, n=4, seed=0):
    """``strength`` scales the step: at 40 a token's decay of the fastest
    state entry passes e^-88 and float32's exp of it is 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, channels))
    dt = strength * jax.nn.softplus(jax.random.normal(ks[1],
                                                      (b, s, channels)))
    a = -jnp.exp(jax.random.uniform(ks[2], (channels, n), minval=0.0,
                                    maxval=math.log(16.0)))
    return x, dt, a, jax.random.normal(ks[3], (b, s, n)), \
        jax.random.normal(ks[4], (b, s, n))


def recurrence(x, dt, a, b, c, **kwargs):
    """The reference's token-by-token scan, a sequence at a time."""
    return jax.vmap(lambda x, dt, b, c: plain.selective_scan(
        x, dt, a, b, c, scan_block=8, **kwargs))(x, dt, b, c)


@pytest.mark.parametrize("s,chunk,strength", [
    (64, 16, 1.0), (64, 8, 40.0), (50, 16, 1.0), (7, 16, 1.0), (48, 4, 5.0),
    (64, 0, 40.0)])
def test_the_chunked_scan_is_the_token_recurrence(s, chunk, strength):
    """Values and all five gradients to 1e-5, at a sequence the chunk does
    not divide, one shorter than a chunk, the default chunk, and a decay
    past e^-88 (an underflow to 0 is the exact float32 value)."""
    args = _scan_inputs(-(-s // 8) * 8, strength)
    cut = tuple(t[:, :s] if t.ndim == 3 else t for t in args)
    if strength == 40.0:
        assert float((cut[1][..., None] * cut[2]).min()) < -88.0
    want = recurrence(*args)[:, :s]
    got = selective_scan(*cut, chunk=chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    grads = jax.grad(lambda *a: (selective_scan(*a, chunk=chunk) * ct).sum(),
                     argnums=range(5))(*cut)
    padded = jnp.pad(ct, ((0, 0), (0, args[0].shape[1] - s), (0, 0)))
    wants = jax.grad(lambda *a: (recurrence(*a) * padded).sum(),
                     argnums=range(5))(*args)
    for g, w in zip(grads, wants):
        w = w[:, :s] if w.ndim == 3 else w
        assert bool(jnp.isfinite(g).all()) and _rel(g, w) < 1e-5


@pytest.mark.parametrize("wrong", ("per_channel", "state", "clamp"))
def test_the_tolerance_fails_the_tool_s_controls(wrong):
    """What ``tools/scan_check.py --cases sel`` must catch on the chip,
    caught here at 1e-5: one decay a channel (the mean over the state
    entries: Mamba-2's form of the transition), a bf16 state, a chunk's
    cumulated log-decay clamped at -20."""
    args = _scan_inputs(64, 2.0 if wrong == "clamp" else 1.0)
    control = {"per_channel": dict(per_channel=True),
               "state": dict(state_dtype=jnp.bfloat16),
               "clamp": dict(decay_floor=-20.0, floor_chunk=16)}[wrong]
    got = selective_scan(*args)
    assert _rel(got, recurrence(*args)) < 1e-5
    assert _rel(got, recurrence(*args, **control)) > 1e-3


def test_the_scan_is_causal():
    args = list(_scan_inputs(48, 1.0))
    base = selective_scan(*args)
    for i in (0, 1, 3, 4):      # x, dt, b, c
        moved = list(args)
        moved[i] = moved[i].at[:, 30:].add(1.0) if i != 1 else \
            moved[i].at[:, 30:].mul(2.0)
        out = selective_scan(*moved)
        assert bool((out[:, :30] == base[:, :30]).all())
        assert float(jnp.abs(out[:, 30:] - base[:, 30:]).max()) > 1e-3


def test_scan_shapes_are_checked_and_its_sites_counted_apart():
    x, dt, a, b, c = _scan_inputs(16, 1.0)
    with pytest.raises(ValueError, match="selective_scan"):
        selective_scan(x, dt, a.T, b, c)
    with pytest.raises(ValueError, match="selective_scan"):
        selective_scan(x, dt[:, :8], a, b, c)
    names = (SEL_SCAN_SITES, SSM_SCAN_SITES, SCAN_SITES)
    before = [metrics.counter(n) for n in names]
    jax.jit(selective_scan).lower(x, dt, a, b, c)
    assert [metrics.counter(n) - v for n, v in zip(names, before)] == [
        1, 0, 0]


def test_the_chunk_gauge_reads_the_fastest_entry_s_decay():
    x, dt, a, b, c = _scan_inputs(40, 1.0)
    got = sel_chunk_log_decay(dt, a)
    assert got.shape == (2, -(-40 // SEL_CHUNK), 12)
    first = float(dt[0, :SEL_CHUNK, 3].sum() * a[3].min())
    assert abs(float(got[0, 0, 3]) - first) < 1e-4 * abs(first)


# --------------------------------------------------------------------------
# differential attention
def two_dense_maps(q, k, v, lam, init, subln, window, eps=1e-5):
    """[b, s, heads, d] by halves (module docstring of phi4_flash.py),
    written out pair by pair: two softmax maps over the square, masked."""
    b, s, heads, d = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (back >= 0) if window is None else (back >= 0) & (back < window)
    out = []
    for i in range(pairs):
        j = i // (pairs // kv_pairs)
        maps = [jax.nn.softmax(jnp.where(seen, jnp.einsum(
            "bqd,bkd->bqk", q[:, :, i + m * pairs], k[:, :, j + m * kv_pairs])
            / math.sqrt(d), -jnp.inf), axis=-1) for m in (0, 1)]
        value = jnp.concatenate([v[:, :, j], v[:, :, j + kv_pairs]], -1)
        o = jnp.einsum("bqk,bkd->bqd", maps[0] - lam * maps[1], value)
        out.append(o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
                   * subln * (1.0 - init))
    return jnp.stack(out, 2).reshape(b, s, -1)


def _attention(index=3, window=8, cross=False, s=24, seed=0):
    layer = DifferentialAttention(8, 4, 8, index, window, cross, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, s, 32))
    handed = tuple(jax.random.normal(k, (2, s, 4, 8)) for k in ks[1:3])
    args = (x, *handed) if cross else (x,)
    params = layer.init(ks[3], *args)
    # lambdas and the sub-norm away from where they start
    p = dict(params["params"])
    for n, name in enumerate(("q1", "k1", "q2", "k2")):
        p[f"lambda_{name}"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(10 + n), (8,))
    p["subln"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(20), (16,))
    return layer, {"params": p}, args


def _dense_attention(p, x, k, v, index, window):
    b, s, _ = x.shape
    q = (x @ p["q"]["kernel"]).reshape(b, s, 8, 8)
    if k is None:
        k, v = ((x @ p[n]["kernel"]).reshape(b, s, 4, 8) for n in "kv")
    lam = (jnp.exp(p["lambda_q1"] @ p["lambda_k1"])
           - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lambda_init(index))
    return two_dense_maps(q, k, v, lam, lambda_init(index), p["subln"],
                          window) @ p["o"]["kernel"]


@pytest.mark.parametrize("window,cross", [(8, False), (None, False),
                                          (None, True)])
def test_differential_attention_is_two_dense_softmax_maps(window, cross):
    """8 query heads over 4 key heads are 4 pairs over 2 key pairs; windowed
    (8), full, and cross on handed K and V; lambda and the sub-norm away
    from their initial values; the gradients too."""
    layer, params, args = _attention(5, window, cross)
    out, k, v = layer.apply(params, *args)
    handed = args[1:] if cross else (None, None)
    want = _dense_attention(params["params"], args[0], *handed, 5, window)
    assert _rel(out, want) < 1e-5
    if cross:
        assert k is args[1] and v is args[2]
        assert "k" not in params["params"] and "v" not in params["params"]
    else:
        assert k.shape == v.shape == (2, 24, 4, 8)
    got = jax.grad(lambda p: layer.apply(p, *args)[0].sum())(params)
    wanted = jax.grad(lambda p: _dense_attention(
        p, args[0], *handed, 5, window).sum())(params["params"])
    for name, g in jax.tree_util.tree_leaves_with_path(got["params"]):
        w = wanted
        for key in name:
            w = w[key.key]
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(name)


@pytest.mark.parametrize("off", (7, 9))
def test_a_band_one_key_off_fails(off):
    layer, params, args = _attention(3, 8)
    out = layer.apply(params, *args)[0]
    assert _rel(out, _dense_attention(params["params"], args[0], None, None,
                                      3, 8)) < 1e-5
    assert _rel(out, _dense_attention(params["params"], args[0], None, None,
                                      3, off)) > 1e-3


def test_the_lambda_formula_and_the_published_index():
    """lambda_init = 0.8 - 0.6 exp(-0.3 l) at the PUBLISHED l: a cut model's
    layer 17 is not its fourth."""
    assert lambda_init(0) == pytest.approx(0.2)
    assert lambda_init(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
    layer, params, args = _attention(17, None)
    out = layer.apply(params, *args)[0]
    assert _rel(out, _dense_attention(params["params"], args[0], None, None,
                                      17, None)) < 1e-5
    assert _rel(out, _dense_attention(params["params"], args[0], None, None,
                                      3, None)) > 1e-3
    model = Phi4FlashTiny(layers=(0, 1, 4, 5, 6, 7), dtype=jnp.float32)
    bound = model.bind(model.init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32)))
    assert bound.kinds == (MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS)
    assert [layer_kind(i, 16) for i in (0, 1, 15, 16, 17, 18, 19, 30, 31)] \
        == [MAMBA, WINDOW, WINDOW, MAMBA, FULL, GMU, CROSS, GMU, CROSS]
    kinds = [layer_kind(i, 16) for i in range(32)]
    assert [kinds.count(k) for k in (MAMBA, WINDOW, FULL, GMU, CROSS)] == [
        9, 8, 1, 7, 7]


def test_layers_that_read_need_their_writer():
    tokens = jnp.zeros((1, 8), jnp.int32)
    for held in ((0, 1, 6), (0, 1, 4, 7), (1, 0), (0, 9)):
        with pytest.raises(ValueError):
            Phi4FlashTiny(layers=held).init(jax.random.PRNGKey(0), tokens)


# --------------------------------------------------------------------------
# the mixers
def test_the_mamba_mixer_is_causal_and_hands_on_y_before_the_gate():
    mixer = Mamba1Mixer(24, 4, 4, 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 16))
    params = mixer.init(jax.random.PRNGKey(1), x)
    p = params["params"]
    assert p["A_log"].shape == (24, 4) and p["dt_proj"].shape == (3, 24)
    assert p["x_proj"].shape == (24, 3 + 2 * 4) and p["conv"].shape == (4, 24)
    out, y = mixer.apply(params, x)
    moved, y_moved = mixer.apply(params, x.at[:, 12:].add(1.0))
    assert bool((moved[:, :12] == out[:, :12]).all())
    assert bool((y_moved[:, :12] == y[:, :12]).all())
    # out = W_out (y SiLU(z)): y carries the skip and no gate
    z = (x @ p["in"]["kernel"])[..., 24:]
    assert _rel(out, (y * jax.nn.silu(z)) @ p["out"]["kernel"]) < 1e-5
    want_out, want_y = jax.vmap(lambda row: plain._mamba(
        row, p, dtype=jnp.float32, scan_block=8))(x)
    assert _rel(out, want_out) < 1e-5 and _rel(y, want_y) < 1e-5


def test_the_gated_memory_unit():
    unit = GatedMemoryUnit(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    m = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 24))
    params = unit.init(jax.random.PRNGKey(2), x, m)
    p = params["params"]
    want = (m * jax.nn.silu(x @ p["in"]["kernel"])) @ p["out"]["kernel"]
    assert _rel(unit.apply(params, x, m), want) < 1e-5
    assert p["in"]["kernel"].shape == (16, 24)


# --------------------------------------------------------------------------
# the model
def _model_and_params(rows=2, s=64, **kwargs):
    model = Phi4FlashTiny(dtype=jnp.float32, **kwargs)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (rows, s), 0, 512)
    params = model.init(jax.random.PRNGKey(1), tokens[:, :8])
    return model, params, tokens


def _plain_loss(p, tokens, **kwargs):
    return plain.causal_lm_nll(p, tokens, **{**PLAIN, **kwargs}).mean()


@pytest.mark.parametrize("held", (tuple(range(8)), (0, 1, 4, 5, 6, 7)))
def test_model_loss_and_gradients_are_the_plain_reference_s(held):
    """The whole tiny model and the cut the rehearsal runs: the loss and
    every gradient leaf to 1e-5."""
    model, params, tokens = _model_and_params(layers=held)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: phi4_flash_loss(model.apply(p, tokens))))(params)
    want, wanted = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, tokens)))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    assert abs(float(loss) - math.log(512)) < 1.0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(wanted)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, name
        # a lambda vector's gradient is one scalar's (d loss / d lambda, a
        # sum over every position and pair that all but cancels: 1e-4 of a
        # leaf that is itself 1e-4) times the other vector
        assert _rel(g, w) < (1e-4 if "lambda_" in name else 1e-5), name


@pytest.mark.parametrize("wrong", ("window", "state", "per_channel"))
def test_the_comparison_fails_what_it_should(wrong):
    """A window one key longer, a bf16 state, one decay a channel."""
    model, params, tokens = _model_and_params()
    loss = float(phi4_flash_loss(model.apply(params, tokens)))
    control = {"window": dict(window=9), "state": dict(
        state_dtype=jnp.bfloat16), "per_channel": dict(per_channel=True)}
    grads = jax.grad(lambda p: phi4_flash_loss(model.apply(p, tokens)))(
        params)
    wanted = jax.grad(lambda p: _plain_loss(p, tokens, **control[wrong]))(
        params)
    worst = max(_rel(g, w) for g, w in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(wanted)))
    assert worst > 1e-4, (wrong, worst, loss)


def _writer_gradients(model, params, tokens):
    """d loss / d (layer 4's Mamba parameters, layer 5's W_k, W_v)."""
    grads = jax.grad(lambda p: phi4_flash_loss(model.apply(p, tokens)))(
        params)["params"]
    return grads["layer_4_mixer"]["ssm"], {
        n: grads["layer_5_mixer"]["attn"][n] for n in "kv"}


def test_the_readers_gradients_reach_the_writers_with_and_without_remat(
        monkeypatch):
    """Layer 6 (a GMU) reads layer 4's memory and layer 7 (cross) layer 5's
    K and V: with layer 5's own attention output cut from the stream (its
    ``W_o`` zero) ``W_k`` and ``W_v`` have a gradient through the cross
    layer alone; with layer 4's ``W_out`` zero the Mamba parameters have one
    through the GMU alone. All equal with ``nn.remat`` taken out."""
    model, params, tokens = _model_and_params()
    cut = jax.tree_util.tree_map(lambda x: x, params)
    cut["params"]["layer_5_mixer"]["attn"]["o"]["kernel"] = jnp.zeros_like(
        cut["params"]["layer_5_mixer"]["attn"]["o"]["kernel"])
    cut["params"]["layer_4_mixer"]["ssm"]["out"]["kernel"] = jnp.zeros_like(
        cut["params"]["layer_4_mixer"]["ssm"]["out"]["kernel"])
    ssm, kv = _writer_gradients(model, cut, tokens)
    for name in ("A_log", "dt_proj", "x_proj", "conv", "D", "dt_bias"):
        assert float(jnp.abs(ssm[name]).max()) > 0, name
    assert all(float(jnp.abs(g["kernel"]).max()) > 0 for g in kv.values())
    # ... and with the readers gone, nothing arrives
    deaf = jax.tree_util.tree_map(lambda x: x, cut)
    for layer, mixer, name in ((6, "gmu", "out"), (7, "attn", "o")):
        kernel = deaf["params"][f"layer_{layer}_mixer"][mixer][name]
        kernel["kernel"] = jnp.zeros_like(kernel["kernel"])
    ssm_deaf, kv_deaf = _writer_gradients(model, deaf, tokens)
    assert float(jnp.abs(ssm_deaf["A_log"]).max()) == 0
    assert all(float(jnp.abs(g["kernel"]).max()) == 0
               for g in kv_deaf.values())
    with_remat = jax.grad(lambda p: phi4_flash_loss(
        model.apply(p, tokens)))(params)
    monkeypatch.setattr(nn, "remat", lambda module, **kwargs: module)
    plain_model = Phi4FlashTiny(dtype=jnp.float32)
    without = jax.grad(lambda p: phi4_flash_loss(
        plain_model.apply(p, tokens)))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(with_remat),
                            jax.tree_util.tree_leaves(without)):
        assert _rel(g, w) < 1e-6, jax.tree_util.keystr(path)


def test_the_handed_tensors_are_kept_once(capsys):
    """Under ``nn.remat`` every half keeps its inputs: the memory is the
    same array at every reader, so the model's residuals hold one [s,
    d_inner] memory in the compute dtype whether one layer reads it or
    two (and K and V likewise)."""
    tokens = jnp.zeros((1, 64), jnp.int32)

    def kept(held, shape):
        model = Phi4FlashTiny(dtype=jnp.bfloat16, num_layers=12,
                              layers=held)
        params = model.init(jax.random.PRNGKey(0), tokens[:, :8])
        jax.ad_checkpoint.print_saved_residuals(
            lambda p: phi4_flash_loss(model.apply(p, tokens)), params)
        return capsys.readouterr().out.count(f"bf16[{shape}]")

    # published depth 12: Mamba 6 hands on m, 7 is full, 8 and 10 are GMUs,
    # 9 and 11 cross
    assert kept((0, 6, 7, 8), "1,64,128") == kept((0, 6, 7, 8, 10),
                                                  "1,64,128") >= 1
    assert kept((0, 6, 7, 9), "1,64,2,8") == kept((0, 6, 7, 9, 11),
                                                  "1,64,2,8") >= 2


# --------------------------------------------------------------------------
# the configuration
def _config():
    cfg = cell_lib.load_json(CONFIG + ".json")
    return cfg, cell_lib.load_module(CONFIG + ".py", "phi4_flash_config")


def test_parameter_count_by_hand():
    """n_params by the docstring's arithmetic, by ``eval_shape`` of the cut
    model, and the published model's 3.85 B."""
    cfg, config = _config()
    d, inner, n, rank = 2560, 5120, 16, 160
    mlp = 3 * d * 10240 + 4 * d
    mamba = (d * 2 * inner + 4 * inner + inner + inner * (rank + 2 * n)
             + rank * inner + inner + inner * n + inner + inner * d)
    attention = 2 * d * d + 2 * d * 1280 + 4 * 64 + 128
    cross = 2 * d * d + 4 * 64 + 128
    gmu = 2 * d * inner
    assert (mamba, attention, cross, gmu) == (41_241_600, 19_661_184,
                                              13_107_584, 26_214_400)
    cut = (6 * mlp + 2 * mamba + 2 * attention + cross + gmu
           + 25_008 * d + 2 * d)
    assert cut == cfg["n_params"] == 697_073_792
    init, _ = config.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(shapes)) == cut
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(shapes))
    whole = (32 * mlp + 9 * mamba + 9 * attention + 7 * cross + 7 * gmu
             + 200_064 * d + 2 * d)
    assert whole == cfg["n_params_published"] == 3_852_457_984
    published = jax.eval_shape(Phi4MiniFlash().init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    assert sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(published)) == whole


def test_flops_per_token_by_hand():
    cfg, config = _config()
    d, s = 2560, cfg["seq_len"]
    products = 6 * (6 * 78_643_200 + 2 * 41_123_840 + 2 * 19_660_800
                    + 13_107_200 + 26_214_400)
    recurrence_ops = 2 * 3 * 5 * 5120 * 16
    band = 512 * s - 512 * 511 // 2
    pairs = (2 * (s * (s + 1) // 2) + band) * 20 * 2304
    head = (s - 1) * 6 * d * 25_008
    want = (s * (products + recurrence_ops) + pairs + head) // s
    assert config.flops_per_token(cfg) == want
    assert config.layer_counts(cfg) == {"mamba": 2, "window": 1, "full": 1,
                                        "cross": 1, "gmu": 1}
    if s == 16384:
        assert want == 4_961_303_355
    assert config.flops_per_token({**cfg, "seq_len": 8192}) == 4_583_424_630


def test_the_readers_counts_by_hand():
    cfg, _ = _config()
    layers = os.path.join(REPO, "benchmark", "layers")
    sel = cell_lib.load_module(os.path.join(layers, "sel.py"), "sel_reader")
    dattn = cell_lib.load_module(os.path.join(layers, "dattn.py"),
                                 "dattn_reader")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    at = {**cfg, "seq_len": 16384}
    assert sel.scan_flops(16384, 5120, 16, 2) == 40_265_318_400
    assert sel.scan_bytes(16384, 5120, 16, 2) == 4_034_920_448
    # bound by bandwidth: 4.03 GB / 819 GB/s = 4.927 ms
    assert sel.scan_roofline_pct(10.0, at, 16384, 2, peaks) == pytest.approx(
        100 * 4_034_920_448 / 819e9 / 10e-3)
    assert dattn.attend_flops(1, 16384, 20, 64, 512) == 8_257_792 * 46_080
    assert dattn.attend_flops(1, 16384, 20, 64) == 134_225_920 * 46_080
    # bound by arithmetic: 2 x 6.185 TFLOP / 197 TFLOP/s = 62.79 ms
    assert dattn.roofline_pct(100.0, at, 1, peaks, 2) == pytest.approx(
        100 * 2 * 134_225_920 * 46_080 / 197e12 / 100e-3)
    assert dattn.roofline_pct(10.0, at, 1, peaks, 1, 512) == pytest.approx(
        100 * 8_257_792 * 46_080 / 197e12 / 10e-3)
    manifest = cell_lib.load_json(os.path.join(REPO, "BENCHMARK.json"))
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 15 and manifest["per_layer"][-15:] == mine
    assert {m["name"].split(".")[0] for m in mine} == {"sel", "dattn", "gmu"}


def test_the_configuration_file_against_the_catalog_row():
    """Every key of the catalog row's ``config`` as published, but those in
    ``reduced``; the held layers are published indices with every kind."""
    cfg, config = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differing == {"num_hidden_layers", "vocab_size"}
    assert [r.split()[0] for r in cfg["reduced"]] == ["num_hidden_layers",
                                                      "vocab_size"]
    assert cfg["num_hidden_layers_published"] == \
        row["config"]["num_hidden_layers"]
    assert cfg["layer_indices"] == [0, 1, 16, 17, 18, 19]
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_expand"]) == (16, 4, 2)
    assert all(config.layer_counts(cfg).values())
    entry = next(c for c in cell_lib.load_json(os.path.join(
        REPO, "BENCHMARK.json"))["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]


def test_stats_are_sown_only_when_asked_for_and_published():
    model, params, tokens = _model_and_params()
    assert model.apply(params, tokens).shape == (2, 63)
    _, stats = model.apply(params, tokens, mutable=["sel_stats"])
    decays = jax.tree_util.tree_leaves(stats["sel_stats"])
    assert len(decays) == 3 and all(float(d) < 0 for d in decays)
    out = publish_kda_stats(stats["sel_stats"],
                            "bps_sel_min_chunk_log_decay")
    assert out == {"bps_sel_min_chunk_log_decay": min(map(float, decays))}


def test_scopes_and_the_site_counters():
    """Each span of the tracing is in the lowered program, forward and
    backward, and one trace of the loss counts its eight layers by kind,
    three scan and three convolution sites, and two attention sites a
    differential-attention layer (on the CPU the XLA form), two of the four
    windowed calls' under the window counter."""
    model, params, tokens = _model_and_params(1)
    names = (PHI4FLASH_SITES, kind_sites(MAMBA), kind_sites(WINDOW),
             kind_sites(FULL), kind_sites(GMU), kind_sites(CROSS),
             SEL_SCAN_SITES, CONV_SITES, XLA_SITES, KERNEL_SITES,
             WINDOW_SITES, SSM_SCAN_SITES)
    before = [metrics.counter(n) for n in names]
    jax.jit(lambda p: phi4_flash_loss(model.apply(p, tokens))).lower(params)
    assert [metrics.counter(n) - b for n, b in zip(names, before)] == [
        8, 3, 2, 1, 1, 1, 3, 3, 8, 0, 4, 0]
    text = jax.jit(jax.grad(lambda p: phi4_flash_loss(
        model.apply(p, tokens)))).lower(params).as_text(debug_info=True)
    for scope in ("bps.sel.proj", "bps.sel.prep", "bps.sel.scan",
                  "bps.sel.out", "bps.dattn.proj", "bps.dattn.window",
                  "bps.dattn.full", "bps.dattn.cross", "bps.dattn.diff",
                  "bps.gmu"):
        assert f"/{scope}/" in text, scope
        assert any(scope in line and "transpose(" in line
                   for line in text.splitlines()), scope
    assert "/bps.ssm.scan/" not in text and "/bps.kda.scan/" not in text


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model, params, tokens = _model_and_params(8, 32)

    def loss_fn(p, batch):
        return phi4_flash_loss(model.apply(p, batch["tokens"]))

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


def test_the_reference_imports_nothing_of_the_program():
    source = open(plain.__file__).read()
    assert "import byteps_tpu" not in source
    assert "from byteps_tpu" not in source
