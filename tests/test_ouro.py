"""OuroModel, its loop and its objective (tier-1, CPU, float32, seeded).

The yardstick shares no code with the program: ``benchmark/lib/
plain_ouro.py``, a Python loop over passes and layers with the exit
distribution as the paper prints it. In float32 on the CPU the two differ by
the order sums are taken in: a relative 1e-5 of a leaf's largest entry
through 4 passes x 3 layers, forward and backward.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from byteps_tpu.models import (Ouro2_6B, OuroModel, OuroTiny, lm_loss,
                               ouro_loss, publish_loop_stats)
from byteps_tpu.models.ouro import exit_distribution

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import plain_ouro  # noqa: E402

L, R, S = 3, 4, 32
PLAIN = dict(num_layers=L, num_heads=4, eps=1e-6, rope_theta=1e6,
             dtype=jnp.float32)
# |got - want| <= RTOL x the leaf's largest |entry|: float32 sums taken in
# another order (scan against a Python loop, logs against products)
RTOL = 1e-5


def _close(got, want, rtol=RTOL):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) <= rtol * max(scale, 1e-30)


def _tokens(rows, seed=0):
    return np.random.default_rng(seed).integers(0, 512, (rows, S),
                                                dtype=np.int32)


def _params(model, seed=0, gate=0.5):
    """Seeded weights; the exit gate (zero at initialisation) gets normal
    weights of scale ``gate``, so that the four exits differ by position."""
    params = model.init(jax.random.PRNGKey(seed), _tokens(1))
    assert list(params) == ["params"]
    p = dict(params["params"])
    rng = np.random.default_rng(seed + 100)
    p["exit_gate"] = {
        "kernel": jnp.asarray(gate * rng.standard_normal((64, 1)),
                              jnp.float32),
        "bias": jnp.asarray(gate * rng.standard_normal(1), jnp.float32)}
    return {"params": p}


def _plain_loss(params, tokens, *, beta=0.05, num_passes=R, **over):
    return plain_ouro.looped_lm_loss_per_position(
        params, tokens, num_passes=num_passes, beta=beta,
        **{**PLAIN, **over}).mean()


def _tree_close(got, want, rtol=RTOL):
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    leaves = jax.tree_util.tree_leaves(got)
    assert len(flat) == len(leaves)
    return [jax.tree_util.keystr(path) for (path, w), g in zip(flat, leaves)
            if not _close(g, w, rtol)]


class UnrolledOuro(OuroModel):
    """The other form of the loop, for the comparison alone: the passes one
    after the other in the program text, the same methods and parameters."""

    def __call__(self, tokens):
        x, outs = self.embed(tokens), []
        for _ in range(self.num_passes):
            x, out = self._pass(x, tokens)
            outs.append(out)
        return tuple(jnp.stack(o) for o in zip(*outs))


# -------------------------------------------------------------------------
# the model against the plain reference

@pytest.mark.parametrize("rows", (1, 2))
def test_loss_and_every_gradient_leaf_are_the_plain_reference_s(rows):
    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model, seed=rows), _tokens(rows, seed=rows)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: ouro_loss(model.apply(p, tokens))))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, tokens)))(params)
    assert abs(float(got) - float(want)) <= 2e-6 * float(want)
    # embed, head, final norm, gate's two, and 11 leaves a block
    assert len(jax.tree_util.tree_leaves(got_g)) == 5 + 11 * L
    assert _tree_close(got_g, want_g) == []
    for leaf in jax.tree_util.tree_leaves(got_g):
        assert np.asarray(leaf).any()       # the gradient reaches every leaf


def test_a_block_s_gradient_is_the_sum_over_the_four_passes():
    """The weight-sharing test: the tree holds L blocks, not R x L, and a
    shared block's gradient is the sum of the gradients that a plain model
    with R x L separately parametrised blocks, set to the same values,
    gives its R copies."""
    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model), _tokens(2)
    blocks = sorted(k for k in params["params"] if k.startswith("layer_"))
    assert blocks == [f"layer_{i}" for i in range(L)]
    got = jax.jit(jax.grad(
        lambda p: ouro_loss(model.apply(p, tokens))))(params)["params"]

    untied = {k: v for k, v in params["params"].items()
              if not k.startswith("layer_")}
    for r in range(R):
        for i in range(L):
            untied[f"layer_{r * L + i}"] = params["params"][f"layer_{i}"]
    parts = jax.jit(jax.grad(lambda p: _plain_loss(
        p, tokens, untied=True)))({"params": untied})["params"]
    for i in range(L):
        copies = [parts[f"layer_{r * L + i}"] for r in range(R)]
        summed = jax.tree_util.tree_map(lambda *g: sum(g), *copies)
        assert _tree_close(got[f"layer_{i}"], summed) == [], i
        # and no single pass gives it: each copy is a part, not the whole
        for one in copies:
            assert not _close(one["mlp"]["down"]["kernel"],
                              got[f"layer_{i}"]["mlp"]["down"]["kernel"],
                              rtol=1e-2)
    for shared in ("embed", "lm_head", "final_norm", "exit_gate"):
        assert _tree_close(got[shared], parts[shared]) == [], shared


@pytest.mark.parametrize("variant", ("dropped_pass", "bf16_gate",
                                     "detached_gate", "norm_outside_loop"))
def test_the_tolerance_has_teeth(variant):
    """What a wrong implementation would compute misses the reference by
    far more than RTOL: a pass left out, the gate in bf16, the gate
    detached, the final norm applied to the exits only."""
    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model), _tokens(2)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, tokens)))(params)

    def wrong(p):
        if variant == "dropped_pass":
            return ouro_loss(OuroTiny(dtype=jnp.float32, num_passes=R - 1)
                             .apply(p, tokens))
        nll, gate, _ = model.apply(p, tokens)
        if variant == "bf16_gate":
            return ouro_loss((nll, gate.astype(jnp.bfloat16)
                              .astype(jnp.float32)))
        if variant == "detached_gate":       # the loss is right, not
            return ouro_loss((nll, jax.lax.stop_gradient(gate)))  # its slope
        q = p["params"]
        # norm_outside_loop: passes chained on the un-normed stream
        x, exits = q["embed"]["embedding"][tokens], []
        for _ in range(R):
            for i in range(L):
                x = plain_ouro.block(x, q[f"layer_{i}"], num_heads=4,
                                     eps=1e-6, rope_theta=1e6,
                                     dtype=jnp.float32)
            h = plain_ouro._rms_norm(x, q["final_norm"]["scale"], 1e-6)
            exits.append(plain_ouro.exit_of(h, q["lm_head"], q["exit_gate"],
                                            tokens, jnp.float32))
        nll = jnp.stack([e[0] for e in exits])
        lam = jnp.stack([e[1] for e in exits])
        gate = jnp.pad(jnp.log(lam) - jnp.log1p(-lam), ((0, 0), (0, 0),
                                                         (0, 1)))
        return ouro_loss((nll, gate))

    got, got_g = jax.jit(jax.value_and_grad(wrong))(params)
    off = abs(float(got) - float(want)) > 20 * 2e-6 * float(want)
    assert off or len(_tree_close(got_g, want_g, rtol=20 * RTOL)) > 0


def test_the_scan_over_the_passes_is_the_passes_unrolled():
    """The kept form of the loop against the other one, on the same
    parameters and the same block code: loss, outputs and every gradient
    leaf to 2e-6 of the leaf's largest entry. What differs is the order in
    which the four passes' partial gradients are added (the scan's backward
    loop adds them last pass first; XLA adds the unrolled ones as it
    likes): measured 3e-7 to 1.01e-6, a few float32 roundings of 6e-8."""
    kept, unrolled = (m(dtype=jnp.float32) for m in (
        OuroTiny, lambda **kw: UnrolledOuro(
            vocab_size=512, num_layers=L, d_model=64, num_heads=4,
            mlp_dim=128, **kw)))
    params, tokens = _params(kept), _tokens(2)
    def loss_and_outputs(model):
        def loss(p):
            outputs = model.apply(p, tokens)
            return ouro_loss(outputs), outputs
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    ((a, a_out), a_g), ((b, b_out), b_g) = (loss_and_outputs(m)
                                            for m in (kept, unrolled))
    assert abs(float(a) - float(b)) <= 1e-6 * float(b)
    assert _tree_close(a_g, b_g, rtol=2e-6) == []
    for x, y in zip(a_out, b_out):
        assert x.shape == y.shape and _close(x, y, rtol=2e-6)


# -------------------------------------------------------------------------
# the exit distribution and the objective's corners

@pytest.mark.parametrize("scale", (0.0, 1.0, 30.0))
def test_the_exit_distribution_sums_to_one(scale):
    logits = scale * jnp.asarray(np.random.default_rng(0).standard_normal(
        (R, 2, S)), jnp.float32)
    log_p = exit_distribution(logits)
    p = np.asarray(jnp.exp(log_p), np.float64)
    assert p.shape == (R, 2, S) and np.isfinite(np.asarray(log_p)).all()
    assert np.abs(p.sum(axis=0) - 1.0).max() <= 1e-6
    if scale == 0.0:
        assert (p == np.array([0.5, 0.25, 0.125, 0.125])[:, None, None]).all()
    # by hand: lambda_r prod_{j<r} (1 - lambda_j), the last takes the rest
    lam = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    left, want = 1.0, []
    for r in range(R):
        want.append(left * lam[r] if r < R - 1 else left * np.ones_like(lam[r]))
        left = left * (1.0 - lam[r])
    assert np.abs(p - np.stack(want)).max() <= 1e-6
    # the last pass's own gate is not read
    other = logits.at[-1].set(7.0)
    assert np.array_equal(np.asarray(exit_distribution(other)),
                          np.asarray(log_p))


def _with_gate(params, bias):
    p = dict(params["params"])
    p["exit_gate"] = {"kernel": jnp.zeros((64, 1), jnp.float32),
                      "bias": jnp.full((1,), bias, jnp.float32)}
    return {"params": p}


def test_gates_shut_and_no_entropy_term_is_lm_loss_of_the_last_pass():
    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _with_gate(_params(model), -30.0), _tokens(2)
    outputs = model.apply(params, tokens)
    logits = outputs[2][-1] @ params["params"]["lm_head"]["kernel"]
    want = float(lm_loss(logits, tokens))
    assert abs(float(ouro_loss(outputs, beta=0.0)) - want) <= 2e-6 * want
    # gates wide open: the first pass's loss instead
    first = model.apply(_with_gate(params, 30.0), tokens)
    want = float(lm_loss(first[2][0] @ params["params"]["lm_head"]["kernel"],
                         tokens))
    assert abs(float(ouro_loss(first, beta=0.0)) - want) <= 2e-6 * want
    # at beta > 0 and the zero gate the entropy of (1/2, 1/4, 1/8, 1/8)
    # comes off: 1.75 ln 2
    zero = model.apply(_with_gate(params, 0.0), tokens)
    assert abs(float(ouro_loss(zero, beta=0.0) - ouro_loss(zero, beta=1.0))
               - 1.75 * math.log(2.0)) <= 1e-5


@pytest.mark.parametrize("bias", (-30.0, 0.0))
def test_one_pass_is_a_plain_decoder_and_the_loss_is_lm_loss(bias):
    """R = 1: the one exit takes all of p whatever its gate says, the
    entropy is 0, and the model is the plain reference's stack run once."""
    model = OuroTiny(dtype=jnp.float32, num_passes=1)
    params, tokens = _with_gate(_params(model), bias), _tokens(2)
    outputs = model.apply(params, tokens)
    assert [o.shape[0] for o in outputs] == [1, 1, 1]
    with jax.default_matmul_precision("highest"):
        (h,) = plain_ouro.passes(params, tokens, num_passes=1, **PLAIN)
    assert _close(outputs[2][0], h)
    want = float(lm_loss(h @ params["params"]["lm_head"]["kernel"], tokens))
    assert abs(float(ouro_loss(outputs, beta=0.7)) - want) <= 2e-6 * want


# -------------------------------------------------------------------------
# what the program holds, counts and says

def test_one_pass_s_logits_are_live_at_a_time():
    """The loss's gradient program holds no array with the passes and the
    vocabulary in one shape, and the exits are inside the loop's body."""
    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model), _tokens(2)
    text = jax.jit(jax.grad(lambda p: ouro_loss(model.apply(p, tokens)))
                   ).lower(params).as_text(debug_info=True)
    for shape in ("4x2x32x512", "4x2x31x512"):
        assert shape not in text
    assert "2x32x512" in text                      # one pass's logits
    assert "bps.loop.stack" in text and "bps.loop.exit" in text
    assert text.count("stablehlo.while") >= 2      # forward and backward


def test_stats_are_sown_only_when_asked_for_and_published():
    from byteps_tpu.monitor import metrics

    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model, gate=0.0), _tokens(2)
    nll, gate, hidden = jax.jit(model.apply)(params, tokens)
    assert (nll.shape, gate.shape, hidden.shape) == (
        (R, 2, S - 1), (R, 2, S), (R, 2, S, 64))
    assert {nll.dtype, gate.dtype, hidden.dtype} == {jnp.dtype(jnp.float32)}
    _, stats = jax.jit(lambda p: model.apply(
        p, tokens, mutable=["loop_stats"]))(params)
    before = metrics._py_counters.get("bps_loop_block_applications_total", 0)
    published = publish_loop_stats(stats["loop_stats"])
    assert published == {"bps_loop_mean_exit_pass": 1.875,
                         "bps_loop_block_applications_total": float(R * L)}
    assert metrics._py_gauges["bps_loop_mean_exit_pass"] == 1.875
    assert metrics._py_counters["bps_loop_block_applications_total"] == \
        before + R * L
    assert publish_loop_stats({}) == {}
    # an open gate leaves earlier
    _, stats = model.apply(_with_gate(params, 2.0), tokens,
                           mutable=["loop_stats"])
    assert publish_loop_stats(stats["loop_stats"])[
        "bps_loop_mean_exit_pass"] < 1.2


def _config():
    path = os.path.join(REPO, "benchmark", "configs", "ouro-2.6b")
    return (cell_lib.load_json(path + ".json"),
            cell_lib.load_module(path + ".py", "cfg_ouro"))


def test_parameter_count_by_hand():
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    outside = 2 * 49_152 * 2048 + 2048 + 2049
    assert (block, outside) == (51_388_416, 201_330_689)
    assert outside + 48 * block == 2_667_974_657       # the "2.6B"

    def count(model):
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                np.zeros((1, 8), np.int32))
        assert list(shapes) == ["params"]
        return sum(math.prod(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(shapes))

    assert count(Ouro2_6B()) == 2_667_974_657
    cfg, _ = _config()
    layers = cfg["num_hidden_layers"]
    assert 4 <= layers <= 9 and cfg["total_ut_steps"] == 4
    assert count(Ouro2_6B(num_layers=layers)) == cfg["n_params"] \
        == outside + layers * block
    assert cfg["reduced"] == [f"num_hidden_layers 48 -> {layers}"]


def test_flops_per_token_by_hand():
    cfg, module = _config()
    application = 6 * 51_380_224 + 6 * 4096 * 2048
    leave = 6 * 2048 * 49_152 + 6 * 2048
    assert (application, leave) == (358_612_992, 603_992_064)
    for layers, want in ((4, 8_153_776_128), (5, 9_588_228_096),
                         (48, 71_269_662_720)):
        assert module.flops_per_token(
            {**cfg, "num_hidden_layers": layers}) \
            == 4 * (layers * application + leave) == want
    assert module.flops_per_token(cfg) == 9_588_228_096     # the cell's L


def test_the_model_trains_through_make_train_step_on_the_mesh():
    """bps.init() -> make_train_step(loss_fn, adamw) -> step on 8 virtual
    chips: the first loss is the single-device loss of the same batch and
    the loss falls."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)

    model = OuroTiny(dtype=jnp.float32)
    params, tokens = _params(model, gate=0.0), _tokens(8)

    def loss_fn(p, batch):
        return ouro_loss(model.apply(p, batch["tokens"]))

    alone = float(np.mean([loss_fn(params, {"tokens": tokens[i:i + 1]})
                           for i in range(8)]))
    bps.init()
    tx = optax.adamw(1e-2)
    step = make_train_step(loss_fn, tx)
    state = (replicate(params), replicate(tx.init(params)))
    losses = []
    for _ in range(4):
        *state, loss = step(*state, shard_batch({"tokens": tokens}))
        losses.append(float(loss))
    assert abs(losses[0] - alone) <= 1e-5 * alone
    assert losses[-1] < losses[0] - 0.1
