"""The yardstick's own arithmetic: operations per token, the plain
reference against the program's model, micro-batching, the loop, the
comparison that decides ``correct``."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_tiny import REPO  # noqa: E402

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib import loop, reference  # noqa: E402

# The generic cases run over every configuration of the manifest: a new
# one gets them without writing any.
CONFIGS = [c["name"] for c in cell_lib.load_json(
    os.path.join(REPO, "BENCHMARK.json"))["configs"]]


def _config(name, tiny=False, **over):
    """The configuration's sizes and module; ``tiny``: at the rehearsal
    size its own file keeps."""
    path = os.path.join(REPO, "benchmark", "configs", name)
    cfg = cell_lib.load_json(path + ".json")
    return ({**cfg, **(cfg["rehearsal_sizing"] if tiny else {}), **over},
            cell_lib.load_module(path + ".py", "cfg_" + name[:4]))


def test_gpt2_flops_per_token_by_hand():
    cfg, module = _config("gpt2-124m")
    per_layer = 4 * 768 * 768 + 2 * 768 * 3072          # qkv, out, mlp
    matmul = 12 * per_layer + 768 * 50257               # + tied head
    assert matmul == 123_532_032
    attention = 12 * 12 * 1024 * 768 // 2               # causal half
    assert module.flops_per_token(cfg) == 6 * matmul + attention \
        == 797_815_296                                  # ~0.80 GFLOP/token
    assert cfg["n_params"] == 124_439_808


def test_bert_large_flops_per_token_by_hand():
    cfg, module = _config("bert-large")
    per_layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    matmul = 24 * per_layer + 1024 * 1024 + 1024 * 30522  # + MLM head
    assert matmul == 334_292_992
    attention = 12 * 24 * 128 * 1024
    assert module.flops_per_token(cfg) == 6 * matmul + attention \
        == 2_043_506_688                                # ~2.04 GFLOP/token
    assert cfg["n_params"] == 366_426_938


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_is_the_published_model_s(name):
    """At the file's own depth and width: its n_params is what the model
    the file describes really has (shapes only, nothing is allocated). The
    published counts themselves are in the by-hand cases above."""
    cfg, module = _config(name)
    init, _ = module.build(cfg)
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    n = sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["n_params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_batches_come_from_the_seed_and_cover_the_vocabulary(name):
    cfg, module = _config(name)
    a = module.make_batch(cfg, np.random.default_rng(7), 16)
    b = module.make_batch(cfg, np.random.default_rng(7), 16)
    c = module.make_batch(cfg, np.random.default_rng(8), 16)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].shape == (16, cfg["seq_len"])
    assert a["tokens"].max() > 0.9 * cfg["vocab_size"]
    if "mask" in a:   # the file's rate of every row (BERT: 15% of 128 =
        #               19 positions), inputs masked there
        masked = round(cfg["mask_rate"] * cfg["seq_len"])
        assert masked > 0 and (a["mask"].sum(axis=1) == masked).all()
        assert (a["tokens"][a["mask"] == 1] == cfg["mask_token_id"]).all()
        assert (a["tokens"] == a["labels"])[a["mask"] == 0].all()


@pytest.mark.parametrize("shards", (1, 4))     # the chips a cell may have
@pytest.mark.parametrize("name", CONFIGS)
def test_plain_reference_is_the_program_s_loss(name, shards):
    """In float32 the plain jax.numpy forward pass and the program's flax
    model agree to rounding; over ``shards`` the reference's weights give
    the mean over shards of each shard's own loss — also where the shards'
    mask counts differ."""
    cfg, module = _config(name, tiny=True, compute_dtype="float32")
    init, loss_fn = module.build(cfg)
    params = init(jax.random.PRNGKey(0))
    batch = module.make_batch(cfg, np.random.default_rng(0), 4 * shards)
    if "mask" in batch:
        batch["mask"][0, :] = 1          # uneven counts across shards
    want = np.mean([
        loss_fn(params, jax.tree_util.tree_map(
            lambda x: x[i * 4:(i + 1) * 4], batch)) for i in range(shards)])
    weighted = {**batch,
                "weight": module.reference_weights(cfg, batch, shards)}
    got = module.reference_loss(cfg)(params, weighted)
    assert abs(float(got) - float(want)) < 2e-5
    assert abs(float(weighted["weight"].sum()) - 1.0) < 1e-5


def test_reference_step_is_exact_under_micro_batching():
    cfg, module = _config("bert-large", tiny=True, compute_dtype="float32")
    init, _ = module.build(cfg)
    tx = optax.adamw(1e-4)
    batch = module.make_batch(cfg, np.random.default_rng(0), 8)
    batch["weight"] = module.reference_weights(cfg, batch, 2)
    losses = []
    for micro in (1, 4):
        params = init(jax.random.PRNGKey(0))
        step = reference.make_reference_step(
            module.reference_loss(cfg), tx, micro)
        losses.append(reference.reference_losses(
            step, params, tx.init(params), [batch, batch]))
    assert np.allclose(losses[0], losses[1], atol=2e-6), losses
    assert losses[0][1] < losses[0][0]


def test_compare_losses_decides_correct():
    ok = reference.compare_losses([10.5, 10.4, 10.3, 9.0],
                                  [10.5004, 10.3995, 10.3001])
    assert ok["ok"] and ok["max_loss_diff"] < 1e-3
    assert not reference.compare_losses([10.5, 10.4, 10.3],
                                        [10.5, 10.4, 10.31])["ok"]
    assert not reference.compare_losses([10.5, 10.4],
                                        [10.5, 10.4, 10.3])["ok"]
    assert not reference.compare_losses([10.5, float("nan"), 10.3],
                                        [10.5, 10.4, 10.3])["ok"]


def _fake_step(fail_at=None, nan_at=None):
    calls = {"n": 0}

    def step(params, opt_state, batch):
        n = calls["n"]
        calls["n"] += 1
        if n == fail_at:
            raise RuntimeError("boom")
        loss = jnp.float32(math.nan if n == nan_at else 1.0 / (n + 1))
        return params + 1, opt_state, loss

    return step


def test_loop_counts_steps_intervals_and_finishes_what_is_in_flight():
    state, win = loop.measure(_fake_step(), (jnp.zeros(()), None), [0, 1, 2],
                              lambda b: b, log_every=4, max_steps=10)
    assert (win.attempted, win.completed, win.failed) == (10, 10, 0)
    assert len(win.step_s) == 3 and len(win.losses) == 10   # 4 + 4 + 2
    assert float(state[0]) == 10 and win.wall_s > 0 and not win.error
    assert loop.step_ms_p50(win) > 0


def test_loop_stops_at_the_deadline():
    _, win = loop.measure(_fake_step(), (jnp.zeros(()), None), [0],
                          lambda b: b, log_every=1, seconds=0.2)
    assert win.attempted == win.completed > 0
    assert 0.2 <= win.wall_s < 1.0


def test_loop_counts_a_non_finite_loss_and_a_raising_step_as_failed():
    _, win = loop.measure(_fake_step(nan_at=2), (jnp.zeros(()), None), [0],
                          lambda b: b, log_every=2, max_steps=4)
    assert (win.attempted, win.completed, win.failed) == (4, 4, 1)
    _, win = loop.measure(_fake_step(fail_at=3), (jnp.zeros(()), None), [0],
                          lambda b: b, log_every=2, max_steps=6)
    assert win.attempted == 4 and win.completed == 2 and win.failed == 2
    assert "boom" in win.error


def test_kwargs_can_name_objects_of_the_program():
    from byteps_tpu.jax.compression import Compression

    got = cell_lib.resolve_kwargs(
        {"compression": "@byteps_tpu.jax.compression:Compression.bf16",
         "donate": True})
    assert got == {"compression": Compression.bf16, "donate": True}
    assert cell_lib.count_all_reduce(
        "%ar = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}\n"
        "%s = f32[8]{0} all-reduce-start(f32[8]{0} %p)\n"
        "%d = f32[8]{0} all-reduce-done(f32[8]{0} %s)\n") == 2


def test_malloc_thresholds_are_fixed_here_and_for_the_children():
    from benchmark.lib import fleet

    env = {}
    fleet.steady_malloc({"mmap_threshold": 1 << 25,
                         "trim_threshold": (1 << 31) - 1}, env)
    assert env == {"MALLOC_MMAP_THRESHOLD_": "33554432",
                   "MALLOC_TRIM_THRESHOLD_": "2147483647"}
