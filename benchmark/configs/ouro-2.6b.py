"""Ouro-2.6B cut in depth: the program's looped model, its batches, its
plain reference and its operations per token. Sizes come from
``ouro-2.6b.json`` (``cfg``)."""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them: the ``loop``
# reader's probe (``layers/loop.py::setup``) sends that batch through the
# weights of that seed. ``lib/cell.py`` hands a reader neither.
FIRST = {}


def _model(cfg):
    from byteps_tpu.models import OuroModel

    return OuroModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], num_passes=cfg["total_ut_steps"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attn_impl=cfg["attn_impl"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import ouro_loss

    model = _model(cfg)
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    def init(key):
        return model.init(key, example)

    def loss_fn(params, batch):
        return ouro_loss(model.apply(params, batch["tokens"]),
                         beta=cfg["exit_entropy_weight"])

    return init, loss_fn


def loop_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: the model's ``"loop_stats"`` collection as numpy,
    under one jit that returns it alone (the compiler drops the heads)."""
    import jax

    model = _model(cfg)
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    @jax.jit
    def stats(key, tokens):
        return model.apply(model.init(key, example), tokens,
                           mutable=["loop_stats"])[1]["loop_stats"]

    return jax.tree_util.tree_map(np.asarray, dict(stats(
        jax.random.PRNGKey(FIRST["seed"]), FIRST["tokens"][:rows])))


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences: uniform tokens over the whole
    vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """The objective is the mean over rows x (s-1) positions of a
    per-position quantity; every shard has as many, so the mean over shards
    of shard means is the global mean."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_ouro import looped_lm_loss_per_position

    def weighted_loss(params, batch):
        per_position = looped_lm_loss_per_position(
            params, batch["tokens"], num_layers=cfg["num_hidden_layers"],
            num_passes=cfg["total_ut_steps"],
            num_heads=cfg["num_attention_heads"], eps=cfg["rms_norm_eps"],
            rope_theta=float(cfg["rope_theta"]),
            beta=cfg["exit_entropy_weight"],
            dtype=jnp.dtype(cfg["compute_dtype"]), checkpoint=True)
        return (per_position * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the forward and backward passes need per trained token,
    once: 6 x the matmul parameters a token meets + attention, of every one
    of the R x L block applications and the R exits. Recomputation (every
    block application and every exit runs forward twice) and the optimizer
    do not count, so ``mfu_pct`` is honest about the price of ``remat``.

    A block application: Q, K, V, O = 4 x 2048^2 = 16,777,216 and gate, up,
    down = 3 x 2048 x 5632 = 34,603,008, together 51,380,224 matmul
    parameters (the four norms' 8,192 scales are not matmuls); attention
    12 s d, halved because a causal model needs only the lower triangle:
    6 x 4096 x 2048 = 50,331,648. 6 x 51,380,224 + 50,331,648 =
    358,612,992. An exit: the untied head 6 x 2048 x 49,152 = 603,979,776
    and the gate 6 x 2048 = 12,288: 603,992,064 (embedding look-ups are not
    matmuls). At R = 4 passes over L = 5 layers: 20 x 358,612,992 + 4 x
    603,992,064 = 9,588,228,096, ~9.59 GFLOP a token, of which the twenty
    block applications are 74.8% (attention itself 10.5%) and the four
    exits 25.2%; at L = 4: 8,153,776,128. The published 48 layers: 192 x
    358,612,992 + 4 x 603,992,064 = 71,269,662,720."""
    d, m, s = cfg["hidden_size"], cfg["intermediate_size"], cfg["seq_len"]
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    application = 6 * (4 * d * d + 3 * d * m) + 12 * s * d // 2
    leave = 6 * d * cfg["vocab_size"] + 6 * d
    return passes * (layers * application + leave)
