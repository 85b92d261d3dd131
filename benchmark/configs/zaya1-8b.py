"""ZAYA1-8B, one chip's share of a 16-chip deployment (8 pipeline stages of
5 layers x 2 chips sharing each layer's experts): experts 0..7 of 16, rows
0..32,783 of the tied vocabulary, which the 8 stages divide, layers 0..4 of
40: the program's model, its batches, its plain reference and its
operations per token. Sizes come from ``zaya1-8b.json`` (``cfg``).

Parameters by hand (D 2048, d 128; the file's ``n_params``). A CCA mixer:
``W_q`` 2048 x 1024 = 2,097,152; ``W_k`` 2048 x 256 = 524,288; ``W_v1`` and
``W_v2`` 2 x 2048 x 128 = 524,288; ``W_o`` 1024 x 2048 = 2,097,152; conv0 2
x 1280 = 2,560 and its bias 1,280; conv1 2 x 10 x 128 x 128 = 327,680 and
its bias 1,280; the temperature 2: **5,575,682**. A router: ``W_down`` 2048
x 256 = 524,288; the MLP 256 x 256 + 256, 256 x 256 + 256, 256 x 16 =
135,680; the balancing bias 16: 659,984 in layer 0 and, with the depth
averaging's 256 from layer 1 on, **660,240**. The 8 experts held: 8 x 3 x
2048 x 2048 = **100,663,296**. A layer's two norms 4,096 and its two
residual merges 2 x 4 x 2048 = 16,384. A layer 106,919,698 (layer 0 256
fewer), five 534,598,234; the tied embedding 32,784 x 2048 = 67,141,632 and
the final norm 2,048: **601,741,914**. Published, 40 layers with all 16
experts (201,326,592 a layer) and 262,272 rows: 40 x (5,575,682 + 660,240 +
201,326,592 + 20,480) - 256 + 262,272 x 2048 + 2,048 = **8,840,454,608**,
8,303,319,504 of them outside the embedding (the family's "8.3 B").
"""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``). ``lib/cell.py`` hands a reader
# neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 2 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _rope(cfg):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    if cfg["sliding_window"] is not None or set(kinds) != {"hybrid"}:
        raise ValueError("ZayaModel has hybrid layers only and no window")
    return cfg["rope_parameters"]["hybrid"]


def _model(cfg):
    from byteps_tpu.models import ZayaModel

    if (cfg["num_experts_per_tok"] != 1 or cfg["attention_bias"]
            or cfg["lm_head_bias"] or not cfg["tie_word_embeddings"]
            or cfg["hidden_act"] != "silu"):
        raise ValueError("ZayaModel routes top-1 over SwiGLU experts, has no "
                         "bias in a projection and ties its head")
    return ZayaModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(_rope(cfg)["rope_theta"]),
        rotary_factor=_rope(cfg)["partial_rotary_factor"],
        router_hidden=cfg["router_hidden_size"],
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        mlp_dim=cfg["moe_intermediate_size"], first_expert=FIRST_EXPERT,
        conv0_taps=cfg["cca_time0"], conv1_taps=cfg["cca_time1"],
        loss_rows=cfg["loss_rows"], dtype=jnp.dtype(cfg["compute_dtype"]),
        eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import zaya_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return zaya_loss(model.apply(params, batch["tokens"]))

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats"}``, the model's collection as numpy,
    under one jit that returns it alone (the compiler drops the head).
    Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["moe_stats"])[1]

        STATS.update(jax.tree_util.tree_map(np.asarray, dict(stats(
            jax.random.PRNGKey(FIRST["seed"]), FIRST["tokens"][:rows]))))
    return STATS


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences, one document each: uniform
    tokens over this chip's slice of the vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """[rows, s-1], summing to 1: the loss is the mean over rows x (s-1)
    positions, so the shards need not be told apart."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    """The plain step's loss, in float32 at the highest matmul precision
    whatever ``compute_dtype`` says (ISSUE 55)."""
    from benchmark.lib.plain_zaya import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"], head_dim=cfg["head_dim"],
            rope_theta=_rope(cfg)["rope_theta"],
            partial_rotary_factor=_rope(cfg)["partial_rotary_factor"],
            first_expert=FIRST_EXPERT, eps=cfg["rms_norm_eps"],
            dtype=jnp.float32, **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the causal triangle's pairs in the latent, the expected held
    experts, the sliced head at the rows with a target — so that blocks
    above the diagonal, rows beyond the held groups and recomputation earn
    no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). A CCA mixer's projections 2,097,152 + 524,288 + 524,288 +
    2,097,152 = 5,242,880 and its grouped convolution 327,680 (the
    depthwise one, the mean, the norms and the rotation are elementwise); a
    causal (query, key) pair of one head costs 2 x 128 (its score) + 2 x 128
    (its value) forward and twice that backward, 1,536: 12,288 over the 8
    heads, and a sequence of 16,384 has 134,225,920 pairs: 1.649 TFLOP a
    layer. The router 524,288 + 65,536 + 65,536 + 4,096 = 659,456; of a
    token's one expert the 8 / 16 expected here, 6,291,456. A row of a layer
    6 x (5,242,880 + 327,680 + 659,456 + 6,291,456) = 75,128,832, of five
    375,644,160; the head 6 x 2048 x 32,784 = 402,849,792 at the s - 1 rows
    with a target (the embedding's look-up is no matmul). Over s 16,384:
    375,644,160 + 5 x 100,669,440 (the pairs) + 402,825,204 (the head) =
    **1,281,816,564** a token, 21.00 TFLOP a step: 39% the scores and values
    in the latent, 12.3% the mixers' projections, 0.8% the grouped
    convolutions, 1.5% the routers, 14.7% the held experts, 31.4% the
    head."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    heads, kv, width = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
    hidden, experts = cfg["router_hidden_size"], cfg["num_experts"]
    mixer = (2 * d * heads * width + 2 * d * kv * width
             + cfg["cca_time1"] * (heads + kv) * width * width)
    router = d * hidden + 2 * hidden * hidden + hidden * experts
    held = (cfg["num_experts_per_tok"] * cfg["num_local_experts"]
            * 3 * d * cfg["moe_intermediate_size"] // experts)
    pairs = 6 * 2 * width * heads * s * (s + 1) // 2
    layers = cfg["num_hidden_layers"]
    return (s * layers * 6 * (mixer + router + held) + layers * pairs
            + (s - 1) * 6 * d * cfg["vocab_size"]) // s
