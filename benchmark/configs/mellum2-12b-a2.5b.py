"""Mellum2-12B-A2.5B, one chip's share of a four-chip deployment (experts
0..15 of 64; rows 0..24,575 of the vocabulary, which the four chips divide),
cut in depth to published layers 0-3 — three windowed layers and a global
one, every one with an expert layer: the program's model, its batches, its
plain reference and its operations per token. Sizes come from
``mellum2-12b-a2.5b.json`` (``cfg``).

Parameters by hand (d 2304, 32 query heads over 4 key heads of 128; the
file's ``n_params``). Attention, the same in both kinds of layer: q and o 2
x 2304 x 4096 = 18,874,368; k and v 2 x 2304 x 512 = 2,359,296:
**21,233,664**. One expert 3 x 2304 x 896 = 6,193,152; the router 2304 x 64
= 147,456; a layer's two norms 4,608. A layer held here 21,233,664 + 4,608
+ 147,456 + 16 x 6,193,152 = **120,476,160**, four 481,904,640; embedding +
head + final norm 2 x 24,576 x 2304 + 2304 = 113,248,512:
**595,153,152**. Published, 28 layers with all 64 experts (417,747,456 a
layer) and 98,304 rows: 28 x 417,747,456 + 2 x 98,304 x 2304 + 2304 =
**12,149,915,904** (the card's 12B); active a token, 8 of 64 experts:
28 x (21,233,664 + 4,608 + 147,456 + 8 x 6,193,152) + 452,987,136 =
**2,439,053,568** (A2.5B; ISSUE 58's 2,438,922,240 leaves the 131,328 norm
scales out). ``intermediate_size`` 7168 (= 8 x 896) sizes no layer: every
entry of ``mlp_layer_types`` is ``sparse``.
"""

import jax.numpy as jnp
import numpy as np

# pairs of one head over one sequence: the causal triangle s (s + 1) / 2, or
# under a window sum_q min(q + 1, window) = 1024 s - 523,776; the roofline's
# reader keeps the function
from benchmark.layers.swa import needed_pairs

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``). ``lib/cell.py`` hands a reader
# neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 4 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _kinds(cfg):
    """The kinds of the first ``num_hidden_layers`` layers."""
    n = cfg["num_hidden_layers"]
    if set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("MellumModel has an expert layer in every block")
    return tuple(cfg["layer_types"][:n])


def _rotary(group):
    from byteps_tpu.models.laguna import Rotary

    yarn = None
    if group["rope_type"] == "yarn":
        yarn = (float(group["factor"]),
                group["original_max_position_embeddings"],
                float(group["beta_fast"]), float(group["beta_slow"]),
                group["attention_factor"])
    return Rotary(float(group["rope_theta"]), 1.0, yarn)


def _model(cfg):
    from byteps_tpu.models import MellumModel

    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or not cfg["norm_topk_prob"] or cfg["hidden_act"] != "silu"):
        raise ValueError("MellumModel has no bias, an untied head, SwiGLU "
                         "experts and renormalised top-k probabilities")
    rope = cfg["rope_parameters"]
    return MellumModel(
        vocab_size=cfg["vocab_size"], layer_kinds=_kinds(cfg),
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"],
        full_rotary=_rotary(rope["full_attention"]),
        window_rotary=_rotary(rope["sliding_attention"]),
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"], first_expert=FIRST_EXPERT,
        loss_rows=cfg["loss_rows"], dtype=jnp.dtype(cfg["compute_dtype"]),
        eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import mellum_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return mellum_loss(model.apply(params, batch["tokens"]))

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
    positions and nothing else, so the shards need not be told apart."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    """The plain step's loss, in float32 at the highest matmul precision
    whatever ``compute_dtype`` says (ISSUE 58)."""
    from benchmark.lib.plain_mellum import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"], layer_types=_kinds(cfg),
            head_dim=cfg["head_dim"], window=cfg["sliding_window"],
            rope_parameters=cfg["rope_parameters"],
            top_k=cfg["num_experts_per_tok"],
            share=(FIRST_EXPERT, cfg["num_local_experts"]),
            eps=cfg["rms_norm_eps"], dtype=jnp.float32,
            **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the pairs of the band and of the causal triangle and no
    others, the expected held experts, the sliced head at the rows with a
    target — so that the blocks a kernel walks beyond the band, rows beyond
    the held groups and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). A layer's projections are the 21,233,664 above. A (query,
    key) pair of one head costs 2 x 128 (its score) + 2 x 128 (its value)
    forward and twice that backward, 1,536: 49,152 a pair over the 32
    heads. A sequence of 8,192 has 33,558,528 causal pairs and 1024 x 8,192
    - 523,776 = 7,864,832 in the band: the global layer 1.649 TFLOP a
    sequence, three windowed ones 1.160. An expert layer: the router
    147,456 and, of a token's 8 experts, the 8 x 16 / 64 = 2 expected
    here, 12,386,304. The head 2304 x 24,576 = 56,623,104 at the s - 1 rows
    with a target (embedding look-ups are not matmuls). A row of the stack
    6 x 4 x (21,233,664 + 147,456 + 12,386,304) = 810,418,176; over s
    8,192: 810,418,176 + 342,918,144 (the pairs: 57,153,024 x 49,152 /
    8,192) + 339,697,152 (the head) = **1,493,033,472** a token (34% the
    projections, 23% the scores and values, 20% the held experts, 23% the
    head), 24.46 TFLOP a step of 2 x 8,192 tokens. ISSUE 58 counts 1.49
    GFLOP a token: the same to its three digits."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    heads, kv, width = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
    layer = (2 * d * heads * width + 2 * d * kv * width
             + d * cfg["num_experts"]
             + cfg["num_experts_per_tok"] * cfg["num_local_experts"]
             * 3 * d * cfg["moe_intermediate_size"] // cfg["num_experts"])
    kinds = _kinds(cfg)
    pairs = sum(6 * 2 * width * heads * needed_pairs(
        s, cfg["sliding_window"] if kind == "sliding_attention" else None)
        for kind in kinds)
    return (s * 6 * len(kinds) * layer + pairs
            + (s - 1) * 6 * d * cfg["vocab_size"]) // s
