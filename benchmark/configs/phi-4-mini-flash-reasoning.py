"""Phi-4-mini-flash-reasoning (SambaY), one chip's share of a pipeline stage
of eight data-parallel chips that divide the 200,064 vocabulary rows (rows
0..25,007 here), cut in depth to the published layers ``0, 1, 16, 17, 18,
19``: two Mamba-1 layers (16 hands on its memory), a windowed and the full
differential-attention layer (17 hands on K and V), a Gated Memory Unit and
a cross layer, every one with its SwiGLU MLP. The program's model, its
batches, its plain reference and its operations per token. Sizes come from
``phi-4-mini-flash-reasoning.json`` (``cfg``).

Parameters by hand (d 2560; the file's ``n_params``). Every layer: the MLP
2560 x 20,480 + 10,240 x 2560 = 78,643,200 and two LayerNorms with bias 4 x
2560 = 10,240. Mixers. Mamba-1: in 2560 x 10,240 = 26,214,400; convolution 4
x 5120 + 5120 = 25,600; x-projection 5120 x 192 = 983,040; Delta-projection
160 x 5120 + 5120 = 824,320; ``A_log`` 5120 x 16 = 81,920; ``D`` 5120; out
5120 x 2560 = 13,107,200: **41,241,600**. Differential attention: ``W_q``
and ``W_o`` 2 x 2560 x 2560 = 13,107,200; ``W_k`` and ``W_v`` 2 x 2560 x
1280 = 6,553,600; four lambda vectors of 64 and the sub-norm's 128: 384:
**19,661,184**; a cross layer has no ``W_k``, ``W_v``: **13,107,584**. Gated
Memory Unit 2 x 2560 x 5120 = **26,214,400**. A layer then: Mamba
119,895,040; windowed or full 98,314,624; GMU 104,867,840; cross
91,761,024. The six held 2 x 119,895,040 + 2 x 98,314,624 + 104,867,840 +
91,761,024 = 633,048,192; the tied embedding 25,008 x 2560 = 64,020,480;
the final LayerNorm 5,120: **697,073,792**. Published, 9 Mamba + 9
attention (8 windowed, 1 full) + 7 GMU + 7 cross layers and 200,064 rows: 9
x 119,895,040 + 9 x 98,314,624 + 7 x 104,867,840 + 7 x 91,761,024 +
512,163,840 + 5,120 = **3,852,457,984** (the card's 3.8 B).
"""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``). ``lib/cell.py`` hands a reader
# neither.
FIRST = {}
STATS = {}

# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _model(cfg):
    from byteps_tpu.models import Phi4FlashModel

    held = cfg["layer_indices"]
    if len(held) != cfg["num_hidden_layers"]:
        raise ValueError("layer_indices names num_hidden_layers layers")
    if cfg["mb_per_layer"] != 2 or not cfg["tie_word_embeddings"]:
        raise ValueError("Phi4FlashModel alternates Mamba and attention and "
                         "ties its head")
    if cfg["mlp_bias"] or cfg["lm_head_bias"] or cfg["hidden_act"] != "silu":
        raise ValueError("Phi4FlashModel's MLP is SwiGLU without bias, its "
                         "head has none")
    return Phi4FlashModel(
        vocab_size=cfg["vocab_size"], layers=tuple(held),
        num_layers=cfg["num_hidden_layers_published"],
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"], window=cfg["sliding_window"],
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        expand=cfg["mamba_expand"], loss_rows=cfg["loss_rows"],
        dtype=jnp.dtype(cfg["compute_dtype"]), eps=cfg["layer_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import phi4_flash_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return phi4_flash_loss(model.apply(params, batch["tokens"]))

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"sel_stats"}``, the model's collection as numpy,
    under one jit that returns it alone (the compiler drops what follows the
    last Mamba layer's preparation). Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["sel_stats"])[1]

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
    whatever ``compute_dtype`` says (ISSUE 71)."""
    from benchmark.lib.plain_phi4_flash import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"],
            depth=cfg["num_hidden_layers_published"],
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            window=cfg["sliding_window"], eps=cfg["layer_norm_eps"],
            dtype=jnp.float32, **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def layer_counts(cfg):
    """``{kind: layers}`` of the held layers, by the published indices (the
    rule of ``models/phi4_flash.py::layer_kind``, written out: the cell's
    readers count with it and import nothing of the program)."""
    half = cfg["num_hidden_layers_published"] // 2
    kinds = ["mamba" if i % 2 == 0 and i <= half else "gmu" if i % 2 == 0
             else "window" if i < half else "full" if i == half + 1
             else "cross" for i in cfg["layer_indices"]]
    return {kind: kinds.count(kind)
            for kind in ("mamba", "window", "full", "cross", "gmu")}


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the recurrence token by token, the band's and the causal
    triangle's pairs with each score map once, the sliced tied head at the
    rows with a target — so that a chunk's extra work, the score maps
    computed twice, blocks outside the band and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). Every layer's MLP 78,643,200. A Mamba-1 mixer's products
    26,214,400 + 983,040 + 819,200 (the Delta-projection without its bias) +
    13,107,200 = 41,123,840; its recurrence, a token, channel and state
    entry 5 (the decay's product 1, the write 2, ``C S`` 2), x 5120 x 16 x 3
    (forward, and twice that backward) = 1,228,800. Differential attention's
    projections 19,660,800 (a cross layer's 13,107,200); a (query, key) pair
    of one pair of heads costs 2 x 2 x 64 (two scores) + 2 x 2 x 128 (two
    maps over a value of 128) = 768 forward and twice that backward, 2,304:
    46,080 over the 20 pairs. The Gated Memory Unit 26,214,400. The head
    2560 x 25,008 = 64,020,480 at the s - 1 rows with a target (embedding
    look-ups are not matmuls). A row of the six layers 6 x (6 x 78,643,200 +
    2 x 41,123,840 + 2 x 19,660,800 + 13,107,200 + 26,214,400) + 2 x
    1,228,800 = 3,798,958,080; at s 16,384 the causal triangle holds
    134,225,920 pairs, twice (the full and the cross layer), and the band
    8,257,792: (2 x 134,225,920 + 8,257,792) x 46,080 = 12.751 TFLOP, and
    the head 6.293: **4,961,303,355** a token, 81.29 TFLOP a step (76.6% the
    layers' matrix products — 46.4% of the step the six MLPs — 15.2% the two
    triangles, 0.5% the band, 0.05% the recurrence, 7.7% the head), 0.413 s
    at the peak; at s 8,192 the triangles hold 33,558,528 pairs and the band
    4,063,488: 4,583,424,630 a token, 37.55 TFLOP a step. ISSUE 71 reckoned
    68.5 TFLOP of products, 12.4 of the triangles and 0.4 of the band at
    16,384: the same (its 68.5 counts the head's rows with the layers': 6
    x 16,384 x 697 M; here the layers are 62.2 and the
    head 68.5: the same)."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    head_dim = d // heads
    inner, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    rank = -(-d // 16)
    kinds = layer_counts(cfg)
    mlp = 3 * d * cfg["intermediate_size"]
    mamba = d * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * d
    recurrence = 3 * 5 * inner * n
    attention = 2 * d * (heads + kv_heads) * head_dim
    cross = 2 * d * heads * head_dim
    gmu = 2 * d * inner
    row = (6 * (sum(kinds.values()) * mlp + kinds["mamba"] * mamba
                + (kinds["window"] + kinds["full"]) * attention
                + kinds["cross"] * cross + kinds["gmu"] * gmu)
           + kinds["mamba"] * recurrence)
    pair = 3 * (heads // 2) * (2 * 2 * head_dim + 2 * 2 * 2 * head_dim)
    window = min(cfg["sliding_window"], s)
    pairs = ((kinds["full"] + kinds["cross"]) * s * (s + 1) // 2
             + kinds["window"] * (window * s - window * (window - 1) // 2))
    return (s * row + pairs * pair
            + (s - 1) * 6 * d * cfg["vocab_size"]) // s
