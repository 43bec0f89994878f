"""Work counts of the `lfm2` family, from the configuration's shapes.

An example is ONE POSITION of a sequence (a token), so examples a second
are tokens a second. Every count is of the share of the deployment this
chip holds (`num_hidden_layers`, `num_dense_layers`, `layer_types`,
`num_experts` and `vocab_size` of the configuration are what is held here)
and is the LEAST work of the mathematics: of the attention scores only the
causal half, `T (T + 1) / 2` pairs a head, no recomputation (remat counts
nothing), 2 FLOPs a multiply-add, the backward twice the forward. The
lookups count zero; so do the norms, the rotary turn and, in the whole
model's count, the short convolution's gates and taps (elementwise: their
bytes are counted in `shortconv_gate_work_per_step`).

The routed experts are counted at their EXPECTED load: a token's
`num_experts_per_tok` choices fall on the held experts of the router's
`router_outputs` with probability held / outputs each, 1 held expert a
token at 4 x 8 / 32. The measured load is the program's counter
`moe_pairs`. The mixers and a leading layer's dense feed-forward see every
token.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

META_COLS = 3  # freq, version, dirty: int32 each


def layer_counts(config: Dict) -> Tuple[int, int, int, int]:
    """(conv mixers, attention mixers, dense feed-forwards, expert
    layers) held here."""
    kinds = config["layer_types"]
    L, dense = config["num_hidden_layers"], config["num_dense_layers"]
    if len(kinds) != L:
        raise ValueError(f"{len(kinds)} layer types for {L} layers")
    return kinds.count("conv"), kinds.count("full_attention"), dense, \
        L - dense


def head_dim(config: Dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def conv_products(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of a short convolution's two products a position: the
    in-projection to B, C and x, and the out-projection."""
    d = config["hidden_size"]
    return [(d, 3 * d), (d, d)]


def attn_products(config: Dict) -> List[Tuple[int, int]]:
    d, H, Hkv, D = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], head_dim(config))
    return [(d, H * D), (d, Hkv * D), (d, Hkv * D), (H * D, d)]


def swiglu_macs(config: Dict, width: int) -> int:
    return 3 * config["hidden_size"] * width


def expert_macs(config: Dict) -> int:
    """Multiply-adds of ONE routed expert on one token."""
    return swiglu_macs(config, config["moe_intermediate_size"])


def mlp_macs(config: Dict) -> int:
    """Multiply-adds of a dense layer's feed-forward on one token."""
    return swiglu_macs(config, config["intermediate_size"])


def held_experts_per_token(config: Dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["router_outputs"])


def score_pairs(mix: Dict) -> int:
    """(query, key) pairs of a sequence under the causal mask."""
    T = mix["seq_len"]
    return T * (T + 1) // 2


def attention_macs_per_token(config: Dict, mix: Dict) -> float:
    """Q K^T and P V over the causal pairs, every query head, a position
    on average."""
    return (config["num_attention_heads"] * 2 * head_dim(config)
            * score_pairs(mix) / mix["seq_len"])


def forward_macs_per_token(config: Dict, mix: Dict) -> Dict[str, float]:
    """Forward multiply-adds a position, by part."""
    C, A, dense, E = layer_counts(config)
    mm = lambda ps: sum(i * o for i, o in ps)  # noqa: E731
    d = config["hidden_size"]
    return {
        "conv_projections": C * mm(conv_products(config)),
        "attn_projections": A * mm(attn_products(config)),
        "attn_scores": A * attention_macs_per_token(config, mix),
        "mlp": dense * mlp_macs(config),
        "router": E * d * config["deployment"]["router_outputs"],
        "experts": E * held_experts_per_token(config) * expert_macs(config),
        "head": d * config["vocab_size"],
    }


def flops_per_example(config: Dict, mix: Dict) -> float:
    """Forward and backward FLOPs of the whole model a POSITION (an example
    is one position): the held share, the causal half of the scores, remat
    not counted, lookups, gates and taps zero, 1 expected held expert a
    token."""
    return 6.0 * sum(forward_macs_per_token(config, mix).values())


def selection_bias_entries(config: Dict) -> int:
    """The routers' selection biases: leaves of the dense tree (under
    Adam's moments like any leaf) that a rule moves and no gradient."""
    return layer_counts(config)[3] * config["deployment"]["router_outputs"]


def dense_params(config: Dict) -> int:
    """The dense parameters a gradient trains; the dense tree holds
    `selection_bias_entries` more."""
    C, A, dense, E = layer_counts(config)
    d, K = config["hidden_size"], config["conv_L_cache"]
    mm = lambda ps: sum(i * o for i, o in ps)  # noqa: E731
    conv = mm(conv_products(config)) + K * d               # the taps
    attn = mm(attn_products(config)) + 2 * head_dim(config)  # q, k norms
    expert_layer = (d * config["deployment"]["router_outputs"]
                    + config["num_experts"] * expert_macs(config))
    return (C * conv + A * attn + dense * mlp_macs(config) + E * expert_layer
            + (C + A) * 2 * d + d + d * config["vocab_size"])


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32,
    as the benchmark's other families count it: each weight read in the
    forward, read in the backward and its gradient written (12 B); each
    product's input read in the forward and in the backward and its
    gradient written, its output written and the output's gradient read
    (4 B x (3 in + 2 out) a position; a routed expert's at its expected
    load)."""
    C, A, dense, E = layer_counts(config)
    positions = mix["batch"] * mix["seq_len"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    fd = config["intermediate_size"]
    per_pos = lambda ps: sum(3 * i + 2 * o for i, o in ps)  # noqa: E731
    ffn = lambda w: per_pos([(d, w), (d, w), (w, d)])  # noqa: E731
    expert_layer = (per_pos([(d, config["deployment"]["router_outputs"])])
                    + held_experts_per_token(config) * ffn(f))
    acts = (C * per_pos(conv_products(config))
            + A * per_pos(attn_products(config)) + dense * ffn(fd)
            + E * expert_layer + per_pos([(d, config["vocab_size"])]))
    return 12.0 * (dense_params(config) + selection_bias_entries(config)) \
        + 4.0 * positions * acts


def engine_bytes_per_unique(config: Dict) -> float:
    """Algorithmic HBM bytes of the embedding engine per unique id per
    step, as the benchmark's other families count them: key gather and
    claim scatter, the row gathered once and scattered once, Adagrad's
    accumulator gathered and scattered, the fused metadata gathered and
    scattered."""
    D = config["emb_dim"]
    value_b = {"float32": 4, "bfloat16": 2}[config["table_dtype"]]
    slot_b = {"adagrad": 4 * D}[config["sparse_optimizer"]["name"]]
    return float(2 * 4 + 2 * D * value_b + 2 * slot_b + 2 * META_COLS * 4)


def router_even_load_per_step(config: Dict, mix: Dict) -> float:
    """What an even router gives EVERY one of its outputs, summed over the
    expert layers held here, a step: positions x experts a token / outputs
    a layer (2,048 x 4 at the cell's sizes)."""
    E = layer_counts(config)[3]
    return (E * mix["batch"] * mix["seq_len"]
            * config["num_experts_per_tok"]
            / config["deployment"]["router_outputs"])


# ------------------------------------------- the parts a roofline is read for


def shortconv_gate_work_per_step(config: Dict,
                                 mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the short convolutions' gates and taps
    (`u = B x`, `v = sum_j w_j u_{t-j}`, `y = C v`), forward and backward,
    every conv mixer, a step. FLOPs a channel a position: the two gates
    and the L taps' multiply-adds, 2 + 2L forward and twice that backward.
    Bytes by the algorithm's minimum, in the dtypes the program stores: the
    forward reads B, C and x once (the in-projection's float32 output) and
    writes the gated output once (bf16, the out-projection's operand); the
    backward reads the output's gradient (bf16, the out-projection's
    operand gradient) and B, C and x once, and writes the gradients of B,
    C and x once (float32, the in-projection's output gradient). The
    taps' weights and their gradient are L x d elements and count
    nothing."""
    C = layer_counts(config)[0]
    positions = mix["batch"] * mix["seq_len"]
    d, L = config["hidden_size"], config["conv_L_cache"]
    forward = 3 * 4 + 2
    backward = 2 + 3 * 4 + 3 * 4
    return (3.0 * C * positions * d * (2 + 2 * L),
            float(C * positions * d * (forward + backward)))


def flash_attn_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of causal attention, forward and backward,
    every attention layer, a step: two products forward and four backward
    (dV, dP, dQ, dK; the scores' recomputation counts nothing). Bytes: q,
    k, v, o and their gradients once each in bf16."""
    A = layer_counts(config)[1]
    positions = mix["batch"] * mix["seq_len"]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], head_dim(config))
    per_pos = 2 * 2 * (2 * H * D + 2 * Hkv * D)
    return (6.0 * A * positions * attention_macs_per_token(config, mix),
            float(A * positions * per_pos))


def experts_work_per_step(config: Dict, mix: Dict,
                          pairs_per_step: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' grouped products, forward
    and backward, a step, for the MEASURED (token, expert) pairs a step
    (all expert layers). Bytes: every held expert's weights read in the
    forward and in the backward and their gradients written (12 B a
    weight), and a pair's rows: input, the two hidden rows and the output,
    each with its gradient, in bf16."""
    E = layer_counts(config)[3]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    weights = 12.0 * E * config["num_experts"] * expert_macs(config)
    rows = 2 * 2 * (2 * d + 3 * f)
    return (6.0 * pairs_per_step * expert_macs(config),
            weights + pairs_per_step * rows)
