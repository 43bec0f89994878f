"""Work counts of the `smallthinker` family, from the configuration's shapes.

An example is ONE POSITION of a sequence (a token), so examples a second
are tokens a second. Every count is of the share of the deployment this
chip holds (`num_hidden_layers`, `moe_num_primary_experts` and `vocab_size`
of the configuration are what is held here) and is the LEAST work of the
mathematics: of the attention scores only the pairs a layer's mask lets
through, counted once (the causal half in a global layer; inside the
window, `T W - W (W - 1) / 2` pairs of the `T (T + 1) / 2`, in a window
layer), no recomputation (remat counts nothing), 2 FLOPs a multiply-add,
the backward twice the forward. The lookups count zero.

The routed experts are counted at their EXPECTED load: a token's
`moe_num_active_primary_experts` choices fall on the held experts of the
router's `router_outputs` with probability held / outputs each, 0.75 held
experts a token at 6 x 8 / 64. The measured load is the program's counter
`moe_pairs`.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# the engine's bytes a unique id do not depend on the family: one table of
# `emb_dim` columns under Adagrad, counted as the other token family counts
from benchmark.work.qwen3next import engine_bytes_per_unique  # noqa: F401


def window_layers(config: Dict) -> Tuple[int, int]:
    """(window layers, global layers) held here."""
    first = config["deployment"].get("first_layer_held", 0)
    held = config["sliding_window_layout"][
        first:first + config["num_hidden_layers"]]
    return sum(map(bool, held)), len(held) - sum(map(bool, held))


def attn_products(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of a layer's mixer products, a position."""
    d, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return [(d, H * D), (d, Hkv * D), (d, Hkv * D), (H * D, d)]


def expert_macs(config: Dict) -> int:
    """Multiply-adds of ONE routed expert on one token."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def held_experts_per_token(config: Dict) -> float:
    return (config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts"]
            / config["deployment"]["router_outputs"])


def score_pairs(mix: Dict, window) -> int:
    """(query, key) pairs a sequence's mask lets through: causal, and inside
    `window` keys where one is given."""
    T = mix["seq_len"]
    if window is None or window >= T:
        return T * (T + 1) // 2
    return T * window - window * (window - 1) // 2


def attention_macs_per_token(config: Dict, mix: Dict, window) -> float:
    """Q K^T and P V over the pairs the mask lets through, every query
    head, a position on average."""
    return (2.0 * config["num_attention_heads"] * config["head_dim"]
            * score_pairs(mix, window) / mix["seq_len"])


def forward_macs_per_token(config: Dict, mix: Dict) -> Dict[str, float]:
    """Forward multiply-adds a position, by part."""
    win, glob = window_layers(config)
    L = win + glob
    d = config["hidden_size"]
    return {
        "attn_projections": L * sum(i * o for i, o in attn_products(config)),
        "attn_scores_global": glob * attention_macs_per_token(
            config, mix, None),
        "attn_scores_window": win * attention_macs_per_token(
            config, mix, config["sliding_window_size"]),
        "router": L * d * config["deployment"]["router_outputs"],
        "experts": L * held_experts_per_token(config) * expert_macs(config),
        "head": d * config["vocab_size"],
    }


def flops_per_example(config: Dict, mix: Dict) -> float:
    """Forward and backward FLOPs of the whole model a POSITION (an example
    is one position): the held share, the scores inside each layer's mask
    counted once, remat not counted, lookups zero, 0.75 expected held
    experts a token."""
    return 6.0 * sum(forward_macs_per_token(config, mix).values())


def dense_params(config: Dict) -> int:
    L, d = config["num_hidden_layers"], config["hidden_size"]
    layer = (sum(i * o for i, o in attn_products(config)) + 2 * d
             + d * config["deployment"]["router_outputs"]
             + config["moe_num_primary_experts"] * expert_macs(config))
    return L * layer + d + d * config["vocab_size"]


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32,
    as the benchmark's other families count it: each weight read in the
    forward, read in the backward and its gradient written (12 B); each
    product's input read in the forward and in the backward and its
    gradient written, its output written and the output's gradient read
    (4 B x (3 in + 2 out) a position; a routed expert's at its expected
    load)."""
    L = config["num_hidden_layers"]
    positions = mix["batch"] * mix["seq_len"]
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    per_pos = lambda ps: sum(3 * i + 2 * o for i, o in ps)  # noqa: E731
    expert = held_experts_per_token(config) * per_pos(
        [(d, f), (d, f), (f, d)])
    router = per_pos([(d, config["deployment"]["router_outputs"])])
    acts = (L * (per_pos(attn_products(config)) + router + expert)
            + per_pos([(d, config["vocab_size"])]))
    return 12.0 * dense_params(config) + 4.0 * positions * acts


# ------------------------------------------- the parts a roofline is read for


def _attn_work(config: Dict, mix: Dict, layers_and_windows):
    """(FLOPs, least bytes) of attention, forward and backward, a step: two
    products forward and four backward (dV, dP, dQ, dK; the scores'
    recomputation counts nothing) over the pairs INSIDE each layer's mask.
    Bytes: q, k, v, o and their gradients once each in bf16, a layer."""
    positions = mix["batch"] * mix["seq_len"]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    per_pos = 2 * 2 * (2 * H * D + 2 * Hkv * D)
    macs = sum(n * attention_macs_per_token(config, mix, w)
               for n, w in layers_and_windows)
    layers = sum(n for n, _ in layers_and_windows)
    return 6.0 * positions * macs, float(layers * positions * per_pos)


def flash_attn_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """Every attention layer held here, window and global: what the three
    flash kernels do between them."""
    win, glob = window_layers(config)
    return _attn_work(config, mix, [(glob, None),
                                    (win, config["sliding_window_size"])])


def window_attn_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """The window layers alone: what runs under the scope `attn_window`."""
    win, _ = window_layers(config)
    return _attn_work(config, mix, [(win, config["sliding_window_size"])])


def experts_work_per_step(config: Dict, mix: Dict,
                          pairs_per_step: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' grouped products, forward
    and backward, a step, for the MEASURED (token, expert) pairs a step
    (all layers). Bytes: every held expert's weights read in the forward
    and in the backward and their gradients written (12 B a weight), and a
    pair's rows: input, the two hidden rows and the output, each with its
    gradient, in bf16. At 1,536 tokens an expert the FLOPs bind, not the
    weights' bytes."""
    layers = config["num_hidden_layers"]
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    weights = 12.0 * layers * config["moe_num_primary_experts"] \
        * expert_macs(config)
    rows = 2 * 2 * (2 * d + 3 * f)
    return (6.0 * pairs_per_step * expert_macs(config),
            weights + pairs_per_step * rows)
