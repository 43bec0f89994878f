"""Work counts of the `nexttok` family: a causal mean over the sequence,
then two matrix products a position. It has no engine count of its own."""
from __future__ import annotations

from typing import Dict


def flops_per_example(config: Dict, mix: Dict) -> float:
    """An example is one position. Forward and backward of the two matrix
    products, 2 FLOPs a multiply-add, the backward twice the forward; the
    causal mean (one add and one multiply an element) and the lookup count
    zero."""
    return 6.0 * (config["emb_dim"] * config["hidden"]
                  + config["hidden"] * mix["vocab"])


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """As the benchmark's other family counts them: 12 B a weight, 4 B x
    (3 in + 2 out) a layer a position."""
    layers = [(config["emb_dim"], config["hidden"]),
              (config["hidden"], mix["vocab"])]
    positions = mix["batch"] * mix["seq_len"]
    return (12.0 * sum(i * o for i, o in layers)
            + 4.0 * positions * sum(3 * i + 2 * o for i, o in layers))
