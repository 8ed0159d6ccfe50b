"""Model operations of the hybrid Mamba-2/attention LM's cell-rounds, from
the configuration's keys alone (never from the compiled program)."""
from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "hq": heads, "hkv": cfg["num_key_value_heads"],
            "dh": d // heads, "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "d_in": cfg["mamba_expand"] * d,
            "n": cfg["mamba_d_state"], "mh": cfg["mamba_n_heads"],
            "mp": cfg["mamba_d_head"], "q": cfg["mamba_chunk_size"],
            "k": cfg["mamba_d_conv"]}


def layer_params(cfg: dict, kind: str) -> tuple:
    """(parameters, of which in matrices) of one layer: its mixer with
    its norm, then the SwiGLU MLP with its norm."""
    g = _dims(cfg)
    d = g["d"]
    if kind == "attention":
        mats = d * g["hq"] * g["dh"] * 2 + d * g["hkv"] * g["dh"] * 2
        other = d
    else:
        conv = g["d_in"] + 2 * g["n"]
        mats = d * (2 * g["d_in"] + 2 * g["n"] + g["mh"]) + g["d_in"] * d
        other = d + 3 * g["mh"] + conv * (g["k"] + 1) + g["d_in"]
    return mats + other + 3 * d * g["ff"] + d, mats + 3 * d * g["ff"]


def param_count(cfg: dict) -> int:
    """Every parameter: the tied embedding, the layers, the final norm."""
    g = _dims(cfg)
    return g["v"] * g["d"] + g["d"] + sum(
        layer_params(cfg, k)[0] for k in cfg["layer_types"])


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Forward FLOPs of one sequence: 2 per matrix parameter per token
    (the tied head counted as a matrix, the embedding gather not), the
    SSD's chunked products per Mamba-2 layer (C B^T within each chunk,
    the within-chunk output, each chunk's state and the inter-chunk
    output: 2 S (Q N + H P (Q + 2 N))), and per attention layer the
    scores and their product with V over the full (S, S) square the
    dense path computes (4 S^2 H_q D)."""
    g = _dims(cfg)
    f = 2.0 * seq * g["v"] * g["d"]
    for kind in cfg["layer_types"]:
        f += 2.0 * seq * layer_params(cfg, kind)[1]
        if kind == "attention":
            f += 4.0 * seq * seq * g["hq"] * g["dh"]
        else:
            f += 2.0 * seq * (g["q"] * g["n"]
                              + g["mh"] * g["mp"] * (g["q"] + 2 * g["n"]))
    return f


def model_flops_per_cell_round(cfg: dict, traffic: dict,
                               n_val: int) -> float:
    """Model FLOPs of one steady-state cell-round: local training (forward
    and backward, 3x the forward, of m x E x B sequences) and one eval
    forward over the held-out documents on the sweep's share of eval
    rounds.  The backward's recompute of each layer is not counted."""
    seq = cfg["image_shape"][0] - 1
    f = forward_flops_per_sequence(cfg, seq)
    rounds = cfg["rounds"]
    evals = len({t for t in range(rounds)
                 if t % cfg["eval_every"] == 0 or t == rounds - 1})
    total = cfg["m"] * cfg["local_steps"] * cfg["batch_size"] * 3 * f
    return total + evals / rounds * n_val * f
