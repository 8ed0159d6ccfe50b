"""Operations the algorithm needs, from the configuration's shapes alone
(never from the compiled program)."""
from __future__ import annotations


def _layers(cfg: dict) -> list:
    """(multiply-adds per sample, parameters) of each layer of the
    configuration's CNN: convolutions ([kernel, channels], each followed by
    a 2x2 max pool), dense layers, the logits."""
    h, w, c = cfg["image_shape"]
    out = []
    for k, ch in cfg["conv"]:
        if cfg["conv_padding"] == "VALID":
            h, w = h - k + 1, w - k + 1
        out.append((h * w * k * k * c * ch, k * k * c * ch + ch))
        c = ch
        h, w = h // 2, w // 2
    fan = h * w * c
    for width in list(cfg["dense"]) + [cfg["classes"]]:
        out.append((fan * width, fan * width + width))
        fan = width
    return out


def cnn_macs_per_sample(cfg: dict) -> int:
    """Forward multiply-adds of one sample (pools, ReLUs and biases are
    not counted)."""
    return sum(macs for macs, _ in _layers(cfg))


def param_count(cfg: dict) -> int:
    return sum(n for _, n in _layers(cfg))


def model_flops_per_cell_round(cfg: dict, traffic: dict, n_val: int) -> float:
    """Model FLOPs of one steady-state cell-round: local training (forward
    and backward, 3x the forward, of m x E x B samples), one eval forward
    over the validation set on the sweep's share of eval rounds, and for
    the dynamic 3DG the probe forward of the m trained models.  A sweep's
    init (for the dynamic 3DG, the all-clients round) is not counted."""
    f = 2.0 * cnn_macs_per_sample(cfg)
    rounds = cfg["rounds"]
    evals = len({t for t in range(rounds)
                 if t % cfg["eval_every"] == 0 or t == rounds - 1})
    total = cfg["m"] * cfg["local_steps"] * cfg["batch_size"] * 3 * f
    total += evals / rounds * n_val * f
    g = traffic["graph"]
    if g["kind"] == "dynamic":
        total += cfg["m"] * g["probe_size"] * f
    return total
