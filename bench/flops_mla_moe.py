"""Operations and bytes that the DeepSeek-V3 block's training needs, from
a configuration's shapes (``bench/configs/moonlight16b-a3b.json``).

As in ``bench/flops.py``, these count what the algorithm requires, not
what an implementation does: causal attention sees each query against the
keys up to its own position, training is three times the forward matmul
work, and nothing recomputed counts.  Routed work counts the pairs this
device is expected to hold: of a token's ``num_experts_per_tok`` choices
among the router's experts, the share ``n_routed_experts / router
experts`` lands on the experts held here.  The grouped expert products
read the held experts' weights and their activations once per pass
(forward, gradient of the activations, gradient of the weights).
"""
from __future__ import annotations

from typing import Dict

F32 = 4  # bytes: parameters and activations are float32


def sizes(cfg: Dict) -> Dict[str, int]:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        qk=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        ff=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        E=cfg["expert_share"]["router_experts"], held=cfg["n_routed_experts"],
        K=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        dense=cfg["first_k_dense_replace"],
        experts_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        V=cfg["vocab_size"],
    )


def held_pairs(cfg: Dict, tokens: float) -> float:
    """Expected (token, choice) pairs routed to the experts held here."""
    z = sizes(cfg)
    return tokens * z["K"] * z["held"] / z["E"]


def mla_fwd_flops(cfg: Dict, seq: int) -> float:
    """One MLA layer's forward FLOPs over a causal sequence of ``seq``."""
    z = sizes(cfg)
    proj = (z["d"] * z["h"] * z["qk"] + z["d"] * (z["rank"] + z["rope"])
            + z["rank"] * z["h"] * (z["nope"] + z["v"]) + z["h"] * z["v"] * z["d"])
    keys = seq * (seq + 1) / 2  # query i sees i + 1 keys
    return 2.0 * proj * seq + 2.0 * z["h"] * (z["qk"] + z["v"]) * keys


def expert_layer_fwd_flops(cfg: Dict, seq: int) -> Dict[str, float]:
    """One expert layer's forward FLOPs over ``seq`` tokens, by part."""
    z = sizes(cfg)
    return {
        "mla": mla_fwd_flops(cfg, seq),
        "router": 2.0 * seq * z["d"] * z["E"],
        "experts": held_pairs(cfg, seq) * 2.0 * 3 * z["d"] * z["fe"],
        "shared": 2.0 * seq * 3 * z["d"] * z["shared"] * z["fe"],
    }


def dense_layer_fwd_flops(cfg: Dict, seq: int) -> float:
    z = sizes(cfg)
    return mla_fwd_flops(cfg, seq) + 2.0 * seq * 3 * z["d"] * z["ff"]


def fwd_flops_per_sequence(cfg: Dict, seq: int) -> float:
    """Forward FLOPs of one sequence: the dense layers, the expert layers
    and the output head (the embedding lookup is no matmul)."""
    z = sizes(cfg)
    return (z["dense"] * dense_layer_fwd_flops(cfg, seq)
            + z["experts_layers"] * sum(expert_layer_fwd_flops(cfg, seq).values())
            + 2.0 * seq * z["d"] * z["V"])


def train_flops_per_sequence(cfg: Dict, seq: int) -> float:
    return 3.0 * fwd_flops_per_sequence(cfg, seq)


def expert_gmm_work(cfg: Dict, tokens: float) -> Dict[str, float]:
    """FLOPs and bytes of the grouped expert products of one expert layer
    in a training step over ``tokens`` tokens of one client: three passes,
    each reading the held experts' weights (or writing their gradient) and
    the routed rows' activations once (inputs, both hidden projections,
    their product and the output)."""
    z = sizes(cfg)
    pairs = held_pairs(cfg, tokens)
    weights = z["held"] * 3 * z["d"] * z["fe"] * F32
    acts = pairs * (2 * z["d"] + 3 * z["fe"]) * F32
    return {"flops": 3.0 * pairs * 2.0 * 3 * z["d"] * z["fe"],
            "bytes": 3.0 * (weights + acts)}
