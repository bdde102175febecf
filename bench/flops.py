"""Operations that a configuration's work needs, from its shapes.

These count what the algorithm requires, not what an implementation does:
a causal attention needs each query against the keys up to its own
position.  Training counts three times the forward matmul work (forward,
gradient of the activations, gradient of the weights), less the gradient
of the input images, which nobody needs.  Nothing recomputed counts.  The
configuration dicts are those of ``bench/configs/*.json``.
"""
from __future__ import annotations

from typing import Dict, List


# --------------------------------------------------------------------------- #
# VGG (convolutions with 3x3 kernels, SAME padding; 2x2 max-pools)
# --------------------------------------------------------------------------- #


def vgg_layer_macs(cfg: Dict) -> List[int]:
    """Multiply-accumulates of each conv and FC layer for one image."""
    hw, cin = cfg["image_size"], cfg["in_channels"]
    macs = []
    for i, cout in enumerate(cfg["conv_channels"]):
        macs.append(hw * hw * 9 * cin * cout)
        cin = cout
        if i in cfg["pool_after"]:
            hw //= 2
    fin = cin * hw * hw
    for fout in cfg["fc_dims"]:
        macs.append(fin * fout)
        fin = fout
    return macs


def vgg_train_flops_per_image(cfg: Dict) -> float:
    macs = vgg_layer_macs(cfg)
    return 2.0 * (3 * sum(macs) - macs[0])


# --------------------------------------------------------------------------- #
# decoder-only transformer (GQA attention, SwiGLU MLP)
# --------------------------------------------------------------------------- #


def _head_dim(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def lm_layer_matmul_params(cfg: Dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, k, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    return d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * ff


def lm_matmul_params(cfg: Dict) -> int:
    """Weights each token multiplies: every layer's and the output head's
    (the tied embedding is read as the head; the lookup is no matmul)."""
    return (cfg["num_hidden_layers"] * lm_layer_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def lm_param_count(cfg: Dict) -> int:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    emb = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else emb
    return emb + head + L * (lm_layer_matmul_params(cfg) + 2 * d) + d


def lm_attention_fwd_flops(cfg: Dict, context: int) -> float:
    """Forward attention FLOPs of one query that sees ``context`` keys
    (scores and the weighted sum of values), over all layers."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * _head_dim(cfg) * context)


def lm_train_flops_per_sequence(cfg: Dict, seq: int) -> float:
    """Causal training FLOPs of one sequence of ``seq`` tokens."""
    matmul = 2.0 * lm_matmul_params(cfg) * seq
    attn = sum(lm_attention_fwd_flops(cfg, i + 1) for i in range(seq))
    return 3.0 * (matmul + attn)


def train_flops_per_sample(cfg: Dict, traffic: Dict) -> float:
    """FLOPs of one training sample: an image, or a sequence."""
    if cfg["family"] == "vgg":
        return vgg_train_flops_per_image(cfg)
    return lm_train_flops_per_sequence(cfg, traffic["seq"])
