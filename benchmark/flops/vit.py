"""Model FLOPs of a ViT classifier, from the configuration's shapes.

Counted as in ``flops/gpt2.py`` (forward matmuls x 3; attention's QK^T and AV
in full, since it is not causal; the patch embedding and the head counted).

Hand-worked, ViT-B/16 (d=768, 12 layers, MLP 3072, patch 16, 224 px, 1000
classes): 196 patches + CLS = 197 tokens, per image:
  patch embed  196 * 2 * (16*16*3) * 768                 =     231,211,008
  per layer    197 * 24*d^2 (qkv, proj, two MLP matmuls)  =   2,788,687,872
  12 layers                                               =  33,464,254,464
  attention    12 * 197 * (2 * 2*197*768)                 =   1,430,654,976
  head         2 * 768 * 1000                             =       1,536,000
  forward                                                 =  35,127,656,448
  x3                                                      = 105,382,969,344  (105.4 GFLOP/image)
"""

from __future__ import annotations


def forward_flops_per_image(cfg: dict) -> float:
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    d_ff, patch = cfg["intermediate_size"], cfg["patch_size"]
    side, classes = cfg["image_size"], cfg["num_labels"]
    patches = (side // patch) ** 2
    tokens = patches + 1
    embed = patches * 2 * (patch * patch * cfg["num_channels"]) * d
    dense = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * d_ff
    attention = 2 * 2 * tokens * d
    return float(embed + layers * tokens * (dense + attention) + 2 * d * classes)


def train_flops_per_sample(cfg: dict, shape: dict) -> float:
    return 3.0 * forward_flops_per_image(cfg)


def units_per_sample(cfg: dict, shape: dict) -> tuple[str, float]:
    return "images", 1.0
