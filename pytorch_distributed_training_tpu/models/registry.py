"""Model registry — uniform factory over the BASELINE model families.

The reference hardcodes its single model inline (``resnet18(num_classes=...)``,
src/main.py:49); the framework generalizes this to a name → entry registry
covering every BASELINE.json config.  Each entry carries a ``kind`` tag so
task-specific kwargs (``num_classes`` for classifiers — the reference's
dataset-driven head sizing) are applied uniformly, not by name matching."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

from .resnet import resnet18, resnet34, resnet50, resnet101, resnet152
from .vit import vit_b16, vit_l16, vit_s16
from .gpt2 import gpt2_124m, gpt2_large, gpt2_medium, gpt2_xl
from .instella_moe import instella_moe_16b_a3b
from .nemotron_h import nemotron_h_30b_a3b
from .qwen3_next import qwen3_next_80b_a3b
from .sdar import sdar_30b_a3b


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    factory: Callable
    kind: str  # "image_classifier" | "lm"


def _gpt2_moe(cfg_overrides: dict | None = None, **kw):
    """GPT-2 with Switch-style MoE MLPs in every odd block (models/moe.py)."""
    overrides = {"num_experts": 8, **(cfg_overrides or {})}
    return gpt2_124m(cfg_overrides=overrides, **kw)


MODEL_REGISTRY: dict[str, ModelEntry] = {
    "resnet18": ModelEntry(resnet18, "image_classifier"),
    "resnet34": ModelEntry(resnet34, "image_classifier"),
    "resnet50": ModelEntry(resnet50, "image_classifier"),
    "resnet101": ModelEntry(resnet101, "image_classifier"),
    "resnet152": ModelEntry(resnet152, "image_classifier"),
    "vit_s16": ModelEntry(vit_s16, "image_classifier"),
    "vit_b16": ModelEntry(vit_b16, "image_classifier"),
    "vit_l16": ModelEntry(vit_l16, "image_classifier"),
    "gpt2": ModelEntry(gpt2_124m, "lm"),
    "gpt2_medium": ModelEntry(gpt2_medium, "lm"),
    "gpt2_large": ModelEntry(gpt2_large, "lm"),
    "gpt2_xl": ModelEntry(gpt2_xl, "lm"),
    "gpt2_moe": ModelEntry(_gpt2_moe, "lm"),
    # An "lm" whose module says it trains by block diffusion
    # (``SdarMoe.lm_objective``, read by train/step.py): same batches, same
    # step, another loss.
    "sdar_30b_a3b": ModelEntry(sdar_30b_a3b, "lm"),
    # Next-token CE plus its MTP module's and the experts' balance term
    # (``InstellaMoe.lm_objective`` = "next_token_mtp").
    "instella_moe_16b_a3b": ModelEntry(instella_moe_16b_a3b, "lm"),
    # Plain next-token CE: single-sublayer layers of Mamba-2, attention and
    # relu² experts by a pattern string (models/nemotron_h.py).
    "nemotron_h_30b_a3b": ModelEntry(nemotron_h_30b_a3b, "lm"),
    # Plain next-token CE: Gated DeltaNet and gated attention 3 : 1, softmax
    # experts beside a gated shared one (models/qwen3_next.py).
    "qwen3_next_80b_a3b": ModelEntry(qwen3_next_80b_a3b, "lm"),
}


def create_model(name: str, *, num_classes: int | None = None, dtype: Any = jnp.float32, **kw):
    """Build a model by registry name.

    ``num_classes`` mirrors the reference's dataset-driven head sizing
    (src/main.py:49); it applies to classifier entries and is ignored for LMs.
    """
    if name not in MODEL_REGISTRY:
        raise ValueError(f"Unknown model {name!r}; available: {sorted(MODEL_REGISTRY)}")
    entry = MODEL_REGISTRY[name]
    if entry.kind == "image_classifier":
        kw["num_classes"] = 1000 if num_classes is None else num_classes
    return entry.factory(dtype=dtype, **kw)
