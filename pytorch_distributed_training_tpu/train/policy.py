"""Mixed-precision policy — the AMP-equivalent (bf16) path.

The reference has no mixed precision (SURVEY.md §2c "AMP" row); BASELINE.json
configs[2] requires it for ViT-B/16, mapped to bf16 on TPU per the north
star.  Unlike CUDA AMP (autocast context + GradScaler, needed because fp16
underflows), TPU bf16 shares the f32 exponent range, so the policy is purely
a dtype assignment: master params stay f32, compute runs in bf16 on the MXU,
and no loss scaling is required.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    """param_dtype: storage (master) dtype; compute_dtype: matmul dtype."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def cast_to_compute(self, tree: Any, keep: tuple = ()) -> Any:
        """Cast float leaves to the compute dtype (int/bool leaves untouched).
        A leaf whose own key is in ``keep`` stays as stored: a model names
        there what its arithmetic reads in float32 (``float32_params`` on the
        module: a state-space mixer's ``A_log``, ``dt_bias``, ``D``)."""
        def cast(path, x):
            kept = bool(path) and getattr(path[-1], "key", None) in keep
            if jnp.issubdtype(x.dtype, jnp.floating) and not kept:
                return x.astype(self.compute_dtype)
            return x
        return jax.tree_util.tree_map_with_path(cast, tree)

    def cast_to_param(self, tree: Any) -> Any:
        def cast(x):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.param_dtype)
            return x
        return jax.tree_util.tree_map(cast, tree)


def make_policy(name: str) -> Policy:
    """"f32" | "bf16" (mixed: f32 master, bf16 compute) | "bf16_full"."""
    if name in ("f32", "float32", "fp32"):
        return Policy()
    if name in ("bf16", "bfloat16", "mixed"):
        return Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16)
    if name == "bf16_full":
        return Policy(param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16)
    raise ValueError(f"Unknown precision policy {name!r}")
