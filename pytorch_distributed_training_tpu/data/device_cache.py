"""Device-cached dataset: the whole uint8 corpus resident in HBM, with
per-step batch assembly (gather + random crop + horizontal flip) running
on-device inside jit.

The reference streams every batch host->device per step (the ``.to(device)``
copies, /root/reference/src/main.py:69-70).  On TPU the idiomatic
alternative for datasets that fit in HBM (CIFAR-10: ~180 MB; packed bench
shards) is the MLPerf-style device cache: upload the uint8 records ONCE,
then assemble each step's batch with on-chip ops — ``jnp.take`` for the
gather, vmapped ``lax.dynamic_slice`` for per-sample random crops, a flip
mask, all jitted.  Steady-state input cost is a few hundred microseconds of
device time and ZERO host->device bytes, so training throughput is immune
to host-feed bandwidth.

Augmentation here is RandomCrop + horizontal flip (the standard CIFAR
recipe; records are pre-resized).  Full RandomResizedCrop needs per-sample
*scaled* resizes — dynamic shapes jit cannot express — so scale/aspect
jitter stays in the host pipeline (``PackedImages``/``ImageFolder``); use
that path when you need it.

Epoch order matches DataLoader semantics: a full permutation per epoch
(``jax.random.permutation`` keyed by (seed, epoch), computed on device),
each index visited exactly once; the last partial batch is dropped
(``drop_last`` — required for a static batch shape under jit).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .imagenet import IMAGENET_MEAN, IMAGENET_STD


class DeviceCachedImages:
    """HBM-resident image dataset with on-device augmentation.

    Args:
      source: anything with ``.images`` (N,H,W,C uint8) and ``.labels``
        (N,) int — e.g. ``PackedImages`` — or an ``(images, labels)`` tuple.
      mesh: optional ``jax.sharding.Mesh``; the cache is placed replicated
        over it so a data-sharded batch gather partitions cleanly.
      crop_size: output spatial size (records must be >= this).
      train: random crop + flip when True; center crop when False.
    """

    def __init__(
        self,
        source: Any,
        *,
        mesh=None,
        crop_size: int,
        train: bool = True,
        seed: int = 0,
        mean: np.ndarray = IMAGENET_MEAN,
        std: np.ndarray = IMAGENET_STD,
    ):
        if isinstance(source, tuple):
            images, labels = source
        else:
            images, labels = source.images, source.labels
        images = np.ascontiguousarray(images)
        labels = np.ascontiguousarray(labels, dtype=np.int32)
        if images.dtype != np.uint8:
            raise ValueError(f"device cache wants uint8 records, got {images.dtype}")
        n, h, w, _ = images.shape
        if h < crop_size or w < crop_size:
            raise ValueError(f"records {h}x{w} smaller than crop {crop_size}")
        self.n = int(n)
        self.crop_size = int(crop_size)
        self.train = train
        self.seed = seed
        self.mean = np.asarray(mean)
        self.std = np.asarray(std)
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(mesh, PartitionSpec())
            self._images = jax.device_put(images, replicated)
            self._labels = jax.device_put(labels, replicated)
        else:
            self._images = jax.device_put(images)
            self._labels = jax.device_put(labels)

    def __len__(self) -> int:
        return self.n

    def batches(
        self, epoch: int, batch_size: int, *, per_sample_crop: bool = False
    ) -> Iterator[dict]:
        """Yield on-device ``{"image", "label"}`` batches for one epoch.

        Every array stays on device; the host loop only threads the
        already-jitted calls, so there is no H2D traffic after the cache
        was built.

        Crop semantics match :meth:`make_epoch_fn`: one random crop box per
        *batch*, flips per-sample (the device-cache trade — see the
        ``per_sample_crop`` note there; per-sample boxes lower to a
        windowed gather XLA executes at ~1 GB/s, measured ~2x slower
        end-to-end at 224px).  Both consumers of the cache therefore run
        the same augmentation math and the same speed.
        """
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
        perm = _permute(self._labels, key) if self.train else jnp.arange(self.n)
        steps = self.n // batch_size
        assemble = _make_assemble(
            self.crop_size, self.train, batch_size,
            self._images.shape[1], self._images.shape[2], per_sample_crop,
        )
        if self.mesh is not None:
            from ..parallel.sharding import batch_sharding

            shardings = {
                "image": batch_sharding(self.mesh, ndim=4),
                "label": batch_sharding(self.mesh, ndim=1),
            }
        for step in range(steps):
            idx = lax.dynamic_slice_in_dim(perm, step * batch_size, batch_size)
            b = assemble(
                self._images, self._labels, idx, jax.random.fold_in(key, step)
            )
            if self.mesh is not None:
                # Reshard replicated->data-sharded on device (drops shards,
                # no transfer) so the DP step sees the same placement the
                # host path's shard_batch() provides.
                b = {k: jax.device_put(v, shardings[k]) for k, v in b.items()}
            yield b

    def make_epoch_fn(self, step_fn, batch_size: int, *,
                      per_sample_crop: bool = False):
        """Whole training epoch as ONE jitted ``lax.scan`` over steps.

        ``batches()`` + ``step_fn`` costs several device dispatches per
        step.  The epoch-scan form touches the host ONCE per epoch: the
        shuffle, per-step batch slice, crop/flip, and train step are all
        inside the scan body.

        ``per_sample_crop=False`` (default) draws one crop box per *batch*
        (flips stay per-sample): a per-sample crop lowers to a windowed
        gather that XLA executes at ~1 GB/s effective (measured: +55 ms on
        a 128x232x232x3 batch vs +2 ms batch-uniform).  Set True when that
        cost is acceptable (small images: CIFAR).

        While training, the epoch's shuffle is materialized as a permuted
        copy of the whole dataset — 2x the cache's HBM footprint for the
        epoch, but contiguous per-step slices instead of per-step row
        gathers (measured ~30% faster end-to-end on v5e); eval skips the
        copy (identity order).

        Returns ``run_epoch(state, epoch) -> (state, mean_metrics)``.
        """
        crop, train = self.crop_size, self.train
        n, h, w = self.n, self._images.shape[1], self._images.shape[2]
        steps = n // batch_size
        seed = self.seed
        mesh = self.mesh
        if mesh is not None:
            from ..parallel.sharding import batch_sharding

            img_sharding = batch_sharding(mesh, ndim=4)
            lbl_sharding = batch_sharding(mesh, ndim=1)

        @partial(jax.jit, donate_argnums=0)
        def run_epoch_jit(state, images, labels, perm, key):
            if train:
                images_p = jnp.take(images, perm, axis=0)
                labels_p = jnp.take(labels, perm, axis=0)
            else:
                images_p, labels_p = images, labels

            def body(st, i):
                k = jax.random.fold_in(key, i)
                imgs = lax.dynamic_slice_in_dim(images_p, i * batch_size, batch_size)
                lbls = lax.dynamic_slice_in_dim(labels_p, i * batch_size, batch_size)
                if mesh is not None:
                    # Hand GSPMD the data-axis sharding the host path gets
                    # from shard_batch(): without it the replicated cache
                    # propagates replicated batches and DP scaling is lost.
                    imgs = lax.with_sharding_constraint(imgs, img_sharding)
                    lbls = lax.with_sharding_constraint(lbls, lbl_sharding)
                if train and per_sample_crop:
                    idx = jnp.arange(batch_size)
                    b = _assemble_body(
                        imgs, lbls, idx, k, crop, True, batch_size, h, w
                    )
                    imgs, lbls = b["image"], b["label"]
                elif train:
                    ky, kx, kf = jax.random.split(k, 3)
                    oy = jax.random.randint(ky, (), 0, h - crop + 1)
                    ox = jax.random.randint(kx, (), 0, w - crop + 1)
                    flip = jax.random.bernoulli(kf, 0.5, (batch_size,))
                    imgs = lax.dynamic_slice(
                        imgs, (0, oy, ox, 0), (batch_size, crop, crop, imgs.shape[-1])
                    )
                    imgs = jnp.where(
                        flip[:, None, None, None], imgs[:, :, ::-1, :], imgs
                    )
                else:
                    oy, ox = (h - crop) // 2, (w - crop) // 2
                    imgs = imgs[:, oy:oy + crop, ox:ox + crop, :]
                st, m = step_fn(st, {"image": imgs, "label": lbls})
                return st, m

            state, ms = lax.scan(body, state, jnp.arange(steps))
            return state, jax.tree_util.tree_map(
                lambda x: jnp.mean(x, axis=0) if jnp.issubdtype(
                    x.dtype, jnp.floating
                ) else x[-1],
                ms,
            )

        def run_epoch(state, epoch: int):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
            perm = _permute(self._labels, key) if train else jnp.arange(n)
            return run_epoch_jit(state, self._images, self._labels, perm, key)

        return run_epoch


@jax.jit
def _permute(labels: jax.Array, key: jax.Array) -> jax.Array:
    return jax.random.permutation(key, labels.shape[0])


def _assemble_body(
    images, labels, idx, key, crop, train, batch, h, w,
    per_sample_crop=True,
):
    """Pure gather + augment math, traced either standalone or fused.

    ``per_sample_crop=False`` draws one crop box for the whole batch
    (flips stay per-sample): a contiguous dynamic_slice instead of the
    windowed per-sample gather — the fast path both the epoch scan and
    ``batches()`` default to.
    """
    imgs = jnp.take(images, idx, axis=0)
    lbls = jnp.take(labels, idx, axis=0)
    if train:
        ky, kx, kf = jax.random.split(key, 3)
        if per_sample_crop:
            oy = jax.random.randint(ky, (batch,), 0, h - crop + 1)
            ox = jax.random.randint(kx, (batch,), 0, w - crop + 1)

            def one(im, y, x):
                return lax.dynamic_slice(
                    im, (y, x, 0), (crop, crop, im.shape[-1])
                )

            imgs = jax.vmap(one)(imgs, oy, ox)
        else:
            oy = jax.random.randint(ky, (), 0, h - crop + 1)
            ox = jax.random.randint(kx, (), 0, w - crop + 1)
            imgs = lax.dynamic_slice(
                imgs, (0, oy, ox, 0), (batch, crop, crop, imgs.shape[-1])
            )
        flip = jax.random.bernoulli(kf, 0.5, (batch,))
        imgs = jnp.where(flip[:, None, None, None], imgs[:, :, ::-1, :], imgs)
    else:
        oy = (h - crop) // 2
        ox = (w - crop) // 2
        imgs = imgs[:, oy:oy + crop, ox:ox + crop, :]
    return {"image": imgs, "label": lbls}


@lru_cache(maxsize=None)
def _make_assemble(
    crop: int, train: bool, batch: int, h: int, w: int,
    per_sample_crop: bool = True,
):
    """Jitted (images, labels, idx, key) -> batch dict, cached per config
    (the lru_cache reuses one jitted callable across epochs — a fresh
    closure per epoch would retrace every time)."""

    @jax.jit
    def assemble(images, labels, idx, key):
        return _assemble_body(
            images, labels, idx, key, crop, train, batch, h, w,
            per_sample_crop,
        )

    return assemble
