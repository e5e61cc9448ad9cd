"""Request and calibration images: the MNIST / smallNORB / CIFAR-10
analogues of the program's `repro.data.synthetic.make_image_dataset`,
copied here so that the benchmark's inputs do not move when the program
does.  Class templates rendered with a random affine pose and noise,
all drawn from one NumPy generator.  The kind decides the content and
the channels; the configuration's `input_shape` decides the size, the
templates and shifts scaled with it from the kind's own size.
"""
from __future__ import annotations

import numpy as np

DIGITS = [
    "01110 10001 10011 10101 11001 10001 01110",  # 0
    "00100 01100 00100 00100 00100 00100 01110",  # 1
    "01110 10001 00001 00010 00100 01000 11111",  # 2
    "01110 10001 00001 00110 00001 10001 01110",  # 3
    "00010 00110 01010 10010 11111 00010 00010",  # 4
    "11111 10000 11110 00001 00001 10001 01110",  # 5
    "01110 10000 11110 10001 10001 10001 01110",  # 6
    "11111 00001 00010 00100 01000 01000 01000",  # 7
    "01110 10001 10001 01110 10001 10001 01110",  # 8
    "01110 10001 10001 01111 00001 00001 01110",  # 9
]


def _bitmap(tpl: str, k: int) -> np.ndarray:
    rows = np.array([[float(c) for c in r] for r in tpl.split()],
                    np.float32)
    return np.kron(rows, np.ones((k, k), np.float32))


def _affine_place(canvas_hw, img, rng, max_shift=3, rot=0.35, scale=0.25):
    """Place `img` on a canvas with a random rotation, scale and shift
    (inverse-mapped bilinear sampling)."""
    H, W = canvas_hw
    h, w = img.shape
    th = rng.uniform(-rot, rot)
    sc = 1.0 + rng.uniform(-scale, scale)
    cx = W / 2 + rng.integers(-max_shift, max_shift + 1)
    cy = H / 2 + rng.integers(-max_shift, max_shift + 1)
    cos, sin = np.cos(th) / sc, np.sin(th) / sc
    ys, xs = np.mgrid[0:H, 0:W]
    u = cos * (xs - cx) + sin * (ys - cy) + w / 2
    v = -sin * (xs - cx) + cos * (ys - cy) + h / 2
    u0 = np.clip(np.floor(u).astype(int), 0, w - 2)
    v0 = np.clip(np.floor(v).astype(int), 0, h - 2)
    du = np.clip(u - u0, 0, 1)
    dv = np.clip(v - v0, 0, 1)
    valid = (u >= 0) & (u < w - 1) & (v >= 0) & (v < h - 1)
    out = (img[v0, u0] * (1 - du) * (1 - dv) + img[v0, u0 + 1] * du * (1 - dv)
           + img[v0 + 1, u0] * (1 - du) * dv + img[v0 + 1, u0 + 1] * du * dv)
    return np.where(valid, out, 0.0).astype(np.float32)


def _shape_mask(kind: int, size: int = 24) -> np.ndarray:
    ys, xs = np.mgrid[0:size, 0:size].astype(np.float32)
    c = (size - 1) / 2
    x, y = (xs - c) / c, (ys - c) / c
    if kind == 0:                                     # ellipse
        return ((x / 0.9) ** 2 + (y / 0.55) ** 2 <= 1).astype(np.float32)
    if kind == 1:                                     # rectangle
        return ((np.abs(x) <= 0.8) & (np.abs(y) <= 0.45)).astype(np.float32)
    if kind == 2:                                     # triangle
        return ((y >= -0.7) & (y <= 0.8) &
                (np.abs(x) <= 0.8 * (0.8 - y) / 1.5)).astype(np.float32)
    if kind == 3:                                     # plus
        return ((np.abs(x) <= 0.25) | (np.abs(y) <= 0.25)).astype(np.float32)
    r = np.sqrt(x * x + y * y)
    a = np.arctan2(y, x)
    return (r <= 0.45 + 0.4 * np.cos(5 * a) ** 2).astype(np.float32)  # star


# (H, W, C, classes): each kind's own size, its channels and classes
KINDS = {"mnist": (28, 28, 1, 10), "edge_tiny": (16, 16, 1, 4),
         "smallnorb": (32, 32, 2, 5), "cifar10": (32, 32, 3, 10)}


def make_images(kind: str, shape, n: int,
                rng: np.random.Generator) -> np.ndarray:
    """n float32 NHWC images in [0, 1] of the analogue `kind` at `shape`
    = (H, W, C); C has to be the kind's."""
    H0, W0, C0, ncls = KINDS[kind]
    H, W, C = shape
    if C != C0:
        raise ValueError(f"images {kind!r} have {C0} channel(s), not {C}")
    z = min(H / H0, W / W0)

    def px(v):
        return max(1, round(v * z))

    imgs = np.zeros((n, H, W, C), np.float32)
    labels = rng.integers(0, ncls, n)
    for i in range(n):
        y = int(labels[i])
        if kind == "mnist":
            imgs[i, :, :, 0] = _affine_place(
                (H, W), _bitmap(DIGITS[y], px(3)), rng, max_shift=px(3))
        elif kind == "edge_tiny":
            imgs[i, :, :, 0] = _affine_place(
                (H, W), _bitmap(DIGITS[y], px(2)), rng, max_shift=px(1))
        elif kind == "smallnorb":
            base = _affine_place((H, W), _shape_mask(y, px(24)), rng,
                                 rot=1.2, max_shift=px(3))
            light = rng.uniform(0.5, 1.0)
            shift = rng.integers(1, 3)
            imgs[i, :, :, 0] = base * light
            imgs[i, :, :, 1] = np.roll(base, shift, axis=1) * light
        else:
            base = _affine_place((H, W), _shape_mask(y % 5, px(24)), rng,
                                 rot=1.2, max_shift=px(3))
            col = rng.uniform(0.6, 1.0, 3)
            col[y // 5] *= 0.3                    # class-dependent colour
            for ch in range(3):
                imgs[i, :, :, ch] = base * col[ch]
            imgs[i] += rng.uniform(0, 0.25) * \
                rng.random((H, W, C)).astype(np.float32)
        imgs[i] += rng.normal(0, 0.04, (H, W, C)).astype(np.float32)
    np.clip(imgs, 0.0, 1.0, out=imgs)
    return imgs
