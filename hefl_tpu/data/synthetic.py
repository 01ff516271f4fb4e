"""Deterministic synthetic image datasets with learnable class structure.

The reference trains on a private medical image folder (`image/Train`,
`image/Test` — 1600/400 images, 2 classes, 256x256x3; SURVEY.md §6) that is
not in the repo, and BASELINE.json's configs add MNIST and CIFAR-10. In a
zero-egress environment none of these can be downloaded, so each gets a
synthetic stand-in with the same (H, W, C, num_classes) signature and a
genuinely learnable but non-trivial class signal: class-conditioned 2-D
Gabor-like textures at class-specific orientations/frequencies, plus
per-sample random phase, amplitude jitter, background blobs, and pixel
noise. A linear probe cannot max these out, a small CNN converges in a few
epochs — which is what FL-convergence tests need.

Images are uint8 (like files on disk); normalization to [0,1] happens in
the batcher, mirroring the reference's `rescale=1/255`
(/root/reference/FLPyfhelin.py:62).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from hefl_tpu.obs import spans as obs_spans


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    height: int
    width: int
    channels: int
    num_classes: int
    n_train: int
    n_test: int
    # --- difficulty knobs -------------------------------------------------
    # The class-information budget of a sample is (sig_amp * per-sample-amp *
    # Gabor + tmpl_amp * per-sample-amp * template) against (bg_amp *
    # background + noise_sigma * pixel noise). `amp_floor` is the lower edge
    # of the per-sample amplitude U(amp_floor, 1): near 0 it produces
    # genuinely ambiguous samples whose class signal is buried in noise, and
    # `orient_jitter` (radians) smears each class's Gabor orientation so the
    # class-conditional distributions overlap. Together these set an
    # irreducible Bayes error — the headroom that makes accuracy a real
    # measurement instead of a saturated 1.0 (VERDICT r2 weak #2).
    sig_amp: float = 0.4
    tmpl_amp: float = 0.5
    bg_amp: float = 0.3
    noise_sigma: float = 0.25
    orient_jitter: float = 0.0
    amp_floor: float = 0.6


# Cardinalities mirror the reference experiment (medical: SURVEY §6) and the
# classic dataset sizes, scaled down where full size adds nothing but time.
# The medical spec is tuned hard on purpose: the reference recipe (MedCNN,
# 2 clients x 10 epochs, 1600 images) should land in the ~0.85-0.95 band
# after one FL round — comparable to the reference's 0.8425 on its real
# data — with multi-round training climbing from there, so any quality
# regression (encoder clipping, augment bug, optimizer bug) is visible.
DATASETS: dict[str, DatasetSpec] = {
    "medical": DatasetSpec(
        "medical", 256, 256, 3, 2, 1600, 400,
        sig_amp=0.50, tmpl_amp=0.35, bg_amp=0.30, noise_sigma=0.32,
        orient_jitter=0.30, amp_floor=0.12,
    ),
    "mnist": DatasetSpec("mnist", 28, 28, 1, 10, 8000, 2000),
    "cifar10": DatasetSpec("cifar10", 32, 32, 3, 10, 8000, 2000),
}


def _class_signal(
    rng: np.random.Generator, spec: DatasetSpec, labels: np.ndarray
) -> np.ndarray:
    """Oriented sinusoidal texture per class + random phase per sample."""
    h, w = spec.height, spec.width
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    yy = yy / h - 0.5
    xx = xx / w - 0.5
    n = labels.shape[0]
    # class k -> orientation k*pi/K (smeared by orient_jitter so the
    # class-conditional orientation distributions overlap) and frequency
    # 4 + 3*(k % 3)
    theta = labels.astype(np.float32) * (np.pi / spec.num_classes)
    if spec.orient_jitter > 0:
        theta = theta + rng.normal(0, spec.orient_jitter, size=n).astype(np.float32)
    freq = 4.0 + 3.0 * (labels % 3).astype(np.float32)
    phase = rng.uniform(0, 2 * np.pi, size=n).astype(np.float32)
    amp = rng.uniform(spec.amp_floor, 1.0, size=n).astype(np.float32)
    proj = (
        np.cos(theta)[:, None, None] * xx[None] + np.sin(theta)[:, None, None] * yy[None]
    )
    sig = amp[:, None, None] * np.sin(
        2 * np.pi * freq[:, None, None] * proj + phase[:, None, None]
    )
    # radial envelope so the texture is localized like an anatomical feature
    r2 = xx[None] ** 2 + yy[None] ** 2
    return sig * np.exp(-r2 / 0.18)


def _class_template(spec: DatasetSpec, labels: np.ndarray) -> np.ndarray:
    """Fixed smooth spatial template per class (deterministic in the class
    index, not the dataset seed — train and test share it)."""
    h, w = spec.height, spec.width
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    yy = yy / h - 0.5
    xx = xx / w - 0.5
    temps = []
    for k in range(spec.num_classes):
        trng = np.random.default_rng(10_000 + k)
        t = np.zeros((h, w), np.float32)
        for _ in range(3):
            cy, cx = trng.uniform(-0.3, 0.3, size=2)
            s = trng.uniform(0.02, 0.08)
            sign = trng.choice([-1.0, 1.0])
            t += sign * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / s)
        temps.append(t / (np.abs(t).max() + 1e-9))
    return np.stack(temps)[labels]


def _box_blur(a: np.ndarray, k: int, axis: int) -> np.ndarray:
    """Vectorized 1-D box filter via cumulative sums (whole-array, no
    Python-level per-row loops)."""
    pad = [(0, 0)] * a.ndim
    pad[axis] = (k // 2 + 1, k // 2)
    c = np.cumsum(np.pad(a, pad, mode="edge"), axis=axis, dtype=np.float32)
    n = a.shape[axis]
    hi = np.take(c, np.arange(k, k + n), axis=axis)
    lo = np.take(c, np.arange(n), axis=axis)
    return (hi - lo) / k


def _background(rng: np.random.Generator, n: int, spec: DatasetSpec) -> np.ndarray:
    """Low-frequency blob background shared across classes (nuisance signal)."""
    h, w = spec.height, spec.width
    small = rng.normal(0, 1, size=(n, max(h // 8, 2), max(w // 8, 2))).astype(np.float32)
    up = small.repeat(h // small.shape[1] + 1, axis=1)[:, :h]
    up = up.repeat(w // small.shape[2] + 1, axis=2)[:, :, :w]
    return _box_blur(_box_blur(up, 5, axis=1), 5, axis=2)


def make_split(spec: DatasetSpec, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """-> (images uint8[n, H, W, C], labels int32[n]), balanced classes.

    Generated in chunks so peak host memory stays ~chunk-sized float32
    intermediates instead of six full-dataset arrays (matters at the
    medical spec: 1600 x 256 x 256).
    """
    rng = np.random.default_rng(seed)
    labels = rng.permutation(np.arange(n) % spec.num_classes).astype(np.int32)
    imgs = np.empty((n, spec.height, spec.width, spec.channels), np.uint8)
    chunk = max(1, min(n, (1 << 24) // (spec.height * spec.width)))
    for lo in range(0, n, chunk):
        lab = labels[lo : lo + chunk]
        k = len(lab)
        sig = _class_signal(rng, spec, lab)
        tmpl = _class_template(spec, lab)
        tmpl_amp = rng.uniform(spec.amp_floor, 1.0, size=k).astype(np.float32)[
            :, None, None
        ]
        bg = _background(rng, k, spec)
        noise = rng.normal(0, spec.noise_sigma, size=sig.shape).astype(np.float32)
        base = (
            spec.sig_amp * sig
            + spec.tmpl_amp * tmpl_amp * tmpl
            + spec.bg_amp * bg
            + noise
        )
        for c in range(spec.channels):
            # slight per-channel gain so channels are informative but correlated
            imgs[lo : lo + chunk, ..., c] = np.clip(
                (base * (1.0 - 0.12 * c) * 0.5 + 0.5) * 255.0, 0, 255
            ).astype(np.uint8)
    return imgs, labels


def make_dataset(
    name: str, seed: int = 0, n_train: int | None = None, n_test: int | None = None
):
    """-> ((x_train, y_train), (x_test, y_test), spec). Deterministic in seed."""
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; available: {sorted(DATASETS)}")
    spec = DATASETS[name]
    # Timed here and not at run_experiment's call: a driver that makes a
    # seed's data once for several calls (the benchmark) is timed too.
    with obs_spans.span("hefl.setup.data"):
        tr = make_split(spec, n_train or spec.n_train, seed)
        te = make_split(spec, n_test or spec.n_test, seed + 1)
    return tr, te, spec
