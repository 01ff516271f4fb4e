"""Jittable image augmentation — the `ImageDataGenerator` analog, MXU-native.

The reference's training generator (/root/reference/FLPyfhelin.py:81-88)
applies rescale=1/255, shear_range=0.2, zoom_range=0.2,
horizontal_flip=True. Keras does this per-image on the host with PIL-style
affine warps. A naive device port (`map_coordinates`) lowers to XLA's
general 2-D gather — historically assumed to be the TPU's slow path — so
the affine warp here is decomposed into stages that map onto MXU / VPU
primitives:

  1. vertical zoom   — one-hot bilinear interpolation MATRIX per image,
                       applied as a batched matmul (two nonzeros per row;
                       building it is a broadcast compare, applying it is
                       256x256 @ 256x(W*C) on the MXU);
  2. shear           — a per-row fractional x-shift delta(y) = tan(s)/zx *
                       (y-c). THREE interchangeable backends (see below);
  3. horizontal zoom + flip — one-hot matrix matmul like stage 1.

Row-shift backends (`HEFL_AUG_SHIFT` / `TrainConfig.aug_backend`):

  * ``gather``  — 1-D bilinear interpolation via `take_along_axis` along
                  the width axis (an XLA gather on ONE axis, not the 2-D
                  general gather). This is exactly Keras' bilinear kernel,
                  convex (no overshoot, no clamp pass), and O(W) per row.
                  Measured fastest everywhere tried so far (on CPU only:
                  the FFT shear cost 120 ms/batch on CPU; this path is
                  >20x cheaper at the same shape).
  * ``fft``     — bandlimited (sinc) shift through XLA's native real FFT:
                  transform each row, rotate bin f by e^{2pi i f delta/W},
                  transform back. O(W log W) per row.
  * ``dft``     — the same spectral shift as constant cos/sin DFT matrices
                  (MXU matmuls), O(W·F) per row.
  * ``auto``    — (default) one-shot micro-timing of the three backends at
                  first use on the live backend; the winner is cached for
                  the process and reported via `backend_report()` so bench
                  artifacts can record the choice.

The composite inverse map equals the reference's affine exactly
(src_y = (y-c)/zy + c, src_x = tan(s)/zx*(y-c) + f/zx*(x-c) + c). The
gather backend interpolates bilinearly like Keras; the spectral backends
interpolate with a bandlimited sinc, which rings at sharp edges (Gibbs), so
their sheared rows are clamped back to each image's own value range.
Randomness semantics follow Keras: shear angle ~ U(-s, s) radians,
zoom ~ U(1-z, 1+z) per axis, flip with probability 0.5.
"""

from __future__ import annotations

import functools
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Edge padding for the spectral shift. Must exceed the worst-case shear
# displacement tan(shear)/zx * (H-1)/2 = tan(0.2)/0.8 * 127.5 = 32.3 px at
# Keras-default ranges on 256x256, else the circular wrap leaks the opposite
# edge into corner rows. (The gather backend needs no padding: it clamps
# sample positions to the row, which IS edge padding.)
_PAD = 40

SHIFT_BACKENDS = ("gather", "fft", "dft")

# Requested backend: "gather" / "fft" / "dft" pin one; "auto" (default)
# micro-times the three at first use and caches the winner. HEFL_AUG_SHIFT
# overrides globally; TrainConfig.aug_backend / random_augment(backend=...)
# override per call site.
_ENV_BACKEND = os.environ.get("HEFL_AUG_SHIFT", "auto")

# One-shot auto-selection state (process-global so every trace of every
# program in one process agrees on the backend). _LAST_RESOLVED tracks the
# most recent resolution INCLUDING per-call pins (TrainConfig.aug_backend /
# random_augment(backend=...)) so backend_report() describes what traced
# programs actually use, not just the env/auto state.
_AUTO_CHOICE: str | None = None
_AUTO_TIMINGS_MS: dict[str, float] | None = None
_AUTO_PERSISTED: bool = False
_LAST_RESOLVED: str | None = None


def _lin_weights(src: jnp.ndarray, n: int) -> jnp.ndarray:
    """Sample positions [..., M] -> bilinear one-hot matrix [..., M, n]."""
    f = jnp.clip(jnp.floor(src), 0, n - 1)
    frac = src - f
    i0 = f.astype(jnp.int32)
    i1 = jnp.clip(i0 + 1, 0, n - 1)
    eye = jnp.arange(n)
    w0 = (1 - frac)[..., None] * (eye == i0[..., None])
    w1 = frac[..., None] * (eye == i1[..., None])
    return (w0 + w1).astype(jnp.float32)


@functools.lru_cache(maxsize=8)
def _dft_mats(wp: int):
    """Real-DFT analysis/synthesis matrices for length wp (host-built)."""
    f = np.arange(wp // 2 + 1)
    m = np.arange(wp)
    ang = 2 * np.pi * np.outer(f, m) / wp
    wgt = np.full(wp // 2 + 1, 2.0)
    wgt[0] = 1.0
    if wp % 2 == 0:
        wgt[-1] = 1.0
    return (
        np.cos(ang).astype(np.float32),
        np.sin(ang).astype(np.float32),
        (np.cos(ang) * wgt[:, None] / wp).astype(np.float32),
        (np.sin(ang) * wgt[:, None] / wp).astype(np.float32),
    )


def _shift_rows_dft(x: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """x[b, y, n, c] -> x sampled at n + delta[b, y] along axis 2 (sinc
    interpolation, edge-padded against circular wrap). Matmul-DFT form."""
    w = x.shape[2]
    wp = w + 2 * _PAD
    cm, sm, icm, ism = _dft_mats(wp)
    xp = jnp.pad(x, ((0, 0), (0, 0), (_PAD, _PAD), (0, 0)), mode="edge")
    xc = jnp.einsum("fm,bymc->byfc", jnp.asarray(cm), xp, preferred_element_type=jnp.float32)
    xs = jnp.einsum("fm,bymc->byfc", jnp.asarray(sm), xp, preferred_element_type=jnp.float32)
    phi = 2 * jnp.pi * jnp.arange(wp // 2 + 1)[None, None, :] * delta[:, :, None] / wp
    cphi, sphi = jnp.cos(phi)[..., None], jnp.sin(phi)[..., None]
    yc = xc * cphi + xs * sphi
    ys = -xc * sphi + xs * cphi
    out = jnp.einsum(
        "fn,byfc->bync", jnp.asarray(icm), yc, preferred_element_type=jnp.float32
    ) + jnp.einsum("fn,byfc->bync", jnp.asarray(ism), ys, preferred_element_type=jnp.float32)
    return out[:, :, _PAD : _PAD + w, :]


def _shift_rows_fft(x: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """Same bandlimited shift through XLA's native real FFT.

    With X_f = Σ_m x_m e^{-2πi f m/wp} (numpy rfft convention), sampling at
    m + δ multiplies bin f by e^{+2πi f δ/wp} — algebraically identical to
    `_shift_rows_dft`'s cos/sin rotation, at O(W log W) instead of O(W·F)
    per row.
    """
    w = x.shape[2]
    wp = w + 2 * _PAD
    xp = jnp.pad(x, ((0, 0), (0, 0), (_PAD, _PAD), (0, 0)), mode="edge")
    spec = jnp.fft.rfft(xp, axis=2)                      # complex64 [b,y,f,c]
    phi = 2 * jnp.pi * jnp.arange(wp // 2 + 1)[None, None, :] * delta[:, :, None] / wp
    rot = jax.lax.complex(jnp.cos(phi), jnp.sin(phi))[..., None]
    out = jnp.fft.irfft(spec * rot, n=wp, axis=2)
    return out[:, :, _PAD : _PAD + w, :].astype(jnp.float32)


def _shift_rows_gather(x: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """x[b, y, n, c] -> x sampled at n + delta[b, y] along axis 2, BILINEAR
    interpolation with edge clamping.

    Two `take_along_axis` gathers on the width axis plus a lerp — the
    integer-shift path the spectral machinery was standing in for. This is
    Keras' exact interpolation kernel (ImageDataGenerator warps
    bilinearly), it cannot overshoot the input range (convex combination),
    and clamping the sample position to [0, W-1] reproduces the edge-pad
    semantics of the spectral backends without materializing padding.
    """
    w = x.shape[2]
    src = jnp.arange(w, dtype=jnp.float32)[None, None, :] + delta[:, :, None]
    src = jnp.clip(src, 0.0, float(w - 1))
    i0 = jnp.floor(src).astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, w - 1)
    frac = (src - i0.astype(jnp.float32))[..., None]
    g0 = jnp.take_along_axis(x, i0[..., None], axis=2)
    g1 = jnp.take_along_axis(x, i1[..., None], axis=2)
    return (g0 * (1.0 - frac) + g1 * frac).astype(jnp.float32)


def _affine_gather(
    images: jnp.ndarray,
    s: jnp.ndarray,
    zx: jnp.ndarray,
    zy: jnp.ndarray,
    f: jnp.ndarray,
) -> jnp.ndarray:
    """The whole per-image affine (vertical zoom, shear, horizontal
    zoom/flip) as TWO separable bilinear gather passes — no matmuls, no
    spectra.

    The inverse map is the same composite the staged pipeline implements
    (src_y = (y-cy)/zy + cy; src_x = f/zx*(x-cx) + cx + tan(s)/zx*(y-cy)),
    but sampled with ONE bilinear kernel per axis directly on the source —
    which is exactly what Keras' ImageDataGenerator does, where the staged
    path convolves two interpolation kernels in x (shear, then zoom).
    Bilinear weights are convex, so no range clamp is needed.
    """
    b, h, w = images.shape[0], images.shape[1], images.shape[2]
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    yv = jnp.arange(h, dtype=jnp.float32)
    xv = jnp.arange(w, dtype=jnp.float32)
    # vertical zoom: gather rows at src_y = (y-cy)/zy + cy
    src_y = jnp.clip((yv[None, :] - cy) / zy[:, None] + cy, 0, h - 1)
    i0 = jnp.floor(src_y).astype(jnp.int32)
    i1 = jnp.minimum(i0 + 1, h - 1)
    fy = (src_y - i0.astype(jnp.float32))[:, :, None, None]
    r0 = jnp.take_along_axis(images, i0[:, :, None, None], axis=1)
    r1 = jnp.take_along_axis(images, i1[:, :, None, None], axis=1)
    t1 = r0 * (1.0 - fy) + r1 * fy
    # shear + horizontal zoom/flip fused into one x-gather:
    # src_x(y, x) = f/zx*(x-cx) + cx + tan(s)/zx*(y-cy)
    delta = (jnp.tan(s) / zx)[:, None] * (yv[None, :] - cy)          # [b, h]
    hx = (f / zx)[:, None] * (xv[None, :] - cx) + cx                 # [b, w]
    src_x = jnp.clip(hx[:, None, :] + delta[:, :, None], 0, w - 1)   # [b, h, w]
    j0 = jnp.floor(src_x).astype(jnp.int32)
    j1 = jnp.minimum(j0 + 1, w - 1)
    fx = (src_x - j0.astype(jnp.float32))[..., None]
    g0 = jnp.take_along_axis(t1, j0[..., None], axis=2)
    g1 = jnp.take_along_axis(t1, j1[..., None], axis=2)
    return (g0 * (1.0 - fx) + g1 * fx).astype(jnp.float32)


_SHIFT_FNS = {
    "gather": _shift_rows_gather,
    "fft": _shift_rows_fft,
    "dft": _shift_rows_dft,
}

# Micro-timing shape for auto-selection: one quarter of the flagship
# training batch (32 x 256 x 256 x 3). Small enough to cost well under a
# second on CPU, large enough that the backends' asymptotics separate.
_PROBE_SHAPE = (8, 256, 256, 3)


def _time_backend(fn, *args) -> float:
    import time

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _autoselect_backend() -> str:
    """One-shot micro-timing of the full augment per backend on the live
    device (the backends differ structurally — the gather path has no
    matmul stages — so timing only the row shift would mis-rank them).

    Runs the first time an auto-mode `random_augment` resolves — usually
    WHILE an outer program (the client train step) is being traced. Under
    an active trace a jitted call on concrete inputs is STAGED into the
    outer jaxpr (it returns tracers; `block_until_ready` on a tracer is a
    no-op), which would time tracing overhead (~1 ms flat, backend-blind)
    instead of execution — so the probe runs inside
    `jax.ensure_compile_time_eval()`, which forces real eager execution of
    the concrete probe inputs regardless of trace context. The winner is
    cached for the process AND persisted per device-kind next to the XLA
    compile cache (utils.autoselect) so short-lived CLI runs skip the
    first-trace micro-timing entirely; `backend_report()` exposes the
    choice + timings for bench artifacts.
    """
    global _AUTO_CHOICE, _AUTO_TIMINGS_MS, _AUTO_PERSISTED
    if _AUTO_CHOICE is not None:
        return _AUTO_CHOICE
    from hefl_tpu.utils.autoselect import load_winner, store_winner

    kind = str(getattr(jax.devices()[0], "device_kind", "unknown"))
    hit = load_winner("augment_shift", kind, allowed=SHIFT_BACKENDS)
    if hit is not None:
        _AUTO_CHOICE = hit["winner"]
        _AUTO_TIMINGS_MS = hit.get("timings_ms")
        _AUTO_PERSISTED = True
        return _AUTO_CHOICE
    with jax.ensure_compile_time_eval():
        # The probe INPUTS must also be built inside the eval context: under
        # an active trace `jax.random.key(0)` would stage and return a
        # tracer key, and one tracer input keeps every probe call staged.
        x = jnp.asarray(
            np.random.default_rng(0).random(_PROBE_SHAPE, np.float32)
        )
        key = jax.random.key(0)
        timings = {
            name: _time_backend(
                lambda k, im, bk=name: _random_augment(k, im, 0.2, 0.2, True, bk),
                key, x,
            )
            for name in SHIFT_BACKENDS
        }
    _AUTO_TIMINGS_MS = {k: round(v * 1e3, 3) for k, v in timings.items()}
    _AUTO_CHOICE = min(timings, key=timings.get)
    store_winner("augment_shift", kind, _AUTO_CHOICE, _AUTO_TIMINGS_MS)
    return _AUTO_CHOICE


def resolve_shift_backend(override: str | None = None) -> str:
    """The backend a `random_augment` call will actually use.

    Priority: explicit `override` (config / call site) > HEFL_AUG_SHIFT >
    "auto". "auto" triggers the one-shot micro-timing.
    """
    global _LAST_RESOLVED
    backend = override or _ENV_BACKEND or "auto"
    if backend == "auto":
        backend = _autoselect_backend()
    elif backend not in SHIFT_BACKENDS:
        raise ValueError(
            f"augment shift backend {backend!r}: expected one of "
            f"{SHIFT_BACKENDS + ('auto',)}"
        )
    _LAST_RESOLVED = backend
    return backend


def backend_report() -> dict:
    """What the augment layer is running — for bench/profile artifacts.

    `backend` is the most recent RESOLVED choice — per-call pins
    (TrainConfig.aug_backend) included, so a driver that pins a backend
    reports that backend, not the idle env/auto state. None before any
    resolution this process. `auto_timings_ms` carries the micro-timing
    that justified an auto choice, when one ran.
    """
    env = _ENV_BACKEND or "auto"
    resolved = _LAST_RESOLVED or (
        env if env in SHIFT_BACKENDS else _AUTO_CHOICE
    )
    return {
        "requested": env,
        "backend": resolved,
        "auto_timings_ms": _AUTO_TIMINGS_MS,
        # True when the auto winner came from the persisted per-device-kind
        # cache (utils.autoselect) instead of a live micro-timing.
        "auto_persisted": _AUTO_PERSISTED,
    }


def _shift_rows(x: jnp.ndarray, delta: jnp.ndarray, backend: str) -> jnp.ndarray:
    return _SHIFT_FNS[backend](x, delta)


def draw_affine_params(
    key: jax.Array, b: int, shear: float, zoom: float, flip: bool
):
    """One Keras-style random affine per image: -> (s, zx, zy, f), each
    f32[b] (shear angle, per-axis zoom, flip sign). The SINGLE source of
    the augment randomness, shared by the per-client `random_augment` path
    and the cross-client fused trainer (fl.fusion), which draws with each
    client's key and applies the warp on the client-folded batch — same
    key => same affines on both paths by construction."""
    k_shear, k_zx, k_zy, k_flip = jax.random.split(key, 4)
    s = jax.random.uniform(k_shear, (b,), minval=-shear, maxval=shear)
    zx = jax.random.uniform(k_zx, (b,), minval=1.0 - zoom, maxval=1.0 + zoom)
    zy = jax.random.uniform(k_zy, (b,), minval=1.0 - zoom, maxval=1.0 + zoom)
    f = jnp.where(
        flip, jnp.sign(jax.random.uniform(k_flip, (b,)) - 0.5), jnp.ones((b,))
    )
    return s, zx, zy, f


def apply_affine(
    images: jnp.ndarray,
    s: jnp.ndarray,
    zx: jnp.ndarray,
    zy: jnp.ndarray,
    f: jnp.ndarray,
    backend: str,
) -> jnp.ndarray:
    """Apply per-image affine params (shapes [b], from `draw_affine_params`)
    to a float batch [b, H, W, C]. Per-image math only — no cross-image
    coupling — so callers may fold any outer axis (e.g. clients) into the
    batch before calling; the per-image results are unchanged."""
    from hefl_tpu.obs import scopes as obs_scopes

    h, w = images.shape[1], images.shape[2]
    # Phase scope (obs): every warp op carries the hefl.augment scope in
    # its HLO metadata, so profiler-trace attribution can bucket augment
    # device time even when the warp is fused inside the train step.
    with jax.named_scope(obs_scopes.AUGMENT):
        if backend == "gather":
            # The fused two-pass bilinear warp: no one-hot matmuls, no
            # spectral shift — the whole affine is two axis gathers.
            return _affine_gather(images, s, zx, zy, f)
        cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
        yv = jnp.arange(h, dtype=jnp.float32)
        xv = jnp.arange(w, dtype=jnp.float32)
        # 1) vertical zoom: src_y = (y-cy)/zy + cy
        src_y = jnp.clip((yv[None, :] - cy) / zy[:, None] + cy, 0, h - 1)
        wy = _lin_weights(src_y, h)
        t1 = jnp.einsum(
            "byv,bvwc->bywc", wy, images, preferred_element_type=jnp.float32
        )
        # 2) shear: x-shift by delta(y) = tan(s)/zx * (y-cy). The sinc
        # kernel overshoots at edges (Gibbs), so clamp back to the image's
        # own range — stages 1 and 3 are convex (bilinear) and cannot
        # overshoot.
        delta = (jnp.tan(s) / zx)[:, None] * (yv[None, :] - cy)
        lo = jnp.min(t1, axis=(1, 2), keepdims=True)
        hi = jnp.max(t1, axis=(1, 2), keepdims=True)
        t2 = jnp.clip(_shift_rows(t1, delta, backend), lo, hi)
        # 3) horizontal zoom + flip: src_x = f/zx*(x-cx) + cx
        src_x = jnp.clip((f / zx)[:, None] * (xv[None, :] - cx) + cx, 0, w - 1)
        wx = _lin_weights(src_x, w)
        return jnp.einsum(
            "bxu,byuc->byxc", wx, t2, preferred_element_type=jnp.float32
        )


@partial(jax.jit, static_argnames=("shear", "zoom", "flip", "backend"))
def _random_augment(
    key: jax.Array,
    images: jnp.ndarray,
    shear: float,
    zoom: float,
    flip: bool,
    backend: str,
) -> jnp.ndarray:
    from hefl_tpu.obs import scopes as obs_scopes

    b = images.shape[0]
    with jax.named_scope(obs_scopes.AUGMENT):
        s, zx, zy, f = draw_affine_params(key, b, shear, zoom, flip)
    return apply_affine(images, s, zx, zy, f, backend)


def random_augment(
    key: jax.Array,
    images: jnp.ndarray,
    shear: float = 0.2,
    zoom: float = 0.2,
    flip: bool = True,
    backend: str | None = None,
) -> jnp.ndarray:
    """Batch [B, H, W, C] float images -> augmented batch, one random
    (shear, zoom, horizontal-flip) affine per image. See the module
    docstring for the three-stage decomposition and the shift backends.

    `backend` pins the row-shift backend for this call site (e.g. from
    `TrainConfig.aug_backend`); None defers to HEFL_AUG_SHIFT / auto.
    Backend resolution happens at trace time, so calls inside jitted code
    (the client train step) resolve once per compiled program.
    """
    bk = resolve_shift_backend(backend)
    return _random_augment(key, images, shear, zoom, flip, bk)


def rescale(images: jnp.ndarray) -> jnp.ndarray:
    """uint8 [0,255] -> float32 [0,1] (the reference's rescale=1/255)."""
    return images.astype(jnp.float32) / 255.0
