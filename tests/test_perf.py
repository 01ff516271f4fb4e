"""Hot-path perf guarantees as CPU-deterministic tests (ISSUE 1).

Three families, none timing-based (timing belongs to mfu_probe.py /
profile_round.py on real hardware):

  * augment golden parity — the gather row-shift (the fast path) against an
    independent numpy bilinear reference (golden values) and against the
    spectral FFT backend it replaced (bandlimited inputs, where bilinear
    and sinc interpolation must agree);
  * scan-layout semantics — the flattened steps-major local-training scan
    and `accum_steps` must reproduce the nested reference layout's
    callback decisions (early-stop / plateau / restore) exactly;
  * FLOP regression — `cost_analysis()['flops']` of the compiled round
    must stay within an analytic envelope of fwd+bwd cost, catching
    accidental recompute blowups (e.g. a scan body that re-materializes
    the forward pass) without any wall-clock flakiness.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.data.augment import (
    SHIFT_BACKENDS,
    _shift_rows_fft,
    _shift_rows_gather,
    backend_report,
    random_augment,
    resolve_shift_backend,
)
from hefl_tpu.fl import TrainConfig, local_train
from hefl_tpu.models import SmallCNN
from hefl_tpu.utils import roofline


# ---------------------------------------------------------------- augment


def _numpy_bilinear_shift(x: np.ndarray, delta: np.ndarray) -> np.ndarray:
    """Independent golden reference: per-row bilinear resample along width
    with edge clamping — np.interp per (b, y, c) row."""
    b, h, w, c = x.shape
    out = np.empty_like(x)
    pos = np.arange(w, dtype=np.float64)
    for bi in range(b):
        for yi in range(h):
            src = np.clip(pos + float(delta[bi, yi]), 0, w - 1)
            for ci in range(c):
                out[bi, yi, :, ci] = np.interp(src, pos, x[bi, yi, :, ci])
    return out


def test_gather_shift_matches_numpy_golden():
    rng = np.random.default_rng(11)
    x = rng.random((2, 6, 24, 3), np.float32)
    delta = rng.uniform(-7.5, 7.5, (2, 6)).astype(np.float32)
    got = np.asarray(_shift_rows_gather(jnp.asarray(x), jnp.asarray(delta)))
    want = _numpy_bilinear_shift(x, delta)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_gather_shift_agrees_with_fft_on_bandlimited_rows():
    # On smooth (low-frequency) rows the bilinear gather and the sinc FFT
    # shift are the same resampling; they diverge only at frequencies the
    # linear kernel attenuates. Interior columns only: the FFT path's
    # edge-pad and the gather's clamp handle the boundary differently.
    w = 64
    t = np.arange(w) / w
    rows = np.stack(
        [np.sin(2 * np.pi * f * t + p)
         for f, p in [(1, 0.0), (2, 1.1), (3, 0.4)]]
    ).astype(np.float32)
    x = np.tile(rows[None, :, :, None], (2, 1, 1, 1))
    x = (x - x.min()) / (x.max() - x.min())
    delta = np.array([[3.25, -2.5, 0.75], [-5.0, 1.5, 4.2]], np.float32)
    a = np.asarray(_shift_rows_gather(jnp.asarray(x), jnp.asarray(delta)))
    b = np.asarray(_shift_rows_fft(jnp.asarray(x), jnp.asarray(delta)))
    # 6e-3: the linear kernel's attenuation of the f=3 component (the
    # kernels are different low-pass filters; they converge as f -> 0).
    np.testing.assert_allclose(a[:, :, 8:-8, :], b[:, :, 8:-8, :], atol=6e-3)


def test_full_augment_gather_parity_with_fft():
    # End-to-end warp parity on smooth images: same key -> same random
    # affine; the gather and spectral pipelines must land on the same
    # augmented batch up to interpolation-kernel tolerance.
    n = 32
    yy, xx = np.mgrid[0:n, 0:n] / n
    img = (0.5 + 0.25 * np.sin(2 * np.pi * yy) * np.cos(2 * np.pi * xx))
    imgs = jnp.asarray(
        np.tile(img[None, :, :, None], (4, 1, 1, 3)).astype(np.float32)
    )
    key = jax.random.key(42)
    a = np.asarray(random_augment(key, imgs, backend="gather"))
    b = np.asarray(random_augment(key, imgs, backend="fft"))
    assert np.mean(np.abs(a - b)) < 2e-3
    np.testing.assert_allclose(a[:, 4:-4, 4:-4, :], b[:, 4:-4, 4:-4, :],
                               atol=3e-2)


def test_backend_resolution_and_autoselect(monkeypatch):
    import hefl_tpu.data.augment as aug

    # explicit pins resolve verbatim; junk raises
    for bk in SHIFT_BACKENDS:
        assert resolve_shift_backend(bk) == bk
    with pytest.raises(ValueError):
        resolve_shift_backend("fancy")
    # auto mode: micro-time once, cache the winner, expose it in the report
    monkeypatch.setattr(aug, "_PROBE_SHAPE", (2, 16, 16, 1))
    monkeypatch.setattr(aug, "_AUTO_CHOICE", None)
    monkeypatch.setattr(aug, "_AUTO_TIMINGS_MS", None)
    monkeypatch.setattr(aug, "_ENV_BACKEND", "auto")
    chosen = aug.resolve_shift_backend(None)
    assert chosen in SHIFT_BACKENDS
    assert aug._AUTO_CHOICE == chosen  # cached for the process
    rep = backend_report()
    assert rep["requested"] == "auto" and rep["backend"] == chosen
    assert set(rep["auto_timings_ms"]) == set(SHIFT_BACKENDS)


def test_autoselect_probe_executes_concretely_inside_trace(monkeypatch):
    # The auto-probe usually fires WHILE the client train step is being
    # traced. Without ensure_compile_time_eval (and concrete probe inputs
    # built under it), the timed calls stage into the outer jaxpr and
    # return tracers — block_until_ready no-ops and every backend "times"
    # at ~1 ms of tracing overhead, so auto mode picks a random (usually
    # slow) backend. Guard: the timed probe results must be concrete.
    import hefl_tpu.data.augment as aug

    monkeypatch.setattr(aug, "_PROBE_SHAPE", (2, 16, 16, 1))
    monkeypatch.setattr(aug, "_AUTO_CHOICE", None)
    monkeypatch.setattr(aug, "_AUTO_TIMINGS_MS", None)
    monkeypatch.setattr(aug, "_ENV_BACKEND", "auto")
    seen: list[str] = []
    orig = aug._time_backend

    def spy(fn, *args):
        out = fn(*args)
        seen.append(type(out).__name__)
        return orig(fn, *args)

    monkeypatch.setattr(aug, "_time_backend", spy)

    @jax.jit
    def traced(x):
        return aug.random_augment(jax.random.key(0), x, backend=None)

    traced(jnp.ones((1, 8, 8, 1), jnp.float32)).block_until_ready()
    assert seen and all("Tracer" not in t for t in seen), seen
    assert aug._AUTO_CHOICE in SHIFT_BACKENDS


# ------------------------------------------------------- scan-layout parity


def _fixture(per_client=96, seed=3):
    (x, y), _, _ = make_dataset("mnist", seed=seed, n_train=per_client,
                                n_test=16)
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    return model, params, jnp.asarray(x), jnp.asarray(y)


# patience tight enough that the 6-epoch fixture exercises plateau + early
# stop + best-weight restore, the semantics the flat layout must preserve.
_SEM_CFG = TrainConfig(
    epochs=6, batch_size=16, num_classes=10, augment=False, val_fraction=0.25,
    es_patience=2, plateau_patience=1,
)


def test_flat_scan_reproduces_nested_callback_semantics():
    model, params, x, y = _fixture()
    key = jax.random.key(7)
    flat_p, flat_m = local_train(
        model, dataclasses.replace(_SEM_CFG, flat_scan=True), params, x, y, key
    )
    nest_p, nest_m = local_train(
        model, dataclasses.replace(_SEM_CFG, flat_scan=False), params, x, y, key
    )
    flat_m, nest_m = np.asarray(flat_m), np.asarray(nest_m)
    # Discrete callback decisions must be IDENTICAL: lr_scale ladder and
    # stopped flags per epoch (columns 2, 3).
    np.testing.assert_array_equal(flat_m[:, 2], nest_m[:, 2])
    np.testing.assert_array_equal(flat_m[:, 3], nest_m[:, 3])
    # Continuous metrics and the shipped weights agree to float tolerance
    # (two XLA programs of the same math may fuse differently).
    np.testing.assert_allclose(flat_m[:, :2], nest_m[:, :2], atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(flat_p),
                    jax.tree_util.tree_leaves(nest_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_accum_steps_equals_larger_batch():
    # accum_steps=k at batch b must be the IDENTICAL computation to
    # accum_steps=1 at batch k*b: same fused-batch geometry, same shuffle
    # stream, one optimizer step per fused batch.
    model, params, x, y = _fixture()
    key = jax.random.key(9)
    base = dataclasses.replace(_SEM_CFG, epochs=3)
    p_accum, m_accum = local_train(
        model, dataclasses.replace(base, batch_size=8, accum_steps=2),
        params, x, y, key,
    )
    p_big, m_big = local_train(
        model, dataclasses.replace(base, batch_size=16, accum_steps=1),
        params, x, y, key,
    )
    np.testing.assert_allclose(
        np.asarray(m_accum), np.asarray(m_big), atol=1e-6
    )
    for a, b in zip(jax.tree_util.tree_leaves(p_accum),
                    jax.tree_util.tree_leaves(p_big)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_accum_steps_clamps_on_tiny_clients():
    # A client too small for the requested accumulation still takes at
    # least one optimizer step per epoch (accum clamps, never starves).
    from hefl_tpu.fl.client import _train_split

    sp = _train_split(
        dataclasses.replace(_SEM_CFG, batch_size=16, accum_steps=8),
        jnp.zeros((24, 4, 4, 1), jnp.uint8), jnp.zeros((24,), jnp.int32),
    )
    assert sp.steps >= 1 and sp.grp <= sp.n_tr


# ------------------------------------------------- cross-client fusion parity


def _block_fixture(num_clients=4, per_client=40, seed=3):
    (x, y), _, _ = make_dataset(
        "mnist", seed=seed, n_train=num_clients * per_client, n_test=16
    )
    from hefl_tpu.data import iid_contiguous, stack_federated

    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    keys = jax.random.split(jax.random.key(7), num_clients)
    return model, params, jnp.asarray(xs), jnp.asarray(ys), keys


# Patience tight enough that the fixture exercises plateau + early stop, the
# per-client semantics the fused GEMM-stream backend must preserve.
_FUSE_CFG = TrainConfig(
    epochs=4, batch_size=8, num_classes=10, augment=True,
    aug_backend="gather", val_fraction=0.25, es_patience=2,
    plateau_patience=1,
)


def test_fused_train_matches_vmap_reference():
    # The ISSUE-3 golden equivalence: the fused backend (client axis folded
    # into every conv/dense GEMM, fl.fusion) against the vmap reference —
    # identical RNG streams, identical callback DECISIONS (lr ladder,
    # stopped flags), float-tolerance weights/metrics (two XLA programs of
    # the same math), per-client early stopping included.
    from hefl_tpu.fl.fedavg import vmapped_train
    from hefl_tpu.fl.fusion import fused_train

    model, params, xs, ys, keys = _block_fixture()
    pv, mv = jax.jit(
        lambda p: vmapped_train(model, _FUSE_CFG, p, xs, ys, keys)
    )(params)
    pf, mf = jax.jit(
        lambda p: fused_train(model, _FUSE_CFG, p, xs, ys, keys)
    )(params)
    mv, mf = np.asarray(mv), np.asarray(mf)
    assert bool(mv[:, :, 3].any()), "fixture must exercise early stopping"
    np.testing.assert_array_equal(mv[:, :, 2], mf[:, :, 2])  # lr ladder
    np.testing.assert_array_equal(mv[:, :, 3], mf[:, :, 3])  # stopped
    np.testing.assert_allclose(mv[:, :, :2], mf[:, :, :2], atol=2e-2)
    for a, b in zip(jax.tree_util.tree_leaves(pv),
                    jax.tree_util.tree_leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2)


def test_fused_accum_steps_matches_vmap():
    # accum_steps>1 changes the fused-batch geometry (grp = bs*accum); the
    # fused backend must keep the identical geometry AND the identical
    # accum==larger-batch math the vmap path has.
    from hefl_tpu.fl.fedavg import vmapped_train
    from hefl_tpu.fl.fusion import fused_train

    model, params, xs, ys, keys = _block_fixture()
    cfg = dataclasses.replace(
        _FUSE_CFG, epochs=3, augment=False, batch_size=4, accum_steps=2
    )
    pv, mv = jax.jit(lambda p: vmapped_train(model, cfg, p, xs, ys, keys))(params)
    pf, mf = jax.jit(lambda p: fused_train(model, cfg, p, xs, ys, keys))(params)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mf), atol=2e-2)
    for a, b in zip(jax.tree_util.tree_leaves(pv),
                    jax.tree_util.tree_leaves(pf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-2)


def test_fused_train_flops_match_vmap():
    # Acceptance: same math, fewer dispatches — cost_analysis() of the
    # fused program stays within counting noise of the vmap reference (the
    # kernel-offset decomposition counts its f32 partial-sum adds, ~7%; a
    # recompute blowup would be 2-3x).
    from hefl_tpu.fl.fedavg import vmapped_train
    from hefl_tpu.fl.fusion import fused_train

    model, params, xs, ys, keys = _block_fixture()
    cfg = dataclasses.replace(_FUSE_CFG, epochs=2, augment=False)
    fv = roofline.program_flops(
        lambda p: vmapped_train(model, cfg, p, xs, ys, keys), params
    )
    ff = roofline.program_flops(
        lambda p: fused_train(model, cfg, p, xs, ys, keys), params
    )
    if fv is None or ff is None:
        pytest.skip("backend offers no cost_analysis")
    ratio = ff / fv
    assert 0.9 < ratio < 1.15, (
        f"fused program FLOPs {ff:.3g} vs vmap {fv:.3g} (ratio {ratio:.3f})"
    )


# ----------------------------------------------------------- FLOP regression


def test_train_round_flops_within_analytic_envelope():
    # XLA's cost analysis counts a while-loop (lax.scan) body ONCE, so the
    # whole E-epoch program's counted FLOPs must sit within a small
    # multiple of ONE optimizer step's analytic fwd+bwd cost (bwd ~= 2x
    # fwd, plus the boundary validation eval). A recompute blowup in the
    # flattened scan — a re-materialized forward, an accidentally unrolled
    # epoch loop (x steps*epochs), a duplicated grad — bursts the ceiling;
    # deterministic on CPU, no timing.
    model, params, x, y = _fixture()
    cfg = dataclasses.replace(_SEM_CFG, epochs=2)
    fwd = roofline.program_flops(
        lambda p, xb: model.apply({"params": p}, xb),
        params,
        jnp.zeros((16, 28, 28, 1), jnp.float32),
    )
    total = roofline.program_flops(
        lambda p, xv, yv, k: local_train(model, cfg, p, xv, yv, k),
        params, x, y, jax.random.key(0),
    )
    if fwd is None or total is None:
        pytest.skip("backend offers no cost_analysis")
    step_analytic = 3.0 * fwd
    ratio = total / step_analytic
    # measured ~1.5 (step core + the lax.cond validation branch + epoch-key
    # derivation, each counted once); a duplicated forward or an unrolled
    # scan (x8 at this geometry) clears 3.0 by a wide margin.
    assert 0.8 < ratio < 3.0, (
        f"train program FLOPs {total:.3g} vs one-step analytic "
        f"{step_analytic:.3g} (ratio {ratio:.2f})"
    )


def test_roofline_schema_and_clamp():
    rec = roofline.phase_stats(
        2.0, flops=8e14, device="TPU v5 lite", images=100
    )
    assert set(rec) >= {"seconds", "flops", "mfu", "images_per_s"}
    # 8e14/2.0 over the v5e peak is an impossible ~2.03 utilization:
    # clamped to 1.0 with the raw value kept and the timing-floor flag set
    # (ISSUE 5 — no artifact ships utilization > 1 unflagged).
    assert rec["mfu"] == 1.0
    assert rec["mfu_raw"] == pytest.approx(8e14 / 2.0 / 197e12, rel=1e-4)
    assert rec["timing_floor_suspect"] is True
    assert rec["images_per_s"] == 50.0
    # A CPU has no peak: its utilization is null, never a placeholder.
    cpu = roofline.phase_stats(2.0, flops=4e11, device="cpu", images=100)
    assert cpu["mfu"] is None and cpu["images_per_s"] == 50.0
    assert roofline.peak_flops("cpu") is None
    # An accelerator that is not in the table is an error, not a guess.
    with pytest.raises(ValueError, match="not in roofline"):
        roofline.peak_flops("Some Future Chip")
    # null-safe: fields PRESENT but null when not computable
    empty = roofline.phase_stats(None)
    assert empty["mfu"] is None and empty["seconds"] is None
    clamped, bad = roofline.clamp_attribution({"a": 1.5, "b": -0.2})
    assert clamped == {"a": 1.5, "b": 0.0} and bad is True
    clamped, bad = roofline.clamp_attribution({"a": 0.3})
    assert bad is False
    assert roofline.peak_flops("TPU v5 lite") == 197e12
