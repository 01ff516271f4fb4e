"""HE backend selection: fused Pallas kernels vs the XLA graph reference.

Mirrors the augment / client-fusion selection machinery (data.augment,
fl.fusion): an env pin (`HEFL_HE=xla|pallas|auto`), a one-shot micro-timing
in "auto" mode on TPU, and per-device-kind persistence next to the XLA
compile cache (utils.autoselect) so short-lived CLI runs skip the probe.
`he_backend_report()` exposes the resolved choice for bench/profile
artifacts — recorded alongside `augment_backend` / `client_fusion`.

The XLA path is the bit-exact semantics reference; the fused Pallas path
(`pallas_ntt.encrypt_fused_pallas` / `decrypt_fused_pallas`) produces
identical canonical residues (parity-tested interpreted on CPU, and on
hardware by `bench_ntt.py`'s stage-1 gate), so selection is purely a speed
decision:

  * off-TPU, "auto" resolves to "xla" without probing — interpreted Pallas
    is a test vehicle, never a fast path;
  * on TPU, "auto" micro-times one fused encrypt (flagship row shape) AND
    one fused key-switch (gadget geometry) per backend and persists the
    combined winner per device kind, with both component timings recorded;
  * rings too small for the (>=8, 128) tile always take the XLA path,
    whatever the pin (the kernels cannot tile them).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

HE_BACKENDS = ("xla", "pallas")

_ENV = os.environ.get("HEFL_HE", "auto")

# One-shot auto-selection state (process-global, same shape as
# data.augment's): winner per device kind + what the last resolution
# actually returned, so he_backend_report() describes traced programs.
_AUTO_CHOICE: dict[str, str] = {}
_AUTO_TIMINGS_MS: dict[str, float] | None = None
_AUTO_PERSISTED: bool = False
_LAST_RESOLVED: str | None = None


def _probe_shapes(ctx) -> tuple:
    """Flagship-row probe batch: enough rows to amortize dispatch."""
    return (8, ctx.num_primes, ctx.n)


def _autoselect(ctx) -> str:
    """Micro-time one fused encrypt, one fused key-switch AND one hoisted
    product sweep per backend on the live TPU; persist the combined winner.

    The key-switch probe (ISSUE 13) runs at the gadget geometry the
    serving path and relinearization actually dispatch ([L*d+1, L, N] key
    tensors); the hoisted probe (ISSUE 18) at the BSGS baby sweep's
    [S, L*d, L, N] pre-permuted key geometry. The persisted record keeps
    every component ({name}_encrypt / {name}_keyswitch / {name}_hoisted)
    so the bench artifacts can show WHY a backend won, not just which.
    """
    global _AUTO_TIMINGS_MS, _AUTO_PERSISTED
    kind = str(getattr(jax.devices()[0], "device_kind", "unknown"))
    if kind in _AUTO_CHOICE:
        return _AUTO_CHOICE[kind]
    from hefl_tpu.utils.autoselect import load_winner, store_winner

    hit = load_winner("he_backend", kind, allowed=HE_BACKENDS)
    if hit is not None:
        _AUTO_CHOICE[kind] = hit["winner"]
        _AUTO_TIMINGS_MS = hit.get("timings_ms")
        _AUTO_PERSISTED = True
        return hit["winner"]
    from hefl_tpu.ckks import ops, pallas_ntt
    from hefl_tpu.utils.roofline import steady_seconds

    with jax.ensure_compile_time_eval():
        # Probe INPUTS built inside the eval context (concrete even when an
        # outer jit is tracing — see augment._autoselect_backend). The
        # candidates themselves are compiled OUTSIDE it, below.
        b, num_l, n = _probe_shapes(ctx)
        rng = np.random.default_rng(0)
        p_col = np.asarray(ctx.ntt.p)[:, 0]
        mk = lambda *shape: jnp.asarray(  # noqa: E731
            (rng.integers(0, 2**31, size=shape, dtype=np.int64)
             % p_col[(None,) * (len(shape) - 2) + (slice(None), None)])
            .astype(np.uint32)
        )
        m, u, e0, e1 = (mk(b, num_l, n) for _ in range(4))
        bk = mk(num_l, n)
        ak = mk(num_l, n)
        num_c = num_l * ctx.ksk_num_digits + 1
        ks_b = mk(num_c, num_l, n)
        ks_a = mk(num_c, num_l, n)
        coeff = mk(b, num_l, n)
        # Hoisted-rotation probe (ISSUE 18): the batched digit x key
        # product sweep the BSGS serving path dispatches per query — a
        # small step count suffices, the kernel's per-step work is what
        # differs between backends.
        num_r = num_l * ctx.ksk_num_digits
        num_s = 4
        h_d = mk(num_r, num_l, n)
        h_b = mk(num_s, num_r, num_l, n)
        h_a = mk(num_s, num_r, num_l, n)
        single = mk(num_l, n)
    # BOTH candidates jitted: production encrypt runs inside jitted
    # round programs, so an eager per-primitive XLA op chain would time
    # dispatch overhead (~100 dispatches for the 4 stage-unrolled NTTs)
    # against the kernel's single dispatch and bias the probe. Each is
    # compiled ahead of time, outside the eval context: a Pallas kernel
    # cannot be traced under it (its grid primitives have no eager
    # evaluation rule), and a compiled executable runs concretely on
    # concrete inputs whatever outer trace is active.
    def timed(fn, arg) -> float:
        return steady_seconds(jax.jit(fn).lower(arg).compile(), arg)

    cands = {
        "xla": lambda mm: ops._encrypt_core_xla(
            ctx, mm, u, e0, e1, bk, ak)[0],
        "pallas": lambda mm: pallas_ntt.encrypt_fused_pallas(
            ctx.ntt, mm, u, e0, e1, bk, ak)[0],
    }
    ks_cands = {
        "xla": lambda cc: ops._keyswitch_coeff_xla(
            ctx, cc, ks_b, ks_a)[0],
        "pallas": lambda cc: pallas_ntt.keyswitch_fused_pallas(
            ctx.ntt, cc, ks_b, ks_a,
            digit_bits=ctx.ksk_digit_bits,
            num_digits=ctx.ksk_num_digits)[0],
    }
    hoist_cands = {
        "xla": lambda cc: ops._hoisted_products_xla(
            ctx, cc, h_d, h_b, h_a)[0],
        "pallas": lambda cc: pallas_ntt.hoisted_rotations_pallas(
            ctx.ntt, cc, h_d, h_b, h_a)[0],
    }
    timings = {name: timed(fn, m) for name, fn in cands.items()}
    ks_timings = {
        name: timed(fn, coeff) for name, fn in ks_cands.items()
    }
    hoist_timings = {
        name: timed(fn, single) for name, fn in hoist_cands.items()
    }
    _AUTO_TIMINGS_MS = {}
    for name in HE_BACKENDS:
        _AUTO_TIMINGS_MS[name] = round(
            (timings[name] + ks_timings[name] + hoist_timings[name]) * 1e3, 3
        )
        _AUTO_TIMINGS_MS[f"{name}_encrypt"] = round(timings[name] * 1e3, 3)
        _AUTO_TIMINGS_MS[f"{name}_keyswitch"] = round(
            ks_timings[name] * 1e3, 3
        )
        _AUTO_TIMINGS_MS[f"{name}_hoisted"] = round(
            hoist_timings[name] * 1e3, 3
        )
    winner = min(HE_BACKENDS, key=lambda name: _AUTO_TIMINGS_MS[name])
    _AUTO_CHOICE[kind] = winner
    store_winner("he_backend", kind, winner, _AUTO_TIMINGS_MS)
    return winner


def resolve_he_backend(ctx, override: str | None = None) -> str:
    """The backend encrypt/decrypt will actually run for this context.

    Priority: explicit `override` > HEFL_HE env > "auto". Small rings (the
    CPU test rings) always resolve to "xla" — the kernels cannot tile them.
    """
    global _LAST_RESOLVED
    from hefl_tpu.ckks import pallas_ntt
    from hefl_tpu.ckks.ntt import on_tpu_backend

    requested = override or _ENV or "auto"
    if requested not in HE_BACKENDS + ("auto",):
        raise ValueError(
            f"HE backend {requested!r}: expected one of {HE_BACKENDS + ('auto',)}"
        )
    if not pallas_ntt.supported(ctx.ntt):
        backend = "xla"
    elif requested == "auto":
        backend = _autoselect(ctx) if on_tpu_backend() else "xla"
    else:
        backend = requested
    _LAST_RESOLVED = backend
    return backend


def he_backend_report() -> dict:
    """What the HE layer is running — for bench/profile artifacts."""
    env = _ENV or "auto"
    resolved = _LAST_RESOLVED or (env if env in HE_BACKENDS else None)
    return {
        "requested": env,
        "backend": resolved,
        "auto_timings_ms": _AUTO_TIMINGS_MS,
        "auto_persisted": _AUTO_PERSISTED,
    }
