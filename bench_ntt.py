"""Microbenchmark: Pallas fused HE kernels vs the stage-unrolled XLA path.

The Pallas kernels (`hefl_tpu/ckks/pallas_ntt.py`) exist to beat the XLA
graph path on TPU — the claim SURVEY.md §2.12 assigns them (the SEAL-C++-NTT
role). This harness measures both backends on identical inputs at the shapes
the framework actually runs:

  * [55, 3, 4096]  — the flagship encrypt/decrypt batch (55 ciphertexts of
    the 222,722-param MedCNN, 3 RNS limbs)
  * [2, 3, 4096]   — keygen-sized (pk has two polynomials)
  * [18, 3, 4096]  — key-switch gadget sized (ksk digits x limbs)

Per shape it times the bare forward/inverse NTT under each backend AND the
fused encrypt/decrypt cores (ISSUE 4: whole-encrypt — 4 NTTs + pointwise
pk combination — as one Mosaic dispatch vs the XLA graph), and asserts
bit-exact parity between the two backends for every op on hardware (the
CPU test suite only ever runs the kernels interpreted).

The keyswitch stage (ISSUE 13) runs at the [18, 3, 4096] gadget shape the
suite has carried since PR 4 precisely to measure this: the whole gadget
key-switch (digit decompose -> per-component forward NTT -> digit x key
Montgomery inner product) as `ops._keyswitch_coeff_xla` vs the fused
`pallas_ntt.keyswitch_fused_pallas` dispatch, bitwise-parity-gated under
the same exit-42 contract as every other stage.

Usage: python bench_ntt.py            (writes a row table to stdout)
"""

from __future__ import annotations

import sys
import time

import numpy as np


def _time(fn, a, reps: int = 50) -> float:
    """Per-op device time via a DEVICE-SIDE rep loop.

    A host-side rep loop measures dispatch as much as compute (big
    shapes' dispatches pipeline, small ones drain per call). Chaining reps
    with lax.fori_loop keeps the whole measurement on-device: each iteration
    feeds its output to the next (mod-p arithmetic is closed, so values
    stay in range and shapes/dtypes are fixed points of both transforms),
    so XLA can neither elide nor overlap iterations, and one dispatch
    amortizes over all reps.
    """
    import jax
    from jax import lax

    @jax.jit
    def loop(x):
        return lax.fori_loop(0, reps, lambda i, v: fn(v), x)

    jax.block_until_ready(loop(a))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(loop(a))
    return (time.perf_counter() - t0) / reps


def main() -> None:
    import os

    import jax

    from hefl_tpu.utils.device import select_platform, setup_compile_cache

    select_platform("bench_ntt.py", cpu=os.environ.get("NTT_SMOKE") == "1")
    import jax.numpy as jnp

    setup_compile_cache()

    from hefl_tpu.ckks import ntt as ntt_mod
    from hefl_tpu.ckks import pallas_ntt
    from hefl_tpu.ckks.keys import CkksContext

    on_tpu = ntt_mod.on_tpu_backend()
    dev = jax.devices()[0]
    print(
        f"device: {getattr(dev, 'device_kind', dev)} "
        f"(backend={jax.default_backend()}, pallas "
        f"{'compiled' if on_tpu else 'interpreted'})",
        file=sys.stderr,
    )

    ctx = CkksContext.create()  # N=4096, L=3 — the flagship parameters
    nttc = ctx.ntt

    # Force each backend via the module selector (read per call).
    def xla_fwd(a):
        return ntt_mod.ntt_forward(ctx.ntt, a)

    def xla_inv(a):
        return ntt_mod.ntt_inverse(ctx.ntt, a)

    from hefl_tpu.ckks import ops as ops_mod
    from hefl_tpu.ckks.modular import add_mod, mont_mul

    prev = ntt_mod._BACKEND
    rows = []
    ks_rows = []
    # [14, 3, 4096] is the PACKED flagship-bench batch (ISSUE 6): the
    # 2-client flagship's 55 ciphertexts bit-interleaved 4-to-a-slot ->
    # ceil(55/4) = 14 rows. (k is client-count-dependent: the 8-client
    # presets' carry-free headroom resolves to k=3 -> 19 rows; 14 is the
    # bench.py configuration's shape.)
    shapes = [(55, 3, 4096), (18, 3, 4096), (14, 3, 4096), (2, 3, 4096)]
    if os.environ.get("NTT_SMOKE") == "1":   # harness shakeout on CPU
        shapes = [(2, 3, 4096)]
    rng = np.random.default_rng(0)

    def rand_res(shape):
        return jnp.asarray(
            rng.integers(
                0, np.asarray(nttc.p)[:, 0][None, :, None], size=shape
            ).astype(np.uint32)
        )

    def dec_ref(c0, c1, s):
        p = jnp.asarray(nttc.p)
        pinv = jnp.asarray(nttc.pinv_neg)
        d = add_mod(c0, mont_mul(c1, s, p, pinv), p)
        return ntt_mod.ntt_inverse(nttc, d)

    try:
        for shape in shapes:
            a = rand_res(shape)
            ntt_mod._BACKEND = "xla"
            fwd_x = jax.jit(xla_fwd)
            inv_x = jax.jit(xla_inv)
            t_fx = _time(fwd_x, a)
            ev = fwd_x(a)
            t_ix = _time(inv_x, ev)

            pl_fwd = jax.jit(lambda v: pallas_ntt.ntt_forward_pallas(nttc, v))
            pl_inv = jax.jit(lambda v: pallas_ntt.ntt_inverse_pallas(nttc, v))
            pl_reps = 50 if on_tpu else 1  # interpreted-mode pallas is slow
            t_fp = _time(pl_fwd, a, reps=pl_reps)
            ev_p = pl_fwd(a)
            t_ip = _time(pl_inv, ev, reps=pl_reps)

            # Fused encrypt/decrypt cores (ISSUE 4): same deterministic
            # inputs through the XLA reference and the one-dispatch kernel.
            # Random eval/Montgomery-domain key stand-ins are fine — parity
            # and throughput do not care that they decrypt to noise.
            u, e0, e1 = rand_res(shape), rand_res(shape), rand_res(shape)
            bk, ak, s_m = (rand_res(shape[1:]), rand_res(shape[1:]),
                           rand_res(shape[1:]))
            enc_x = jax.jit(lambda m: ops_mod._encrypt_core_xla(
                ctx, m, u, e0, e1, bk, ak)[0])
            enc_p = jax.jit(lambda m: pallas_ntt.encrypt_fused_pallas(
                nttc, m, u, e0, e1, bk, ak)[0])
            t_ex = _time(enc_x, a)
            t_ep = _time(enc_p, a, reps=pl_reps)
            dec_x = jax.jit(lambda c0: dec_ref(c0, ev, s_m))
            dec_p = jax.jit(lambda c0: pallas_ntt.decrypt_fused_pallas(
                nttc, c0, ev, s_m))
            t_dx = _time(dec_x, ev)
            t_dp = _time(dec_p, ev, reps=pl_reps)

            # Bit-exact cross-backend parity (all four ops). A mismatch is
            # a DETERMINISTIC kernel failure: exit 42 so a caller can tell
            # it from an environment failure.
            try:
                np.testing.assert_array_equal(np.asarray(ev), np.asarray(ev_p))
                np.testing.assert_array_equal(
                    np.asarray(inv_x(ev)), np.asarray(pl_inv(ev))
                )
                np.testing.assert_array_equal(
                    np.asarray(enc_x(a)), np.asarray(enc_p(a))
                )
                np.testing.assert_array_equal(
                    np.asarray(dec_x(ev)), np.asarray(dec_p(ev))
                )
            except AssertionError as e:
                print(f"PARITY MISMATCH at {shape}: {e}", file=sys.stderr)
                sys.exit(42)
            rows.append(
                (shape, t_fx * 1e3, t_fp * 1e3, t_fx / t_fp,
                 t_ix * 1e3, t_ip * 1e3, t_ix / t_ip,
                 t_ex * 1e3, t_ep * 1e3, t_ex / t_ep,
                 t_dx * 1e3, t_dp * 1e3, t_dx / t_dp)
            )

            # Keyswitch stage (ISSUE 13): the fused gadget key-switch vs
            # the XLA reference, at the gadget shape this bench has
            # carried since PR 4 (and at the smoke shape on CPU). Same
            # exit-42 parity contract: a c0/c1 mismatch is a
            # deterministic kernel failure.
            if shape[0] == 18 or os.environ.get("NTT_SMOKE") == "1":
                num_c = ctx.num_primes * ctx.ksk_num_digits + 1
                ks_b = rand_res((num_c,) + shape[1:])
                ks_a = rand_res((num_c,) + shape[1:])
                ks_x = jax.jit(lambda c: ops_mod._keyswitch_coeff_xla(
                    ctx, c, ks_b, ks_a)[0])
                ks_p = jax.jit(lambda c: pallas_ntt.keyswitch_fused_pallas(
                    nttc, c, ks_b, ks_a,
                    digit_bits=ctx.ksk_digit_bits,
                    num_digits=ctx.ksk_num_digits)[0])
                t_kx = _time(ks_x, a, reps=5)
                t_kp = _time(ks_p, a, reps=5 if on_tpu else 1)
                try:
                    # ONE jitted evaluation per backend covers both
                    # components of the parity contract (c0 AND c1).
                    full_x = jax.jit(lambda c: ops_mod._keyswitch_coeff_xla(
                        ctx, c, ks_b, ks_a))(a)
                    full_p = jax.jit(
                        lambda c: pallas_ntt.keyswitch_fused_pallas(
                            nttc, c, ks_b, ks_a,
                            digit_bits=ctx.ksk_digit_bits,
                            num_digits=ctx.ksk_num_digits))(a)
                    np.testing.assert_array_equal(
                        np.asarray(full_x[0]), np.asarray(full_p[0])
                    )
                    np.testing.assert_array_equal(
                        np.asarray(full_x[1]), np.asarray(full_p[1])
                    )
                except AssertionError as e:
                    print(f"KEYSWITCH PARITY MISMATCH at {shape}: {e}",
                          file=sys.stderr)
                    sys.exit(42)
                ks_rows.append(
                    (shape, t_kx * 1e3, t_kp * 1e3, t_kx / t_kp)
                )
        # Packed-quantized parity stage (ISSUE 6, exit-42 contract): the
        # bit-interleaved payload must survive the EXACT integer encode ->
        # (both NTT backends') encrypt/decrypt cores -> exact integer
        # decode bit-for-bit. Random 62-bit (hi, lo) pairs at the packed
        # flagship shape; any field corruption is a deterministic kernel/
        # encode failure.
        from hefl_tpu.ckks import encoding, quantize
        from hefl_tpu.ckks.keys import keygen

        n_rows = 2 if os.environ.get("NTT_SMOKE") == "1" else 14
        pshape = (n_rows, ctx.num_primes, ctx.n)
        hi = jnp.asarray(
            rng.integers(0, 1 << 31, size=(n_rows, ctx.n), dtype=np.int64)
            .astype(np.uint32)
        )
        lo = jnp.asarray(
            rng.integers(0, 1 << 31, size=(n_rows, ctx.n), dtype=np.int64)
            .astype(np.uint32)
        )
        m_pk = encoding.encode_packed(nttc, hi, lo)
        v_ref = quantize.packed_value_int64(np.asarray(hi), np.asarray(lo))
        sk_p, pk_p = keygen(ctx, jax.random.key(0))
        u_p, e0_p, e1_p = ops_mod.encrypt_samples(
            ctx, jax.random.key(1), (n_rows,)
        )
        try:
            # (a) exact integer encode/decode round-trip (no HE).
            np.testing.assert_array_equal(
                np.asarray(encoding.decode_int_center(nttc, m_pk)), v_ref
            )
            # (b) the full cipher loop under EACH NTT backend (fresh jit
            # per backend — the module selector is read at trace time):
            # values up to 2**62 must decrypt to within the noise guard of
            # the payload (|error| < 2**15 here, far below the default
            # 2**17 guard).
            for backend in (["xla", "pallas-interpret"] if not on_tpu
                            else ["xla", "pallas"]):
                ntt_mod._BACKEND = backend

                def _loop(m):
                    ct = ops_mod.encrypt_core(
                        ctx, pk_p, m, u_p, e0_p, e1_p
                    )
                    return dec_ref(ct.c0, ct.c1, sk_p.s_mont)

                res_p = jax.jit(_loop)(m_pk)
                v_out = np.asarray(encoding.decode_int_center(nttc, res_p))
                err = np.abs(v_out - v_ref).max()
                if err >= (1 << 15):
                    raise AssertionError(
                        f"packed payload noise {err} under backend "
                        f"{backend} exceeds the guard budget"
                    )
            ntt_mod._BACKEND = prev
            print(
                f"packed parity: encode_packed/decode_int_center exact at "
                f"{list(pshape)}; cipher round-trip noise < 2**15 on every "
                "backend",
                file=sys.stderr,
            )
        except AssertionError as e:
            print(f"PACKED PARITY FAILURE at {pshape}: {e}", file=sys.stderr)
            sys.exit(42)
    finally:
        ntt_mod._BACKEND = prev

    print("| shape [B, L, N] | fwd XLA (ms) | fwd Pallas (ms) | speedup | "
          "inv XLA (ms) | inv Pallas (ms) | speedup | "
          "enc XLA (ms) | enc Pallas (ms) | speedup | "
          "dec XLA (ms) | dec Pallas (ms) | speedup |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|")
    recs = []
    for (shape, fx, fp, sf, ix, ip_, si, ex, ep, se, dx, dp, sd) in rows:
        print(
            f"| {list(shape)} | {fx:.3f} | {fp:.3f} | {sf:.2f}x "
            f"| {ix:.3f} | {ip_:.3f} | {si:.2f}x "
            f"| {ex:.3f} | {ep:.3f} | {se:.2f}x "
            f"| {dx:.3f} | {dp:.3f} | {sd:.2f}x |"
        )
        recs.append(
            {"shape": list(shape), "fwd_xla_ms": round(fx, 3),
             "fwd_pallas_ms": round(fp, 3), "fwd_speedup": round(sf, 2),
             "inv_xla_ms": round(ix, 3), "inv_pallas_ms": round(ip_, 3),
             "inv_speedup": round(si, 2),
             "enc_xla_ms": round(ex, 3), "enc_pallas_ms": round(ep, 3),
             "enc_speedup": round(se, 2),
             "dec_xla_ms": round(dx, 3), "dec_pallas_ms": round(dp, 3),
             "dec_speedup": round(sd, 2)}
        )
    ks_recs = []
    if ks_rows:
        print()
        print("| keyswitch shape [B, L, N] | XLA (ms) | Pallas (ms) | "
              "speedup |")
        print("|---|---|---|---|")
        for (shape, kx, kp, sk_) in ks_rows:
            print(f"| {list(shape)} | {kx:.3f} | {kp:.3f} | {sk_:.2f}x |")
            ks_recs.append(
                {"shape": list(shape), "keyswitch_xla_ms": round(kx, 3),
                 "keyswitch_pallas_ms": round(kp, 3),
                 "keyswitch_speedup": round(sk_, 2)}
            )
    import json

    with open("ntt_bench.json", "w") as f:
        json.dump(
            {"device": getattr(dev, "device_kind", str(dev)),
             "backend": jax.default_backend(),
             "pallas_mode": "compiled" if on_tpu else "interpreted",
             "parity": "bit-exact fwd+inv+enc+dec at all shapes"
                       " + fused keyswitch (c0 AND c1) at the gadget shape",
             "timing_method": "device-side fori_loop rep chain "
                              "(one dispatch amortized over all reps)",
             "rows": recs,
             "keyswitch_rows": ks_recs},
            f, indent=2,
        )
    print("parity: bit-exact fwd/inv/fused-enc/fused-dec across backends "
          "at all shapes + fused keyswitch at the gadget shape; rows "
          "saved to ntt_bench.json",
          file=sys.stderr)


if __name__ == "__main__":
    main()
