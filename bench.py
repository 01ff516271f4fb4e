"""Benchmark: the reference's headline experiment, end-to-end on TPU.

Reference configuration (BASELINE.md; captured from the notebook's cell-3
outputs): 2 clients, 1 FL round, 10 local epochs, 1600 train / 400 test
images at 256x256x3, the 222,722-param CNN, HE-encrypted FedAvg — total
pipeline wall-clock **6583.6 s** on its CPU (train + encrypt + export +
aggregate + decrypt + evaluate).

What this harness measures (BASELINE.json's north star is FL
rounds/sec/chip, so cold and warm are reported separately):

  * round 0  — the reference-equivalent pipeline, COLD: one full encrypted
    round (2-client 10-epoch training + CKKS encrypt + homomorphic
    aggregation) + owner decrypt + test-set evaluation, including every
    one-time cost this process pays (XLA compile or persistent-cache load).
    This is `value` / `vs_baseline` in the JSON line.
  * rounds 1..R-1 — the same program WARM (compiled program reuse).
    `warm_round_s` is their mean; `rounds_per_sec_per_chip` = 1 /
    warm_round_s on this single chip. `train_mfu` is the analytic CNN
    fwd+bwd FLOPs over the warm train-phase time vs the chip's bf16 peak.
  * cell-6 comparison artifact (`Encrypted FL Main-Rel.ipynb:428`): a real
    plaintext FedAvg round is timed (`plaintext_round_s`), and the
    production encrypted round is re-run in `with_plain_reference` mode so
    the IDENTICAL in-program trained weights flow through both aggregators
    — plain pmean vs encrypt/hierarchical-psum/decrypt. That makes
    `enc_plain_max_abs_diff` pure CKKS encode/encrypt/aggregate/decrypt
    error by construction, measured THROUGH the production collective;
    `ciphertext_expansion` is wire bytes of the aggregated ciphertexts over
    float32 weight bytes.

A persistent XLA compilation cache is enabled (standard TPU production
practice); `compile_cache` in the JSON records whether round 0 found it
warm, so the cold number is never silently conflated across runs.

Output: ONE JSON line on stdout; phase breakdown on stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


# Peak-FLOPs lookup, cost_analysis plumbing, and the per-phase
# {seconds, flops, mfu, images_per_s} records all come from
# hefl_tpu.utils.roofline — the single source every measurement driver
# shares (mfu_probe.py, profile_round.py, experiment.py).
from hefl_tpu.utils import roofline


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    platform = None if smoke else os.environ.get("BENCH_PLATFORM")
    # BENCH_SMOKE: harness shakeout on CPU (same code path, tiny shapes).
    # BENCH_PLATFORM=cpu: FULL flagship shapes pinned to CPU — accuracy,
    # fidelity, and encode-overflow evidence is device-independent; timing
    # fields carry the pinned device name — never quote them as TPU
    # numbers. Otherwise the run requires a TPU and fails without one.
    from hefl_tpu.utils.device import select_platform, setup_compile_cache

    select_platform("bench.py", cpu=smoke or platform == "cpu")
    import jax.numpy as jnp

    cache_dir = setup_compile_cache()
    cache_warm = os.path.isdir(cache_dir) and len(os.listdir(cache_dir)) > 0

    # Observability (obs.metrics): count new XLA executables + memory peaks
    # for the whole run; the snapshot ships in the JSON artifact.
    from hefl_tpu.obs import metrics as obs_metrics

    obs_metrics.install_jax_listeners()

    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, stack_federated
    from hefl_tpu.data.augment import backend_report as augment_backend_report
    from hefl_tpu.fl import (
        decrypt_average,
        evaluate,
        fedavg_round,
        secure_fedavg_round,
    )
    from hefl_tpu.fl.fusion import fusion_report
    from hefl_tpu.flagship import (
        BASELINE_ACC,
        BASELINE_TOTAL_S,
        flagship_keygen_key,
        flagship_round_key,
        flagship_setup,
    )
    from hefl_tpu.models import count_params
    from hefl_tpu.parallel import make_mesh

    num_clients = 2
    # >= 5 rounds so "steady" is a min over >= 3 genuinely-warm samples
    # (round 1 still carries one-time trickle costs).
    rounds = max(1, int(os.environ.get("BENCH_ROUNDS", "2" if smoke else "5")))
    seed = int(os.environ.get("BENCH_SEED", "0"))
    dev = jax.devices()[0]
    log(f"devices: {jax.devices()} (cache_warm={cache_warm})")

    # --- data + model + HE context: single-sourced flagship configuration
    # (hefl_tpu.flagship — shared with flagship_acc.py so the timed config
    # and the accuracy-evidence config cannot drift apart). Data is not
    # timed: the reference reads pre-existing files on disk. ---
    setup = flagship_setup(seed, smoke=smoke)
    module, params, cfg, ctx = (
        setup["module"], setup["params"], setup["cfg"], setup["ctx"],
    )
    (x, y), (xt, yt) = setup["train"], setup["test"]
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    log(f"data: train {x.shape} -> {xs.shape} federated, test {xt.shape}")
    mesh = make_mesh(num_clients)
    sk, pk = keygen(ctx, flagship_keygen_key())
    pack = PackSpec.for_params(params, ctx.n)
    log(f"CKKS: N={ctx.n}, L={ctx.num_primes}, n_ct={pack.n_ct}")

    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)

    # Analytic train FLOPs for the MFU estimate: fwd cost of one fused
    # batch x 3 (fwd + bwd ~= 3x fwd) x steps/epoch x epochs x clients.
    # Batch geometry comes from the same helper _train_split uses, so the
    # numerator cannot drift from what training actually runs.
    from hefl_tpu.fl.client import train_batch_geometry

    _, grp, steps_per_epoch = train_batch_geometry(cfg, int(xs.shape[1]))
    fwd_flops = roofline.program_flops(
        lambda p, xb: module.apply({"params": p}, xb),
        params,
        jnp.zeros((grp, *x.shape[1:]), jnp.float32),
    )
    if fwd_flops is None:
        log("cost_analysis unavailable; MFU columns will be null")
    train_flops = roofline.train_flops_per_round(
        fwd_flops, steps_per_epoch, cfg.epochs, num_clients
    )
    train_images_per_round = num_clients * cfg.epochs * steps_per_epoch * grp

    round_stats = []
    history = []
    xt_d = None
    overflow_total = 0
    # Per-round exclusion record (ISSUE 2). The timed hot path runs the
    # clean all-clients-present program, so each row is the compact
    # {excluded, overflow_clients} summary (NOT the richer per-cause
    # RoundMeta.record() dict experiment history carries): `excluded` is
    # structurally 0 here, and `overflow_clients` says how many clients
    # on_overflow="exclude" WOULD have dropped that round.
    exclusions_by_round = []
    cur = params
    for r in range(rounds):
        k_round = flagship_round_key(seed, r)
        t0 = time.perf_counter()
        ct_sum, metrics, overflow = secure_fedavg_round(
            module, cfg, mesh, ctx, pk, cur, xs_d, ys_d, k_round
        )
        if xt_d is None:
            # Prefetch the test set while training runs: dispatch is async,
            # so the host->device copy rides out the train wall-clock.
            xt_d = jax.device_put(jnp.asarray(xt))
        jax.block_until_ready((ct_sum.c0, ct_sum.c1, metrics))
        t1 = time.perf_counter()
        new_params = decrypt_average(ctx, sk, ct_sum, num_clients, pack)
        jax.block_until_ready(new_params)
        t2 = time.perf_counter()
        results = evaluate(module, new_params, xt_d, yt)
        t3 = time.perf_counter()
        round_stats.append(
            {"train": t1 - t0, "decrypt": t2 - t1, "evaluate": t3 - t2,
             "total": t3 - t0}
        )
        history.append({k: float(results[k]) for k in ("accuracy", "f1")})
        log(
            f"round {r}: train+encrypt+aggregate {t1 - t0:.2f}s | "
            f"decrypt {t2 - t1:.2f}s | evaluate {t3 - t2:.2f}s | "
            f"total {t3 - t0:.2f}s | acc {results['accuracy']:.4f} "
            f"f1 {results['f1']:.4f}"
        )
        ov = int(np.sum(np.asarray(overflow)))
        overflow_total += ov
        exclusions_by_round.append(
            {"excluded": 0,
             "overflow_clients": int(np.sum(np.asarray(overflow) > 0))}
        )
        log(f"  per-client val-acc: {np.asarray(metrics)[:, :, 1].round(3)}"
            + (f" | ENCODE OVERFLOW: {ov} weights clipped" if ov else ""))
        last_ct_sum, last_start, last_key = ct_sum, cur, k_round
        cur = new_params
        # Rolling partial artifact (atomic): a timeout after round r must
        # not cost the whole run's evidence.
        partial = {
            "partial": True,
            "seed": seed,
            "device": getattr(dev, "device_kind", str(dev)),
            "rounds_completed": r + 1,
            "rounds_planned": rounds,
            "accuracy_by_round": [h["accuracy"] for h in history],
            "f1_by_round": [h["f1"] for h in history],
            "round_stats": round_stats,
            "exclusions_by_round": exclusions_by_round,
            "encode_overflow_count": overflow_total,
            **({"smoke": True} if smoke else {}),
            **({"platform_pinned": platform} if platform else {}),
        }
        # Namespaced by platform pin: a CPU-pinned evidence run and a TPU
        # run of the same seed must not clobber each other's file.
        ptag = "smoke" if smoke else (platform or "hw")
        with open(f"bench_partial_{ptag}_{seed}.json.tmp", "w") as f:
            json.dump(partial, f)
        os.replace(
            f"bench_partial_{ptag}_{seed}.json.tmp",
            f"bench_partial_{ptag}_{seed}.json",
        )

    # --- cell-6 comparison artifact ---------------------------------------
    # BENCH_SKIP_CELL6=1 skips the whole diagnostic tail (3 extra
    # round-equivalents of compute: plaintext warmup + timed plaintext
    # round + the with_plain_reference round). Meant for accuracy-evidence
    # runs on slow backends (BENCH_PLATFORM=cpu) where the tail would
    # multiply a multi-hour run; the JSON then carries nulls for the
    # cell-6 fields rather than numbers from a config that never ran.
    skip_cell6 = os.environ.get("BENCH_SKIP_CELL6") == "1"
    plaintext_round_s = max_diff = max_diff_exact = cell6_overflow = None
    fusion_seconds = {}
    ct_bytes = (last_ct_sum.c0.size + last_ct_sum.c1.size) * 4
    param_bytes = count_params(params) * 4
    expansion = ct_bytes / param_bytes
    if skip_cell6:
        log("cell-6 artifact skipped (BENCH_SKIP_CELL6=1)")
    else:
        # (a) plaintext_round_s: one REAL plaintext FedAvg round (train +
        # pmean), the cost denominator for "what does encryption add per
        # round".
        k_train, _ = jax.random.split(last_key)
        # Warm-up (untimed): the plaintext program has never run in this
        # process, and a cold timing would fold its XLA compile into the
        # "what does encryption add per round" denominator, which is
        # compared against WARM encrypted rounds.
        jax.block_until_ready(
            fedavg_round(module, cfg, mesh, last_start, xs_d, ys_d, k_train)[0]
        )
        tp0 = time.perf_counter()
        plain_params, _ = fedavg_round(
            module, cfg, mesh, last_start, xs_d, ys_d, k_train
        )
        jax.block_until_ready(plain_params)
        plaintext_round_s = time.perf_counter() - tp0
        # Fused-vs-vmap comparison rows (ISSUE 3): the same plaintext
        # round timed warm under each cross-client backend pinned, so the
        # artifact records both backends' MFU at identical math. Each
        # pinned variant is its own compiled program (diagnostic tail,
        # like with_plain_reference — not part of any timed round above).
        import dataclasses as _dc

        from hefl_tpu.fl.fusion import supports_fusion

        for bk_name in ("vmap", "fused"):
            if bk_name == "fused" and not supports_fusion(module):
                continue
            cfg_bk = _dc.replace(cfg, client_fusion=bk_name)
            jax.block_until_ready(
                fedavg_round(
                    module, cfg_bk, mesh, last_start, xs_d, ys_d, k_train
                )[0]
            )  # warm (compile excluded)
            tb = time.perf_counter()
            jax.block_until_ready(
                fedavg_round(
                    module, cfg_bk, mesh, last_start, xs_d, ys_d, k_train
                )[0]
            )
            fusion_seconds[bk_name] = time.perf_counter() - tb
            log(f"plaintext round [client_fusion={bk_name}]: "
                f"{fusion_seconds[bk_name]:.2f}s")
        # (b) fidelity: the PRODUCTION encrypted round (same program family:
        # train + encrypt + hierarchical psum-of-limbs) run once in
        # with_plain_reference mode, which additionally emits the plaintext
        # FedAvg mean of the SAME in-program trained weights. decrypt vs
        # that reference isolates pure CKKS encode/encrypt/aggregate/decrypt
        # error at flagship scale THROUGH the production collective.
        # (Comparing against (a)'s weights instead would measure training
        # chaos: a second XLA program is not bit-reproducible, and
        # fusion-level float differences flip the discrete best-epoch
        # restore.)
        # Measurement-only cost: the with_plain_reference variant is its own
        # XLA program (one extra flagship-shape compile, ~44 s cold on TPU,
        # persistent-cached afterwards) — it is NOT part of any timed round
        # above, so do not read its wall-clock as a perf regression.
        ct_diag, _, ov_diag, plain_ref = secure_fedavg_round(
            module, cfg, mesh, ctx, pk, last_start, xs_d, ys_d, last_key,
            with_plain_reference=True,
        )
        cell6_overflow = int(np.sum(np.asarray(ov_diag)))
        enc_avg = decrypt_average(ctx, sk, ct_diag, num_clients, pack)
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), enc_avg, plain_ref
        )
        max_diff = max(jax.tree_util.tree_leaves(diffs))
        # Same comparison through the exact bignum/C++ CRT decode: isolates
        # pure HE noise (encrypt/aggregate/decrypt) from the jittable f32
        # decode's recombination error.
        enc_exact = decrypt_average(
            ctx, sk, ct_diag, num_clients, pack, exact=True
        )
        diffs_exact = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), enc_exact, plain_ref
        )
        max_diff_exact = max(jax.tree_util.tree_leaves(diffs_exact))
        log(
            f"cell-6 artifact: plaintext round {plaintext_round_s:.2f}s, "
            f"max |enc_avg - plain_avg| = {max_diff:.2e} (f32 decode) / "
            f"{max_diff_exact:.2e} (exact decode), "
            f"ciphertext {ct_bytes / 1e6:.1f} MB vs plain "
            f"{param_bytes / 1e6:.1f} MB ({expansion:.1f}x expansion)"
            + (f" | ENCODE OVERFLOW: {cell6_overflow}" if cell6_overflow else "")
        )

    # Standalone HE phase timings (warm, min-over-reps): the numerators for
    # the int-op/bandwidth he_roofline rows — encrypt is 1 client, aggregate
    # a 2-stack, decrypt the core (no decode). Cheap relative to a round;
    # runs on every config so no artifact ships null HE rows (ISSUE 4).
    from hefl_tpu.ckks import ops as ckks_ops
    from hefl_tpu.ckks.backend import he_backend_report
    from hefl_tpu.fl.secure import aggregate_encrypted, encrypt_params

    enc_one = jax.jit(lambda prm, k: encrypt_params(ctx, pk, prm, k))
    ct_he = enc_one(cur, flagship_keygen_key())
    t_he_encrypt = roofline.steady_seconds(
        lambda: enc_one(cur, flagship_keygen_key()).c0
    )
    agg2 = jax.jit(lambda c0, c1: aggregate_encrypted(
        ctx, type(ct_he)(c0=jnp.stack([c0, c0]), c1=jnp.stack([c1, c1]),
                         scale=ct_he.scale)).c0)
    t_he_aggregate = roofline.steady_seconds(agg2, ct_he.c0, ct_he.c1)
    dec_core = jax.jit(lambda c0, c1: ckks_ops.decrypt(
        ctx, sk, type(ct_he)(c0=c0, c1=c1, scale=ct_he.scale)))
    t_he_decrypt = roofline.steady_seconds(dec_core, ct_he.c0, ct_he.c1)
    he_rows = roofline.he_roofline(
        {"encrypt": t_he_encrypt, "aggregate": t_he_aggregate,
         "decrypt": t_he_decrypt},
        n=ctx.n, num_limbs=ctx.num_primes, n_ct=pack.n_ct,
        num_clients=num_clients, encrypt_clients=1, device=dev,
    )
    log(
        f"HE phases: encrypt {t_he_encrypt:.3f}s | aggregate "
        f"{t_he_aggregate:.3f}s | decrypt-core {t_he_decrypt:.3f}s | "
        f"backend {he_backend_report()['backend']}"
    )

    # --- packed quantized aggregation rows (ISSUE 6) --------------------
    # Standalone packed encrypt / decrypt-core at the flagship geometry
    # (single-program timings, robust), uplink bytes-on-wire, and — unless
    # the diagnostic tail is skipped — one packed with_plain_reference
    # round whose decrypt is checked against the in-program plain mean of
    # its OWN trained weights (the same methodology as the cell-6 artifact,
    # so the diff is pure quantization + HE error).
    from hefl_tpu.ckks.packing import PackedSpec
    from hefl_tpu.fl import PackingConfig
    from hefl_tpu.fl.secure import encrypt_params_packed

    pack_cfg = PackingConfig(bits=8, interleave=4, clip=0.5)
    pspec = PackedSpec.for_params(params, ctx, pack_cfg, num_clients)
    ct_pk = encrypt_params_packed(
        ctx, pk, cur, cur, flagship_keygen_key(), pspec
    )
    t_he_encrypt_packed = roofline.steady_seconds(
        lambda: encrypt_params_packed(
            ctx, pk, cur, cur, flagship_keygen_key(), pspec
        ).c0
    )
    dec_core_p = jax.jit(lambda c0, c1: ckks_ops.decrypt(
        ctx, sk, type(ct_pk)(c0=c0, c1=c1, scale=ct_pk.scale)))
    t_he_decrypt_packed = roofline.steady_seconds(
        dec_core_p, ct_pk.c0, ct_pk.c1
    )
    from hefl_tpu.ckks.packing import bytes_on_wire_record

    bytes_on_wire = bytes_on_wire_record(pspec, ctx.num_primes)
    uplink_unpacked = bytes_on_wire["ciphertext_unpacked"]
    uplink_packed = bytes_on_wire["ciphertext_packed"]
    packed_max_diff = packed_saturation = None
    if not skip_cell6:
        ct_pd, _, sat_pd, plain_ref_pd = secure_fedavg_round(
            module, cfg, mesh, ctx, pk, last_start, xs_d, ys_d, last_key,
            with_plain_reference=True, packing=pspec,
        )
        packed_saturation = int(np.sum(np.asarray(sat_pd)))
        packed_avg = decrypt_average(
            ctx, sk, ct_pd, num_clients, packing=pspec,
            base_params=last_start,
        )
        packed_max_diff = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(packed_avg),
                jax.tree_util.tree_leaves(plain_ref_pd),
            )
        )
    packing_rec = {
        **pspec.geometry_record(),
        "standalone_encrypt_packed_s": round(t_he_encrypt_packed, 6),
        "encrypt_speedup": round(t_he_encrypt / t_he_encrypt_packed, 3),
        "decrypt_core_packed_s": round(t_he_decrypt_packed, 6),
        "decrypt_speedup": round(t_he_decrypt / t_he_decrypt_packed, 3),
        # Packed-round fidelity vs its own in-program plain reference
        # (null when the cell-6 tail is skipped — "not measured", never
        # "failed"): must sit within error_budget — quantization, not HE
        # noise, is the budget.
        "packed_round_max_abs_diff": packed_max_diff,
        "packed_round_within_budget": (
            None
            if packed_max_diff is None
            else packed_max_diff <= pspec.error_budget
        ),
        "packed_saturation_count": packed_saturation,
        "he_roofline_packed": roofline.he_roofline(
            {"encrypt": t_he_encrypt_packed, "aggregate": None,
             "decrypt": t_he_decrypt_packed},
            n=ctx.n, num_limbs=ctx.num_primes, n_ct=pspec.n_ct,
            num_clients=num_clients, encrypt_clients=1, device=dev,
        ),
    }
    log(
        f"packing (b={pspec.bits} k={pspec.k}): n_ct {pack.n_ct} -> "
        f"{pspec.n_ct} | encrypt {t_he_encrypt_packed:.3f}s "
        f"({packing_rec['encrypt_speedup']}x) | decrypt-core "
        f"{t_he_decrypt_packed:.3f}s ({packing_rec['decrypt_speedup']}x) | "
        f"uplink {uplink_unpacked / 1e6:.1f} -> {uplink_packed / 1e6:.1f} MB"
        + (
            f" | packed fidelity {packed_max_diff:.2e} "
            f"(budget {pspec.error_budget:.2e})"
            if packed_max_diff is not None
            else ""
        )
    )

    # --- cohort-only training rows (ISSUE 15) ---------------------------
    # Full-C-masked vs cohort-gathered upload producer at the FIXED
    # cohort-2-of-16 smoke geometry (single-sourced with profile_round.py
    # in fl.stream.cohort_compare_smoke_record — the ROADMAP's "millions
    # registered, thousands per cohort" shape in miniature), with the
    # committed-aggregate hash equality shipped as `bitwise_equal`.
    from hefl_tpu.fl.stream import cohort_compare_smoke_record

    cohort_rec = cohort_compare_smoke_record()
    log(
        f"cohort_compare (C=16, cohort=2, bucket {cohort_rec['bucket']}): "
        f"full-C {cohort_rec['full_c_train_s']:.3f}s vs cohort-only "
        f"{cohort_rec['cohort_train_s']:.3f}s = {cohort_rec['speedup']}x, "
        f"bitwise_equal={cohort_rec['bitwise_equal']}"
    )

    # --- hierarchical-aggregation DCN rows (ISSUE 16) -------------------
    # Flat O(cohort) vs two-tier O(hosts) cross-host bytes at the fixed
    # cohort-8-of-16 / 4-host smoke geometry, with the bitwise equality
    # of the committed aggregates across every tested arrival order
    # (single-sourced with `python -m hefl_tpu.fl.hierarchy`).
    from hefl_tpu.fl.hierarchy import dcn_compare_smoke_record

    dcn_rec = dcn_compare_smoke_record()
    log(
        f"dcn_compare (cohort={dcn_rec['cohort_size']}, "
        f"hosts={dcn_rec['num_hosts']}): flat {dcn_rec['flat_dcn_bytes']}B "
        f"vs hier {dcn_rec['hier_dcn_bytes']}B = "
        f"{dcn_rec['bytes_ratio']}x (floor {dcn_rec['ratio_floor']}), "
        f"bitwise_equal={dcn_rec['bitwise_equal']}"
    )

    obs_metrics.record_device_memory(dev)
    obs_snapshot = obs_metrics.snapshot()

    cold = round_stats[0]
    warm = round_stats[1:]
    warm_round_s = float(np.mean([s["total"] for s in warm])) if warm else None
    # Mean warm time still carries one-time costs trickling into round 1
    # (transfers, cache writes); the MIN warm round is the
    # steady-state an R-round experiment converges to, so the north-star
    # rate uses it.
    steady_round_s = float(np.min([s["total"] for s in warm])) if warm else None
    steady_train_s = float(np.min([s["train"] for s in warm])) if warm else None
    steady_decrypt_s = float(np.min([s["decrypt"] for s in warm])) if warm else None
    steady_eval_s = float(np.min([s["evaluate"] for s in warm])) if warm else None
    # Per-phase roofline records (steady = min over warm rounds; falls back
    # to the cold round when only one round ran, labeled by steady=null
    # above). The train numerator is TRAIN math only — the fused program
    # also encrypts+aggregates, so its MFU is a lower bound.
    # decrypt/evaluate rows no longer ship flops/mfu nulls (ISSUE 4): the
    # decrypt row carries the HE int-op model (op_kind marks the unit;
    # utilization is vs the ESTIMATED VPU int peak), evaluate its real
    # forward FLOPs from cost analysis.
    # seconds stays the round's full decrypt_average step; flops/mfu are
    # the CORE int-op model over the CORE time (same numerator AND
    # denominator as the he_roofline decrypt row, so the two records agree
    # by construction), with core_seconds carrying the denominator.
    decrypt_s_row = steady_decrypt_s if warm else cold["decrypt"]
    decrypt_phase = roofline.phase_stats(decrypt_s_row, device=dev)
    decrypt_phase.update(
        flops=he_rows["decrypt"]["int_ops"],
        mfu=he_rows["decrypt"]["util_vs_peak_int_ops"],
        core_seconds=round(t_he_decrypt, 4),
        op_kind="int32",
        peak_is_estimate=True,
    )
    eval_flops = roofline.program_flops(
        lambda p, xb: module.apply({"params": p}, xb), cur,
        jnp.zeros((len(xt), *x.shape[1:]), jnp.float32),
    )
    phase_roofline = {
        "train+encrypt+aggregate": roofline.phase_stats(
            steady_train_s if warm else cold["train"],
            flops=train_flops, device=dev, images=train_images_per_round,
        ),
        "decrypt": decrypt_phase,
        "evaluate": roofline.phase_stats(
            steady_eval_s if warm else cold["evaluate"], flops=eval_flops,
            device=dev, images=len(xt),
        ),
    }
    mfu = roofline.mfu(train_flops, steady_train_s, dev)
    log(
        f"cold round {cold['total']:.2f}s | warm mean "
        f"{warm_round_s and round(warm_round_s, 2)}s | steady "
        f"{steady_round_s and round(steady_round_s, 2)}s | "
        f"rounds/sec/chip {steady_round_s and round(1 / steady_round_s, 4)} | "
        f"train MFU {mfu and round(mfu, 3)} | train images/s "
        f"{phase_roofline['train+encrypt+aggregate']['images_per_s']}"
    )

    print(
        json.dumps(
            {
                "metric": "encrypted_fedavg_pipeline_wallclock",
                # Smoke runs keep the schema but must be filterable: their
                # vs_baseline/accuracy compare a tiny CPU config against the
                # medical-TPU reference numbers (results.py skips them).
                **({"smoke": True} if smoke else {}),
                **({"platform_pinned": platform} if platform else {}),
                "value": round(cold["total"], 3),
                "unit": "s",
                "vs_baseline": round(BASELINE_TOTAL_S / cold["total"], 2),
                "compile_cache": "warm" if cache_warm else "cold",
                "rounds": rounds,
                "warm_round_s": warm_round_s and round(warm_round_s, 3),
                "steady_round_s": steady_round_s and round(steady_round_s, 3),
                "rounds_per_sec_per_chip": steady_round_s
                and round(1.0 / steady_round_s, 4),
                "train_mfu": mfu and round(mfu, 4),
                # Per-phase {seconds, flops, mfu, images_per_s} sourced
                # from hefl_tpu.utils.roofline (steady-state values).
                "phase_roofline": phase_roofline,
                # Which augment row-shift backend the round programs traced
                # with (incl. auto-selection micro-timings when in "auto").
                "augment_backend": augment_backend_report(),
                # Cross-client training backend record (TrainConfig.
                # client_fusion; fl.fusion) + fused-vs-vmap MFU rows at
                # identical math (null rows when the cell-6 tail was
                # skipped).
                "client_fusion": fusion_report(),
                "client_fusion_compare": roofline.backend_compare(
                    fusion_seconds, flops=train_flops, device=dev,
                    images=train_images_per_round,
                ),
                # HE backend (fused Pallas vs XLA reference) + int-op /
                # bandwidth roofline rows for every HE phase (ISSUE 4).
                "he_backend": he_backend_report(),
                "he_roofline": he_rows,
                # Quantized bit-interleaved packing rows (ISSUE 6): the
                # packed-vs-unpacked HE timings, fidelity-vs-budget, and
                # per-client uplink bytes.
                "packing": packing_rec,
                "bytes_on_wire": bytes_on_wire,
                # Cohort-only training rows (ISSUE 15): full-C vs
                # cohort-only producer seconds, bucket chosen, devices
                # per mesh axis, committed-aggregate hash equality.
                "cohort_compare": cohort_rec,
                # Hierarchical-aggregation DCN rows (ISSUE 16): flat vs
                # two-tier cross-host bytes, per-uplink breakdown, ratio
                # vs the cohort/hosts floor, arrival-order bitwise gate.
                "dcn_compare": dcn_rec,
                "device": getattr(dev, "device_kind", str(dev)),
                "seed": seed,
                # `accuracy` pairs with `value`: both are the round-0
                # pipeline (the reference-equivalent single pass). Later
                # rounds' accuracies are in accuracy_by_round.
                "accuracy": history[0]["accuracy"],
                "accuracy_by_round": [h["accuracy"] for h in history],
                "acc_vs_reference": round(
                    history[0]["accuracy"] - BASELINE_ACC, 4
                ),
                "plaintext_round_s": plaintext_round_s
                and round(plaintext_round_s, 3),
                "enc_plain_max_abs_diff": max_diff,
                "enc_plain_max_abs_diff_exact_decode": max_diff_exact,
                **({"cell6_skipped": True} if skip_cell6 else {}),
                # Saturation guard : per-client weights
                # clipped at the CKKS encode envelope across ALL rounds —
                # 0 proves the fidelity number above is unclipped.
                # max_abs_trained_weight is the final AVERAGED model's
                # largest weight (a scale-headroom indicator only; per-client
                # clipping is exactly what encode_overflow_count counts).
                "encode_overflow_count": overflow_total,
                # Per-round exclusion counts (robustness schema shared with
                # experiment history[r]["robust"] and CHAOS_SMOKE.json).
                "exclusions_by_round": exclusions_by_round,
                # Same guard for the cell-6 artifact's own (re-)training.
                "cell6_encode_overflow_count": cell6_overflow,
                # Source: the cell-6 plaintext round's weights when it ran,
                # else the final decrypted encrypted-average model.
                "max_abs_trained_weight": round(
                    max(
                        float(jnp.max(jnp.abs(v)))
                        for v in jax.tree_util.tree_leaves(
                            cur if skip_cell6 else plain_params
                        )
                    ),
                    4,
                ),
                "ciphertext_expansion": round(expansion, 2),
                # Process-wide observability counters (obs.metrics): new
                # XLA executables, autoselect outcomes, memory high-water.
                "obs_metrics": obs_snapshot,
            }
        )
    )
    # The run completed and printed its full JSON: the rolling partial is
    # superseded — leaving it behind would let a later rename/removal of
    # the complete artifact resurrect it as bogus "rescued" evidence.
    try:
        os.remove(f"bench_partial_{ptag}_{seed}.json")
    except OSError:
        pass


if __name__ == "__main__":
    main()
