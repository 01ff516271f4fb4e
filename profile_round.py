"""Phase attribution for the fused secure round.

The production round is ONE jitted SPMD program (train + encrypt + psum),
which is the right design but makes per-phase cost invisible to wall-clock
brackets. This harness attributes it two ways:

  * `--profile` (PRIMARY, `attribution_source: "trace"`): ONE warm
    execution of the production round (+ decrypt + evaluate) runs under
    `jax.profiler.start_trace`; `hefl_tpu.obs.trace` buckets the trace's
    device-op events by the `jax.named_scope` phase annotations baked into
    the programs (augment / sgd_core / val / encrypt / psum_aggregate /
    decrypt / evaluate), joined through the compiled programs' own HLO
    metadata. Per-phase device time from a single program — no
    cross-program subtraction — printed as the `trace_attribution` table
    and embedded in the JSON with a wall-clock agreement field
    (run_perf_smoke.sh gates rows-sum vs traced wall at 15% on CPU).

  * Ablation (CROSS-CHECK, always runs): the historical
    separately-compiled variants (full round; no HE; no augment; 1-image
    val at matched geometry). Each delta subtracts two programs XLA may
    fuse differently, so raw deltas can go negative on fast rounds — rows
    are clamped at 0, raw values kept (`*_raw`), and
    `attribution_unreliable: true` flags any negative. Standalone
    encrypt/aggregate/decrypt timings cross-check the HE rows.

All timings are min-over-reps of warm executions (sub-millisecond phases
repetition-timed — `roofline.steady_seconds`) on the bench configuration
(2 clients, 10 local epochs, medical 256x256; PROFILE_SMOKE=1 shrinks to a
CPU-sized mnist config whose traced round stays under the trace-viewer
event cap). Writes markdown tables + one JSON line to stdout.

Every phase row also carries {mfu, images_per_s} sourced from
`hefl_tpu.utils.roofline` (train-math FLOPs over phase seconds — a lower
bound for the fused row, which also encrypts).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _steady(fn, reps: int = 3, warmup: int = 1) -> float:
    from hefl_tpu.utils.roofline import steady_seconds

    return steady_seconds(fn, reps=reps, warmup=warmup)


def main(argv: list[str] | None = None) -> None:
    args = argparse.ArgumentParser(
        description="per-phase attribution of the fused secure round"
    )
    args.add_argument(
        "--profile", nargs="?", const="profile_trace", default=None,
        metavar="DIR",
        help="trace ONE warm round (+ decrypt + evaluate) with "
             "jax.profiler into DIR and emit the trace_attribution table "
             "(per-phase device time from one program; "
             "attribution_source becomes 'trace')",
    )
    opts = args.parse_args(argv)

    import jax

    from hefl_tpu.utils.device import select_platform, setup_compile_cache

    smoke = os.environ.get("PROFILE_SMOKE") == "1"
    select_platform("profile_round.py", cpu=smoke)
    import jax.numpy as jnp

    setup_compile_cache()

    from hefl_tpu.obs import metrics as obs_metrics

    obs_metrics.install_jax_listeners()

    from hefl_tpu.ckks.keys import CkksContext, keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.data.augment import (
        SHIFT_BACKENDS,
        backend_report,
        random_augment,
        resolve_shift_backend,
    )
    from hefl_tpu.fl import (
        TrainConfig,
        decrypt_average,
        evaluate,
        fedavg_round,
        secure_fedavg_round,
    )
    from hefl_tpu.ckks.backend import he_backend_report
    from hefl_tpu.fl.secure import aggregate_encrypted, encrypt_params
    from hefl_tpu.models import create_model
    from hefl_tpu.parallel import make_mesh
    from hefl_tpu.utils import roofline

    num_clients = 2
    if smoke:
        # CI/CPU shakeout of the harness itself (tiny shapes, same code
        # path); real numbers come from the TPU run without this flag.
        # n_train=32 (1 optimizer step/epoch/client) keeps the traced
        # round's CPU event count well under the trace-viewer converter's
        # 1e6-event cap — the maxpool-backward scatter loop logs one event
        # per output element, so event volume scales with train geometry.
        (x, y), (xt, yt), _ = make_dataset("mnist", seed=0, n_train=32, n_test=32)
        xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
        module, params = create_model("smallcnn", rng=jax.random.key(123))
        cfg = TrainConfig(epochs=1, batch_size=8, num_classes=10,
                          val_fraction=0.25)
    else:
        (x, y), (xt, yt), _ = make_dataset("medical", seed=0)
        xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
        module, params = create_model("medcnn", rng=jax.random.key(123))
        cfg = TrainConfig(warmup_steps=44)
    ctx = CkksContext.create(n=256) if smoke else CkksContext.create()
    mesh = make_mesh(num_clients)
    sk, pk = keygen(ctx, jax.random.key(99))
    pack = PackSpec.for_params(params, ctx.n)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)
    xt_d = jax.device_put(jnp.asarray(xt))
    key = jax.random.key(5)
    dev = jax.devices()[0]

    # Full-config train geometry (the same helper _train_split uses): the
    # matched-geometry val ablation below needs n_tr to hold the variant's
    # step count identical to the full round's.
    from hefl_tpu.fl.client import train_batch_geometry

    _n_tr_full, _grp_full, _steps_full = train_batch_geometry(
        cfg, int(xs.shape[1])
    )

    variants = {
        "full secure round (train+encrypt+aggregate)": lambda: secure_fedavg_round(
            module, cfg, mesh, ctx, pk, params, xs_d, ys_d, key
        )[0].c0,
        "plain round (train+pmean, no HE)": lambda: fedavg_round(
            module, cfg, mesh, params, xs_d, ys_d, key
        )[0],
        "plain round, augment off": lambda: fedavg_round(
            module,
            dataclasses.replace(cfg, augment=False),
            mesh, params, xs_d, ys_d, key,
        )[0],
        # Matched-geometry val ablation. val_fraction=0.0 would be wrong
        # twice over: _train_split's val_fraction=0 fallback validates on
        # the whole TRAIN slice (the source of the committed −17.7% row,
        # the ablated variant coming out SLOWER than the full round), and
        # an epsilon fraction alone changes n_tr and hence the step count.
        # Feeding the variant n_tr+1 samples with an epsilon fraction
        # clamps the val split to ONE image at the SAME train geometry
        # (same batch, same steps/epoch), so the delta is eval cost only.
        "plain round, 1-image val": lambda: fedavg_round(
            module,
            dataclasses.replace(cfg, val_fraction=1e-9, es_patience=10**6,
                                plateau_patience=10**6),
            mesh, params, xs_d[:, : _n_tr_full + 1], ys_d[:, : _n_tr_full + 1],
            key,
        )[0],
    }
    times: dict[str, float] = {}
    for name, fn in variants.items():
        times[name] = _steady(fn)
        log(f"{name}: {times[name]:.3f}s")

    # Packed-quantized round (ISSUE 6): the SAME production secure round
    # with the FedBit-style b-bit k-interleaved upload — every HE stage
    # sees [n_ct/k] ciphertext rows, so (full_packed - plain) is the
    # he_in_round cost at the packed geometry.
    from hefl_tpu.ckks.packing import PackedSpec
    from hefl_tpu.fl import PackingConfig
    from hefl_tpu.fl.secure import encrypt_params_packed

    pack_cfg = PackingConfig(bits=8, interleave=4, clip=0.5)
    pspec = PackedSpec.for_params(params, ctx, pack_cfg, num_clients)
    t_full_packed = _steady(
        lambda: secure_fedavg_round(
            module, cfg, mesh, ctx, pk, params, xs_d, ys_d, key,
            packing=pspec,
        )[0].c0
    )
    log(f"full secure round [packed b={pspec.bits} k={pspec.k}]: "
        f"{t_full_packed:.3f}s")

    # Fused-vs-vmap comparison rows (ISSUE 3): the SAME plain round timed
    # under each cross-client training backend (fl.fusion) — identical
    # math/FLOPs, different per-layer GEMM shaping — so every profile
    # artifact records what client fusion buys on this device.
    from hefl_tpu.fl.fusion import fusion_report, supports_fusion

    fusion_times: dict[str, float] = {}
    for bk_name in ("vmap", "fused"):
        if bk_name == "fused" and not supports_fusion(module):
            continue
        cfg_bk = dataclasses.replace(cfg, client_fusion=bk_name)
        fusion_times[bk_name] = _steady(
            lambda c=cfg_bk: fedavg_round(
                module, c, mesh, params, xs_d, ys_d, key
            )[0]
        )
        log(f"plain round [client_fusion={bk_name}]: "
            f"{fusion_times[bk_name]:.3f}s")

    # Standalone HE stages (not inside the big program): encrypt both
    # clients' params + aggregate + decrypt + evaluate.
    from hefl_tpu.ckks import ops as ckks_ops

    enc2 = jax.jit(
        lambda prm, k: encrypt_params(ctx, pk, prm, k)
    )
    ct0 = enc2(params, jax.random.key(1))
    t_encrypt = _steady(lambda: enc2(params, jax.random.key(1)).c0)
    stacked = jax.jit(
        lambda c0, c1: aggregate_encrypted(
            ctx,
            type(ct0)(c0=jnp.stack([c0, c0]), c1=jnp.stack([c1, c1]),
                      scale=ct0.scale),
        ).c0
    )
    t_aggregate = _steady(lambda: stacked(ct0.c0, ct0.c1))
    # Decrypt CORE (c0 + c1*s + iNTT) timed apart from the full owner step
    # (which also runs the CRT decode + unpack) — the core is what the HE
    # int-op roofline models.
    dec_core = jax.jit(lambda c0, c1: ckks_ops.decrypt(
        ctx, sk, type(ct0)(c0=c0, c1=c1, scale=ct0.scale)))
    t_decrypt_core = _steady(lambda: dec_core(ct0.c0, ct0.c1))
    t_decrypt = _steady(
        lambda: jax.tree_util.tree_leaves(
            decrypt_average(ctx, sk, ct0, 1, pack)
        )[0]
    )
    t_evaluate = _steady(lambda: evaluate(module, params, xt_d, yt)["accuracy"])
    log(f"standalone encrypt(1 client): {t_encrypt:.3f}s, aggregate(2): "
        f"{t_aggregate:.3f}s, decrypt: {t_decrypt:.3f}s (core "
        f"{t_decrypt_core:.3f}s), evaluate: {t_evaluate:.3f}s")

    # Standalone PACKED encrypt/decrypt-core at the same geometry: the
    # [n_ct/k] twin of the two timings above (a zero update is a perfectly
    # representative payload — HE cost is shape-, not value-, dependent).
    ct_pk = encrypt_params_packed(
        ctx, pk, params, params, jax.random.key(1), pspec
    )
    t_encrypt_packed = _steady(
        lambda: encrypt_params_packed(
            ctx, pk, params, params, jax.random.key(1), pspec
        ).c0
    )
    dec_core_p = jax.jit(lambda c0, c1: ckks_ops.decrypt(
        ctx, sk, type(ct_pk)(c0=c0, c1=c1, scale=ct_pk.scale)))
    t_decrypt_core_packed = _steady(
        lambda: dec_core_p(ct_pk.c0, ct_pk.c1)
    )
    log(f"standalone packed encrypt: {t_encrypt_packed:.3f}s "
        f"({t_encrypt / t_encrypt_packed:.2f}x), packed decrypt core: "
        f"{t_decrypt_core_packed:.3f}s "
        f"({t_decrypt_core / t_decrypt_core_packed:.2f}x)")

    # Cohort-only vs full-C training producer (ISSUE 15): the
    # `cohort_compare` record at the FIXED cohort-2-of-16 smoke geometry
    # (single-sourced with bench.py in
    # fl.stream.cohort_compare_smoke_record) — full-C-masked vs
    # cohort-gathered train seconds, bucket chosen, devices per axis,
    # and the committed-aggregate hash equality as `bitwise_equal`.
    # run_perf_smoke.sh gates the schema and a >= 2x speedup floor.
    from hefl_tpu.fl.stream import cohort_compare_smoke_record

    cohort_rec = cohort_compare_smoke_record()
    log(
        f"cohort_compare (C=16, cohort=2, bucket {cohort_rec['bucket']}): "
        f"full-C {cohort_rec['full_c_train_s']:.3f}s vs cohort-only "
        f"{cohort_rec['cohort_train_s']:.3f}s = {cohort_rec['speedup']}x, "
        f"bitwise_equal={cohort_rec['bitwise_equal']}"
    )

    # Augment backend shootout at the training batch shape (always the
    # flagship 256x256 image — augment cost is what this PR attacks, so
    # the row must stay comparable across configs). The per-device winner
    # of this same race is what "auto" mode picks at first use.
    batch = jnp.asarray(
        np.random.default_rng(3).random((cfg.batch_size, 256, 256, 3), np.float32)
    )
    aug_times = {}
    for backend in SHIFT_BACKENDS:
        fn = lambda: random_augment(jax.random.key(0), batch, backend=backend)  # noqa: B023,E731
        aug_times[backend] = _steady(fn, reps=10)
        log(f"random_augment[{backend}] per batch-{cfg.batch_size}: "
            f"{aug_times[backend] * 1e3:.2f} ms")
    chosen = resolve_shift_backend(cfg.aug_backend)

    # ------------------------------------------------------------------
    # Trace-native attribution (--profile): ONE warm execution of the
    # production round + decrypt + evaluate under jax.profiler; obs.trace
    # buckets the device-op events by the named scopes baked into the
    # programs. This is the PRIMARY attribution (attribution_source:
    # "trace"); the ablation below remains as a cross-check.
    # ------------------------------------------------------------------
    trace_rec = None
    if opts.profile:
        from hefl_tpu.ckks.ops import Ciphertext
        from hefl_tpu.fl.fedavg import _predict_all, replicate_on
        from hefl_tpu.fl.secure import _build_secure_round_fn
        from hefl_tpu.obs import trace as obs_trace

        # The SAME compiled program family the ablation's full-round
        # variant ran (the factory is lru_cached, so this returns the very
        # jitted fn secure_fedavg_round used) with the identical key
        # derivation — the traced round IS the production round.
        round_fn = _build_secure_round_fn(module, cfg, mesh, ctx, False)
        gp = replicate_on(mesh, params)
        k_train, k_enc = jax.random.split(key)
        tks = jax.random.split(k_train, num_clients)
        eks = jax.random.split(k_enc, num_clients)
        rargs = (gp, pk, xs_d, ys_d, tks, eks)
        dec_fn = jax.jit(
            lambda c0, c1: decrypt_average(
                ctx, sk,
                Ciphertext(c0=c0, c1=c1, scale=ctx.scale),
                num_clients, pack,
            )
        )
        # Warm everything the traced region runs, then trace one pass.
        ct_w, _, _ = round_fn(*rargs)
        jax.block_until_ready(dec_fn(ct_w.c0, ct_w.c1))
        evaluate(module, params, xt_d, yt)
        eval_bs = 32
        pad = (-len(xt)) % eval_bs
        x_pad = (
            xt_d if pad == 0
            else jnp.concatenate([xt_d, jnp.repeat(xt_d[:1], pad, axis=0)])
        )

        jax.profiler.start_trace(opts.profile)
        t0 = time.perf_counter()
        ct_t, mets_t, _ = round_fn(*rargs)
        jax.block_until_ready((ct_t.c0, ct_t.c1, mets_t))
        wall_round = time.perf_counter() - t0
        t1 = time.perf_counter()
        jax.block_until_ready(
            jax.tree_util.tree_leaves(dec_fn(ct_t.c0, ct_t.c1))
        )
        wall_decrypt = time.perf_counter() - t1
        t2 = time.perf_counter()
        evaluate(module, params, xt_d, yt)
        wall_evaluate = time.perf_counter() - t2
        wall_total = time.perf_counter() - t0
        jax.profiler.stop_trace()
        log(f"traced one round into {opts.profile} "
            f"(round {wall_round:.3f}s decrypt {wall_decrypt:.3f}s "
            f"evaluate {wall_evaluate:.3f}s)")

        # The compiled HLO of the three traced programs: the join key
        # between trace events (hlo_module/hlo_op) and the phase scopes.
        # Compiled OUTSIDE the persistent cache — a cache-deserialized
        # executable's as_text() drops the op_name metadata the join needs.
        with obs_trace.metadata_preserving_compile():
            hlo_round = round_fn.lower(*rargs).compile().as_text()
            hlo_dec = dec_fn.lower(ct_t.c0, ct_t.c1).compile().as_text()
            hlo_eval = _predict_all.lower(
                module, params, x_pad, eval_bs
            ).compile().as_text()
        rec = obs_trace.trace_attribution(
            opts.profile, [hlo_round, hlo_dec, hlo_eval]
        )
        round_module = obs_trace.hlo_module_name(hlo_round)
        round_dev = rec["modules"].get(round_module, 0.0)
        trace_rec = {
            **rec,
            "wall_s": {
                "round": round(wall_round, 6),
                "decrypt": round(wall_decrypt, 6),
                "evaluate": round(wall_evaluate, 6),
                "total": round(wall_total, 6),
            },
            "round_module": round_module,
            # Sum-vs-wall agreement for the ROUND program (the CI gate):
            # union of the round module's device-op time over its traced
            # wall clock. Profiler overhead inflates both sides together,
            # so a healthy trace sits near 1.0.
            "round_wall_agreement": (
                round(round_dev / wall_round, 4) if wall_round else None
            ),
            "attributed_sum_s": obs_trace.attributed_sum_s(rec),
        }
        if rec.get("suspected_truncated"):
            log("WARNING: trace near the 1e6-event converter cap — "
                "attribution may undercount late phases")

    full = times["full secure round (train+encrypt+aggregate)"]
    train_only = times["plain round (train+pmean, no HE)"]
    no_aug = times["plain round, augment off"]
    no_val = times["plain round, 1-image val"]
    raw = {
        "he_in_round_s": full - train_only,
        "augment_s": train_only - no_aug,
        "per_epoch_val_s": train_only - no_val,
    }
    raw["sgd_core_s"] = no_aug - raw["per_epoch_val_s"]
    clamped, unreliable = roofline.clamp_attribution(raw)

    # Roofline columns: train-math FLOPs (fwd+bwd ~= 3x fwd at the fused
    # batch) over phase seconds, at the geometry computed above (the same
    # helper _train_split uses).
    grp, steps_per_epoch = _grp_full, _steps_full
    fwd_flops = roofline.program_flops(
        lambda p, xb: module.apply({"params": p}, xb),
        params,
        jnp.zeros((grp, *x.shape[1:]), jnp.float32),
    )
    train_flops = roofline.train_flops_per_round(
        fwd_flops, steps_per_epoch, cfg.epochs, num_clients
    )
    train_images = num_clients * cfg.epochs * steps_per_epoch * grp
    # HE roofline (ISSUE 4): analytic int-op/bandwidth rows for the HE
    # phases at this geometry — the encrypt row is the 1-client standalone
    # timing, aggregate the 2-stack, decrypt the core (no decode).
    he_rows = roofline.he_roofline(
        {"encrypt": t_encrypt, "aggregate": t_aggregate,
         "decrypt": t_decrypt_core},
        n=ctx.n, num_limbs=ctx.num_primes, n_ct=pack.n_ct,
        num_clients=num_clients, encrypt_clients=1, device=dev,
    )
    # The decrypt/evaluate phase rows used to carry flops/mfu nulls: decrypt
    # now reports the HE int-op model (op_kind marks the unit — uint32 ops,
    # not flops; mfu is utilization vs the ESTIMATED VPU int peak), and
    # evaluate gets its real forward FLOPs from cost analysis.
    eval_flops = roofline.program_flops(
        lambda p, xb: module.apply({"params": p}, xb), params,
        jnp.zeros((len(xt), *x.shape[1:]), jnp.float32),
    )
    # seconds stays the full owner step; flops/mfu are the CORE int-op
    # model over the CORE time (identical numerator AND denominator to the
    # he_roofline decrypt row, so the two records cannot disagree), with
    # core_seconds carrying the denominator explicitly.
    decrypt_phase = roofline.phase_stats(t_decrypt, device=dev)
    decrypt_phase.update(
        flops=he_rows["decrypt"]["int_ops"],
        mfu=he_rows["decrypt"]["util_vs_peak_int_ops"],
        core_seconds=round(t_decrypt_core, 4),
        op_kind="int32",
        peak_is_estimate=True,
    )
    phase_roofline = {
        "fused_round": roofline.phase_stats(
            full, flops=train_flops, device=dev, images=train_images
        ),
        "train_only": roofline.phase_stats(
            train_only, flops=train_flops, device=dev, images=train_images
        ),
        "decrypt": decrypt_phase,
        "evaluate": roofline.phase_stats(
            t_evaluate, flops=eval_flops, device=dev, images=len(xt)
        ),
    }
    client_fusion_compare = roofline.backend_compare(
        fusion_times, flops=train_flops, device=dev, images=train_images
    )

    # Packed-vs-unpacked record (ISSUE 6): he_in_round at both geometries
    # (ablation-subtracted, so clamped + raw like the other rows), the
    # standalone encrypt/decrypt-core speedups (single-program timings, the
    # robust numbers), bytes-on-wire, and the packed he_roofline rows.
    he_in_round_packed_raw = t_full_packed - train_only
    he_rows_packed = roofline.he_roofline(
        {"encrypt": t_encrypt_packed, "aggregate": None,
         "decrypt": t_decrypt_core_packed},
        n=ctx.n, num_limbs=ctx.num_primes, n_ct=pspec.n_ct,
        num_clients=num_clients, encrypt_clients=1, device=dev,
    )
    from hefl_tpu.ckks.packing import bytes_on_wire_record

    # Per-client uplink bytes: float32 update vs CKKS ciphertext pair,
    # unpacked and packed (the ~k-fold reduction the ISSUE targets).
    bytes_on_wire = bytes_on_wire_record(pspec, ctx.num_primes)
    packing_rec = {
        **pspec.geometry_record(),
        "full_round_packed_s": round(t_full_packed, 6),
        "he_in_round_packed_s": round(max(he_in_round_packed_raw, 0.0), 6),
        "he_in_round_packed_s_raw": round(he_in_round_packed_raw, 6),
        # Ablation-subtracted, so null when either raw delta goes
        # non-positive (the documented fast-round noise mode — same
        # clamp-and-flag philosophy as the other attribution rows; the
        # perf-smoke gate treats null as noise and leans on the robust
        # single-program standalone speedups instead).
        "he_in_round_speedup": (
            round(raw["he_in_round_s"] / he_in_round_packed_raw, 3)
            if he_in_round_packed_raw > 0 and raw["he_in_round_s"] > 0
            else None
        ),
        "standalone_encrypt_packed_s": round(t_encrypt_packed, 6),
        "encrypt_speedup": round(t_encrypt / t_encrypt_packed, 3),
        "decrypt_core_packed_s": round(t_decrypt_core_packed, 6),
        "decrypt_speedup": round(t_decrypt_core / t_decrypt_core_packed, 3),
        "he_roofline_packed": he_rows_packed,
    }

    att = {
        # The PRIMARY attribution: trace-derived when --profile ran (the
        # ablation rows below are then a cross-check), else ablation.
        "attribution_source": "trace" if trace_rec is not None else "ablation",
        **({"trace_attribution": trace_rec} if trace_rec is not None else {}),
        "full_round_s": round(full, 3),
        "train_s": round(train_only, 3),
        **{k: round(v, 3) for k, v in clamped.items()},
        **{f"{k}_raw": round(v, 3) for k, v in raw.items()},
        "attribution_unreliable": unreliable,
        # 6 decimals: sub-millisecond phases (the repetition-timed
        # aggregate) must never round to a bare 0.0.
        "standalone_encrypt_s": round(t_encrypt, 6),
        "standalone_aggregate_s": round(t_aggregate, 6),
        "decrypt_s": round(t_decrypt, 6),
        "decrypt_core_s": round(t_decrypt_core, 6),
        "evaluate_s": round(t_evaluate, 6),
        **{
            f"augment_{b}_ms": round(t * 1e3, 3) for b, t in aug_times.items()
        },
        "augment_backend": {**backend_report(), "backend": chosen},
        # Cross-client backend record + the timed fused-vs-vmap MFU rows.
        "client_fusion": fusion_report(),
        "client_fusion_compare": client_fusion_compare,
        "phase_roofline": phase_roofline,
        # HE backend (fused Pallas vs XLA reference) + the int-op/bandwidth
        # roofline rows for encrypt/aggregate/decrypt (ISSUE 4).
        "he_backend": he_backend_report(),
        "he_roofline": he_rows,
        # Quantized bit-interleaved packing rows (ISSUE 6): packed-vs-
        # unpacked he_in_round / standalone HE timings + uplink bytes.
        "packing": packing_rec,
        "bytes_on_wire": bytes_on_wire,
        # Cohort-only training rows (ISSUE 15): full-C-masked vs
        # cohort-gathered producer seconds, the bucket chosen, devices
        # per mesh axis, and the committed-aggregate hash equality.
        "cohort_compare": cohort_rec,
        # Process-wide observability counters (obs.metrics): compile
        # count, autoselect outcomes, memory high-water.
        "obs_metrics": obs_metrics.snapshot(),
        "device": roofline.device_kind(dev),
    }

    if trace_rec is not None:
        total_attr = trace_rec["attributed_sum_s"] or 1.0
        print(
            "Attribution method: TRACE — one warm execution of the "
            "production round (+ decrypt + evaluate) under jax.profiler; "
            "rows are per-phase device-time unions of the trace's op "
            "events, bucketed by the named scopes compiled into the "
            "programs (hefl_tpu.obs.trace). No cross-program subtraction. "
            "The ablation table below is retained as a cross-check."
        )
        print()
        print("| phase (trace) | device s | share of traced device time |")
        print("|---|---|---|")
        for ph, row in trace_rec["rows"].items():
            print(f"| {ph} | {row['device_seconds']:.4f} "
                  f"| {row['device_seconds'] / total_attr:.1%} |")
        print(f"| (unattributed) | {trace_rec['unattributed_s']:.4f} "
              f"| {trace_rec['unattributed_s'] / total_attr:.1%} |")
        print()
        print(
            f"traced round wall {trace_rec['wall_s']['round']:.3f}s vs "
            f"round-program device time "
            f"{trace_rec['modules'].get(trace_rec['round_module'], 0.0):.3f}s "
            f"(agreement {trace_rec['round_wall_agreement']}); "
            f"attribution_source: trace"
        )
        print()
    print(
        "Ablation cross-check"
        + ("" if trace_rec is not None else
           " (attribution_source: ablation — run with --profile for the "
           "trace-derived table)")
        + ": each row below the total is the "
        "difference between two separately-compiled program variants "
        "(estimates; XLA may fuse each variant differently). Raw deltas "
        "are clamped at 0 in this table; the JSON keeps the raw values "
        "(`*_raw`) and sets `attribution_unreliable: true` when any raw "
        "delta was negative"
        + (" — WHICH IS THE CASE FOR THIS RUN" if unreliable else "")
        + ". Standalone encrypt/aggregate rows cross-check the HE estimate."
    )
    print()
    print("| phase | seconds | share of fused round |")
    print("|---|---|---|")
    rows = [
        ("fused round total", full, 1.0),
        ("  local SGD (no augment, no val)", clamped["sgd_core_s"],
         clamped["sgd_core_s"] / full),
        ("  data augmentation (affine warp)", clamped["augment_s"],
         clamped["augment_s"] / full),
        ("  per-epoch validation + callbacks", clamped["per_epoch_val_s"],
         clamped["per_epoch_val_s"] / full),
        ("  CKKS encrypt + psum (fused - plain)", clamped["he_in_round_s"],
         clamped["he_in_round_s"] / full),
    ]
    for name, t, share in rows:
        print(f"| {name} | {t:.3f} | {share:.1%} |")
    print(f"| decrypt (separate phase) | {att['decrypt_s']:.3f} | — |")
    print(f"| evaluate (separate phase) | {att['evaluate_s']:.3f} | — |")
    print()
    tr = phase_roofline["train_only"]
    print(
        f"train-phase roofline: MFU {tr['mfu']} | {tr['images_per_s']} "
        f"images/s"
    )
    print()
    print("| augment backend (full warp) | ms / batch |")
    print("|---|---|")
    for b in SHIFT_BACKENDS:
        tag = " (selected)" if b == chosen else ""
        print(f"| {b}{tag} | {att[f'augment_{b}_ms']} |")
    print()
    print("| client-fusion backend (plain round) | seconds | MFU |")
    print("|---|---|---|")
    for b, t in fusion_times.items():
        row = client_fusion_compare[b]
        print(f"| {b} | {t:.3f} | {row['mfu']} |")
    sp = client_fusion_compare.get("fused_speedup_vs_vmap")
    if sp is not None:
        print(f"\nfused train-round speedup vs vmap: {sp}x")
    print()
    print(f"| HE phase (backend={att['he_backend']['backend']}) | seconds "
          "| int-ops/s | bytes/s |")
    print("|---|---|---|---|")
    for ph in ("encrypt", "aggregate", "decrypt"):
        row = he_rows[ph]
        print(f"| {ph} | {row['seconds']} | {row['int_ops_per_s']:.3g} "
              f"| {row['bytes_per_s']:.3g} |")
    print()
    print(f"| packing (b={pspec.bits}, k={pspec.k}) | unpacked | packed "
          "| speedup/reduction |")
    print("|---|---|---|---|")
    print(f"| n_ct | {pack.n_ct} | {pspec.n_ct} "
          f"| {pack.n_ct / pspec.n_ct:.2f}x |")
    sp_he = packing_rec["he_in_round_speedup"]
    print(f"| he_in_round (s) | {clamped['he_in_round_s']:.3f} "
          f"| {packing_rec['he_in_round_packed_s']:.3f} "
          f"| {f'{sp_he}x' if sp_he is not None else 'n/a (ablation noise)'} |")
    print(f"| standalone encrypt (s) | {t_encrypt:.3f} "
          f"| {t_encrypt_packed:.3f} "
          f"| {packing_rec['encrypt_speedup']}x |")
    print(f"| decrypt core (s) | {t_decrypt_core:.3f} "
          f"| {t_decrypt_core_packed:.3f} "
          f"| {packing_rec['decrypt_speedup']}x |")
    print(f"| uplink bytes/client | {bytes_on_wire['ciphertext_unpacked']} "
          f"| {bytes_on_wire['ciphertext_packed']} "
          f"| {bytes_on_wire['packed_reduction']}x |")
    print(json.dumps({"metric": "phase_attribution", **att}))


if __name__ == "__main__":
    main()
