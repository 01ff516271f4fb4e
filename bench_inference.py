"""Private-inference serving benchmark: the BENCH_INFER artifact family.

Measures the steady-state serving cost of the precompiled scorers
(`he_inference.LinearScorer` ladder reference, `BsgsLinearScorer` — the
ISSUE-13 baby-step giant-step serving plan — and `MlpScorer`): compile
time once, then per-call latency percentiles (p50/p95/p99) and QPS, with
each call blocked to completion the way a serving loop would experience
it. Batched rows drive `score_many` (bucket-padded batches, one fused
dispatch chain per batch) against the single-query rows, which is the
throughput claim the perf smoke gates at >= 1.3x.

ISSUE 18 adds the hoisting rows: the BSGS plan with the baby sweep's
gadget decomposition shared ("bsgs", the serving default) vs re-run per
step ("bsgs_unhoisted") — bitwise-equal outputs (gated by parity shas),
strictly fewer forward NTTs per score, and a gated hoisted-QPS floor —
plus the composed two-layer "mlp_bsgs" plan against the per-class-ladder
"mlp" rows (same circuit to decryption tolerance, far fewer
key-switches). The `hoisted` and `mlp_compare` artifact blocks carry the
comparisons.

Both configurations sit within the 128-bit-security envelope (linear:
N=4096 / 3x27-bit primes, log2(q)=81 <= 109; MLP: N=8192 / 5 primes,
log2(q)=135 <= 218). The reference has no private-inference capability at
all (its model always runs on plaintext, /root/reference/FLPyfhelin.py:
366-390), so these rows are beyond-parity: there is no baseline number.

Output: a markdown table on stdout (the TPU suite redirects it to
INFERENCE_TABLE.md), one machine-readable JSON line per row, and the
BENCH_INFER JSON artifact (path: $BENCH_INFER_PATH, default
BENCH_INFER.json) carrying the rows + the `analysis_check` evidence
(certify_inference AND certify_keyswitch per serving ring) + the resolved
`he_backend` record.

INFERENCE_SMOKE=1 pins CPU and shrinks rings for a pipeline shakeout.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

SMOKE = os.environ.get("INFERENCE_SMOKE") == "1"
import jax

from hefl_tpu.utils.device import select_platform

select_platform("bench_inference.py", cpu=SMOKE)

REPS = int(os.environ.get("INFERENCE_REPS", "20"))
ARTIFACT_PATH = os.environ.get("BENCH_INFER_PATH", "BENCH_INFER.json")


def _measure(call, ready, reps):
    """Per-call wall latencies, each blocked to completion (serving
    style: a single query pays its own dispatch; a batch amortizes one).
    -> (compile_s, latencies_s[reps])."""
    t0 = time.perf_counter()
    out = call()
    jax.block_until_ready(ready(out))
    compile_s = time.perf_counter() - t0
    lats = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = call()
        jax.block_until_ready(ready(out))
        lats.append(time.perf_counter() - t0)
    return compile_s, np.asarray(lats), out


def _row(name, plan, batch, keyswitches, compile_s, lats, err, argmax_ok,
         ntts=None):
    mean = float(np.mean(lats))
    row = {
        "row": name,
        "plan": plan,
        "batch": batch,
        "keyswitches_per_score": keyswitches,
        "compile_s": round(compile_s, 3),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
        "p95_ms": round(float(np.percentile(lats, 95)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
        "warm_latency_ms": round(mean * 1e3, 3),
        "qps": round(batch / mean, 2),
        "scores_per_s": round(batch / mean, 2),
        "max_abs_err": err,
        "argmax_ok": argmax_ok,
    }
    if ntts is not None:
        row["forward_ntts_per_score"] = int(ntts)
    return row


def _parity_sha(out) -> str:
    """Bitwise fingerprint of a ciphertext result: sha256 over the raw
    (c0, c1) residue bytes. Equal shas == bitwise-equal ciphertexts —
    the hoisted/unhoisted parity gate run_perf_smoke.sh checks."""
    import hashlib

    h = hashlib.sha256()
    h.update(np.asarray(out.c0).tobytes())
    h.update(np.asarray(out.c1).tobytes())
    return h.hexdigest()


def main():
    from hefl_tpu import he_inference as hei
    from hefl_tpu.analysis import check_inference
    from hefl_tpu.ckks import encoding
    from hefl_tpu.ckks.backend import he_backend_report
    from hefl_tpu.ckks.keys import CkksContext, gen_relin_key, keygen
    from hefl_tpu.obs import metrics as obs_metrics

    backend = jax.devices()[0]
    rows = []
    rng = np.random.default_rng(42)
    certified = []

    # --- Encrypted linear: ladder reference vs the BSGS serving plan ----
    n_lin = 256 if SMOKE else 4096
    ctx = CkksContext.create(n=n_lin)
    # Pre-flight static analysis (ISSUE 12/13): the rotate-and-sum ladder
    # AND the key-switch gadget certify at this ring's geometry before any
    # bench work — an uncertified serving ring fails loudly here.
    certified.extend(
        c.summary() for c in check_inference(ctx).values()
    )
    sk, pk = keygen(ctx, jax.random.key(0))
    gks = hei.gen_rotation_keys(ctx, sk, jax.random.key(1))
    slots = encoding.num_slots(ctx.ntt)
    # d = slots/4 leaves headroom for 4-per-ct query packing in the
    # batched row (full-width d admits no packing, q = 1).
    d = 32 if SMOKE else slots // 4
    K = 10
    W = rng.normal(0, 0.3, (K, d))
    b = rng.normal(0, 0.2, K)
    want = lambda xs: np.asarray(xs) @ W.T + b  # noqa: E731

    x1 = rng.normal(0, 0.5, d)
    ct1 = hei.encrypt_features(ctx, pk, x1, jax.random.key(100))
    B_lin = 8 if SMOKE else 16

    ladder = hei.LinearScorer(ctx, W, b, gks)
    compile_s, lats, out = _measure(
        lambda: ladder.score_batched(ct1), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_scores(
        ctx, sk,
        [hei.Ciphertext(c0=out.c0[k], c1=out.c1[k], scale=out.scale)
         for k in range(K)],
    )
    rows.append(_row(
        f"linear N={n_lin} d={d} K={K}", "ladder", 1,
        hei.ladder_keyswitches(slots, K), compile_s, lats,
        float(np.max(np.abs(got - want(x1)))),
        bool(np.argmax(got) == np.argmax(want(x1))),
    ))

    plan = hei.bsgs_plan(slots, d, K)
    bsgs_gks = hei.gen_rotation_keys_for_steps(
        ctx, sk, jax.random.key(2), plan.rotation_steps_needed
    )
    bsgs = hei.BsgsLinearScorer(ctx, W, b, bsgs_gks)
    compile_s, lats, out = _measure(
        lambda: bsgs.score(ct1), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_class_scores(ctx, sk, out, K)
    single = _row(
        f"bsgs N={n_lin} d={d} K={K}", "bsgs", 1,
        bsgs.plan.num_keyswitches, compile_s, lats,
        float(np.max(np.abs(got - want(x1)))),
        bool(np.argmax(got) == np.argmax(want(x1))),
        ntts=bsgs.hoisted_ntts,
    )
    rows.append(single)

    # Hoisted vs unhoisted (ISSUE 18): the SAME plan run with the baby
    # sweep's shared decomposition vs re-run per step — identical
    # uncentered digits, so the outputs must be BITWISE equal (the parity
    # shas the perf smoke gates) while the hoisted run pays L*d forward
    # NTTs once instead of per baby step (the gated forward-NTT and QPS
    # deltas). The pair uses a baby-HEAVY split: hoisting makes baby
    # rotations NTT-free, so the hoisting-optimal plan shifts rotations
    # out of the giant sweep — the default min-keyswitch split would
    # leave most of the work on the (mode-independent) giant path and
    # understate the win.
    hoist_baby = 16 if SMOKE else 64
    hoist_gks = hei.gen_rotation_keys_for_steps(
        ctx, sk, jax.random.key(3),
        hei.bsgs_plan(slots, d, K, hoist_baby).rotation_steps_needed,
    )
    hoisted = hei.BsgsLinearScorer(ctx, W, b, hoist_gks, baby=hoist_baby)
    compile_s, lats, out_h = _measure(
        lambda: hoisted.score(ct1), lambda o: (o.c0, o.c1), REPS
    )
    got_h = hei.decrypt_class_scores(ctx, sk, out_h, K)
    hoisted_row = _row(
        f"bsgs_hoisted N={n_lin} d={d} K={K} b={hoist_baby}",
        "bsgs_hoisted", 1, hoisted.plan.num_keyswitches, compile_s, lats,
        float(np.max(np.abs(got_h - want(x1)))),
        bool(np.argmax(got_h) == np.argmax(want(x1))),
        ntts=hoisted.hoisted_ntts,
    )
    rows.append(hoisted_row)
    unhoisted = hei.BsgsLinearScorer(
        ctx, W, b, hoist_gks, baby=hoist_baby, rotation_mode="unhoisted"
    )
    compile_s, lats, out_u = _measure(
        lambda: unhoisted.score(ct1), lambda o: (o.c0, o.c1), REPS
    )
    got_u = hei.decrypt_class_scores(ctx, sk, out_u, K)
    unhoisted_row = _row(
        f"bsgs_unhoisted N={n_lin} d={d} K={K} b={hoist_baby}",
        "bsgs_unhoisted", 1, unhoisted.plan.num_keyswitches, compile_s,
        lats,
        float(np.max(np.abs(got_u - want(x1)))),
        bool(np.argmax(got_u) == np.argmax(want(x1))),
        ntts=unhoisted.unhoisted_ntts,
    )
    rows.append(unhoisted_row)
    hoisted_cmp = {
        "plan": "bsgs",
        "baby": hoist_baby,
        "hoisted_qps": hoisted_row["qps"],
        "unhoisted_qps": unhoisted_row["qps"],
        "speedup": round(hoisted_row["qps"] / unhoisted_row["qps"], 3),
        "hoisted_ntts_per_score": hoisted.hoisted_ntts,
        "unhoisted_ntts_per_score": unhoisted.unhoisted_ntts,
        "parity_sha_hoisted": _parity_sha(out_h),
        "parity_sha_unhoisted": _parity_sha(out_u),
    }
    hoisted_cmp["parity"] = (
        hoisted_cmp["parity_sha_hoisted"]
        == hoisted_cmp["parity_sha_unhoisted"]
    )

    # Batched serving: queries packed q-per-ciphertext into slot blocks
    # (ISSUE 13 — the device program is unchanged, the diagonals tile) AND
    # batched across ciphertexts, so one dispatch scores q * B_ct queries.
    q = max(1, slots // max(d, K))
    while slots % q:
        q -= 1
    B_ct = max(1, B_lin // q)
    n_queries = q * B_ct
    xq = rng.normal(0, 0.5, (B_ct, q, d))
    packed = hei.BsgsLinearScorer(
        ctx, W, b, bsgs_gks, queries_per_ct=q
    )
    ct_q = hei.encrypt_query_block(ctx, pk, xq, jax.random.key(102), q)
    compile_s, lats, out = _measure(
        lambda: packed.score_many(ct_q), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_class_scores(ctx, sk, out, K, queries_per_ct=q)
    batched = _row(
        f"bsgs N={n_lin} d={d} K={K} q={q} B={n_queries}", "bsgs",
        n_queries, round(packed.plan.num_keyswitches / q, 2),
        compile_s, lats,
        float(np.max(np.abs(got - want(xq)))),
        bool(np.all(np.argmax(got, -1) == np.argmax(want(xq), -1))),
    )
    rows.append(batched)
    batched_vs_single = {
        "plan": "bsgs",
        "batch": n_queries,
        "queries_per_ct": q,
        "single_qps": single["qps"],
        "batched_qps": batched["qps"],
        "speedup": round(batched["qps"] / single["qps"], 3),
    }

    # --- Depth-2 MLP (square activation) --------------------------------
    n_mlp = 512 if SMOKE else 8192
    ctx2 = CkksContext.create(n=n_mlp, num_primes=5)
    certified.extend(c.summary() for c in check_inference(ctx2).values())
    sk2, pk2 = keygen(ctx2, jax.random.key(10))
    gks2 = hei.gen_rotation_keys(ctx2, sk2, jax.random.key(11))
    rlk2 = gen_relin_key(ctx2, sk2, jax.random.key(12))
    d2, H = (16, 4) if SMOKE else (64, 16)
    w1 = rng.normal(0, 0.3, (H, d2))
    b1 = rng.normal(0, 0.2, H)
    w2 = rng.normal(0, 0.3, (K, H))
    b2 = rng.normal(0, 0.2, K)
    mlp = hei.MlpScorer(ctx2, w1, b1, w2, b2, gks2, rlk2)
    sk_dec = hei.slice_secret_key(sk2, mlp.sub_ctx.num_primes)
    mlp_want = lambda xs: (  # noqa: E731
        (np.asarray(xs) @ w1.T + b1) ** 2
    ) @ w2.T + b2
    # H hidden-ladder key-switches per sample plus H relinearizations.
    mlp_ks = hei.ladder_keyswitches(encoding.num_slots(ctx2.ntt), H) + H

    xm = rng.normal(0, 0.4, d2)
    ctm = hei.encrypt_features(ctx2, pk2, xm, jax.random.key(110))
    compile_s, lats, out = _measure(
        lambda: mlp.score_batched(ctm), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_scores(
        mlp.sub_ctx, sk_dec,
        [hei.Ciphertext(c0=out.c0[k], c1=out.c1[k], scale=out.scale)
         for k in range(K)],
    )
    rows.append(_row(
        f"mlp N={n_mlp} d={d2} H={H} K={K}", "mlp", 1, mlp_ks,
        compile_s, lats,
        float(np.max(np.abs(got - mlp_want(xm)))),
        bool(np.argmax(got) == np.argmax(mlp_want(xm))),
    ))

    B_mlp = 2 if SMOKE else 8
    xms = rng.normal(0, 0.4, (B_mlp, d2))
    ctms = hei.encrypt_features(ctx2, pk2, xms, jax.random.key(111))
    compile_s, lats, out = _measure(
        lambda: mlp.score_many(ctms), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_score_matrix(mlp.sub_ctx, sk_dec, out)
    ladder_mlp_row = _row(
        f"mlp N={n_mlp} d={d2} H={H} K={K} B={B_mlp}", "mlp", B_mlp,
        mlp_ks, compile_s, lats,
        float(np.max(np.abs(got - mlp_want(xms)))),
        bool(np.all(np.argmax(got, -1) == np.argmax(mlp_want(xms), -1))),
    )
    rows.append(ladder_mlp_row)

    # Composed MLP BSGS (ISSUE 18): both linear layers as diagonal plans
    # on the hoisted path, ONE squaring, same depth budget. The unhoisted
    # twin runs once for the bitwise parity sha; ladder-vs-bsgs is the
    # serving comparison (different rotation sets, so those two agree only
    # after decryption).
    plan1, plan2 = hei.bsgs_mlp_plans(
        encoding.num_slots(ctx2.ntt), d2, H, K
    )
    mgks1 = hei.gen_rotation_keys_for_steps(
        ctx2, sk2, jax.random.key(13), plan1.rotation_steps_needed
    )
    msub = hei.mlp_sub_context(ctx2, 2)
    mgks2 = hei.gen_rotation_keys_for_steps(
        msub, hei.slice_secret_key(sk2, msub.num_primes),
        jax.random.key(14), plan2.rotation_steps_needed,
    )
    mlp_bsgs = hei.BsgsMlpScorer(
        ctx2, w1, b1, w2, b2, mgks1, rlk2, mgks2
    )
    compile_s, lats, out_mb = _measure(
        lambda: mlp_bsgs.score(ctm), lambda o: (o.c0, o.c1), REPS
    )
    got = hei.decrypt_class_scores(mlp_bsgs.sub_ctx, sk_dec, out_mb, K)
    mlp_bsgs_row = _row(
        f"mlp_bsgs N={n_mlp} d={d2} H={H} K={K}", "mlp_bsgs", 1,
        mlp_bsgs.num_keyswitches, compile_s, lats,
        float(np.max(np.abs(got - mlp_want(xm)))),
        bool(np.argmax(got) == np.argmax(mlp_want(xm))),
        ntts=mlp_bsgs.hoisted_ntts,
    )
    rows.append(mlp_bsgs_row)
    mlp_bsgs_u = hei.BsgsMlpScorer(
        ctx2, w1, b1, w2, b2, mgks1, rlk2, mgks2,
        rotation_mode="unhoisted",
    )
    out_mbu = mlp_bsgs_u.score(ctm)
    jax.block_until_ready((out_mbu.c0, out_mbu.c1))
    mlp_compare = {
        "plan": "mlp_bsgs",
        "ladder_qps": ladder_mlp_row["qps"] / ladder_mlp_row["batch"],
        "mlp_bsgs_qps": mlp_bsgs_row["qps"],
        "ladder_keyswitches_per_score": mlp_ks,
        "mlp_bsgs_keyswitches_per_score": mlp_bsgs.num_keyswitches,
        "hoisted_ntts_per_score": mlp_bsgs.hoisted_ntts,
        "unhoisted_ntts_per_score": mlp_bsgs.unhoisted_ntts,
        "parity_sha_hoisted": _parity_sha(out_mb),
        "parity_sha_unhoisted": _parity_sha(out_mbu),
    }
    mlp_compare["parity"] = (
        mlp_compare["parity_sha_hoisted"]
        == mlp_compare["parity_sha_unhoisted"]
    )

    print(f"# Private-inference serving bench ({backend.device_kind}, reps={REPS})")
    print()
    print("| config | plan | B | keyswitches/score | compile (s) | "
          "p50 (ms) | p95 (ms) | p99 (ms) | QPS | max |err| | argmax ok |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['row']} | {r['plan']} | {r['batch']} "
            f"| {r['keyswitches_per_score']} | {r['compile_s']} "
            f"| {r['p50_ms']} | {r['p95_ms']} | {r['p99_ms']} "
            f"| {r['qps']} | {r['max_abs_err']:.2e} | {r['argmax_ok']} |"
        )
    print()
    print(
        f"batched-vs-single ({batched_vs_single['plan']}, "
        f"B={batched_vs_single['batch']}): "
        f"{batched_vs_single['speedup']}x QPS"
    )
    print(
        f"hoisted-vs-unhoisted (bsgs): {hoisted_cmp['speedup']}x QPS, "
        f"{hoisted_cmp['hoisted_ntts_per_score']} vs "
        f"{hoisted_cmp['unhoisted_ntts_per_score']} forward NTTs/score, "
        f"parity={'OK' if hoisted_cmp['parity'] else 'BROKEN'}"
    )
    print(
        f"mlp ladder-vs-bsgs: {mlp_compare['ladder_keyswitches_per_score']}"
        f" vs {mlp_compare['mlp_bsgs_keyswitches_per_score']} "
        f"keyswitches/score, "
        f"parity={'OK' if mlp_compare['parity'] else 'BROKEN'}"
    )
    print()
    # The analysis evidence row (ISSUE 12/13): violations is the same
    # `analysis.violations` counter training artifacts embed — 0 here is
    # queryable proof the serving rings AND the key-switch gadget were
    # certified, not skipped.
    check_row = {
        "row": "analysis_check",
        "violations": int(
            obs_metrics.snapshot().get("analysis.violations", 0)
        ),
        "certified": certified,
    }
    for r in rows + [check_row]:
        print(json.dumps(r))

    artifact = {
        "artifact": "BENCH_INFER",
        "device": getattr(backend, "device_kind", str(backend)),
        "backend": jax.default_backend(),
        "smoke": SMOKE,
        "reps": REPS,
        "rows": rows,
        "batched_vs_single": batched_vs_single,
        "hoisted": hoisted_cmp,
        "mlp_compare": mlp_compare,
        "analysis_check": {
            "violations": check_row["violations"],
            "certified": certified,
        },
        "he_backend": he_backend_report(),
    }
    with open(ARTIFACT_PATH, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"artifact written to {ARTIFACT_PATH}")


if __name__ == "__main__":
    main()
