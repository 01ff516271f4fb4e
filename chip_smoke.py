"""Chip smoke: the encrypted federated round, end to end, on a TPU.

The quickest proof that the system still starts on the chip. ONE process
imports JAX once, holds the chip throughout and starts no child that needs
a device. It drives the main path through the entry points a user calls
(`run_experiment`, `StreamEngine` via `run_experiment(stream=...)`, the
BSGS scorers) at the full width of the models the repo supports, checks
every result by the repo's own means, and fails — non-zero exit, no
`"ok": true` line — when JAX finds no TPU or any phase fails.

    python chip_smoke.py             one chip: device, kernels, flagship,
                                     stream, serve
    python chip_smoke.py --chips 4   four chips: ONLY the cross-chip phase
                                     (psum_mod, 1-D vs 2x2 mesh rounds,
                                     per-device placement)

Each phase prints one JSON object per line; the last line of stdout is
exactly `{"ok": true, "device": {"platform", "kind", "count"}}`. Widths are
never cut; images, epochs and rounds may be, and every cut is printed.
The wall-clock and micro-timing fields are smoke output for the next
builder, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import hashlib
import json
import os
import sys
import tempfile
import time

import numpy as np

# The verify skill's yardstick for an encrypted average against the plain
# mean of the same trained weights (float32 decode of a 2**30-scale CKKS
# aggregate). The scorers state no error bound of their own: their tests
# hold toy shapes (|score| < 1) to 0.05 absolute, so a score here is held
# to 0.05 of the largest plaintext score (at least 1) plus an equal argmax.
# At the serving shape the composed MLP's error is 0.080 absolute on a
# largest score of 3.29 — identical on the CPU and on the chip.
ENC_AVG_YARDSTICK = 5e-6
SCORE_TOLERANCE = 0.05


def require(ok, why="") -> None:
    """A phase's check: raises (and so fails the run) even under `-O`."""
    if not ok:
        raise AssertionError(str(why) or "chip_smoke check failed")


def _score_check(what: str, got, want) -> tuple[float, float]:
    err = float(np.max(np.abs(got - want)))
    bound = SCORE_TOLERANCE * max(1.0, float(np.max(np.abs(want))))
    require(err <= bound, f"{what} score error {err:.3e} > {bound:.3e}")
    require(int(np.argmax(got)) == int(np.argmax(want)), what)
    return err, bound


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _sha(tree) -> str:
    import jax

    return hashlib.sha256(b"".join(
        np.ascontiguousarray(np.asarray(leaf)).tobytes()
        for leaf in jax.tree_util.tree_leaves(tree)
    )).hexdigest()


def _cut_record(cuts: dict) -> dict:
    """The cuts as printed: a config dataclass shows the fields that
    differ from its defaults."""
    out = {}
    for name, val in cuts.items():
        if dataclasses.is_dataclass(val):
            ref = type(val)()
            out[name] = {
                f.name: getattr(val, f.name)
                for f in dataclasses.fields(val)
                if getattr(val, f.name) != getattr(ref, f.name)
            }
        else:
            out[name] = val
    return out


def _max_abs_diff(a, b) -> float:
    import jax

    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


# --------------------------------------------------------------------------
# kernels: every Pallas entry point vs its XLA twin, at production shapes
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One Pallas entry point at one ring: `kernel(interpret, *args)` and
    its XLA twin `reference(*args)` over the same arguments. `shapes`
    names each argument's shape and kind ("res": canonical residues mod
    the ring's primes, limb axis second to last; "word": raw 31-bit
    words with no limb axis)."""

    name: str
    ctx: object
    kernel: object
    reference: object
    shapes: tuple


def kernel_cases() -> list[KernelCase]:
    """The seven kernel entry points at the training ring (N=4096, 3
    primes: [55|8|14, 3, 4096] rows, [19, 3, 4096] gadget keys) plus the
    two serving-path kernels at the serving ring (N=8192, 5 primes).
    Shared with tests/test_tpu_compile.py, which compiles each for a
    described chip."""
    from hefl_tpu.ckks import ntt as ntt_mod
    from hefl_tpu.ckks import ops, pallas_ntt
    from hefl_tpu.ckks.keys import CkksContext, SecretKey
    from hefl_tpu.hhe import transcipher as hhe_tc

    def ring_cases(ctx, tag, only=None):
        nt, num_l, n = ctx.ntt, ctx.num_primes, ctx.n
        d = ctx.ksk_num_digits
        ks = dict(digit_bits=ctx.ksk_digit_bits, num_digits=d)
        num_c, num_r, num_s = num_l * d + 1, num_l * d, 8
        row, key = (num_l, n), (num_c, num_l, n)
        big, ksb, tc = (55, *row), (8, *row), (14, *row)
        cases = {
            "ntt_forward": (
                lambda i, a: pallas_ntt.ntt_forward_pallas(nt, a, interpret=i),
                lambda a: ntt_mod.ntt_forward(nt, a),
                ((big, "res"),),
            ),
            "ntt_inverse": (
                lambda i, a: pallas_ntt.ntt_inverse_pallas(nt, a, interpret=i),
                lambda a: ntt_mod.ntt_inverse(nt, a),
                ((big, "res"),),
            ),
            "encrypt_fused": (
                lambda i, *a: pallas_ntt.encrypt_fused_pallas(
                    nt, *a, interpret=i),
                lambda *a: ops._encrypt_core_xla(ctx, *a),
                ((big, "res"),) * 4 + ((row, "res"),) * 2,
            ),
            "decrypt_fused": (
                lambda i, c0, c1, s: pallas_ntt.decrypt_fused_pallas(
                    nt, c0, c1, s, interpret=i),
                lambda c0, c1, s: ops.decrypt(
                    ctx, SecretKey(s_mont=s),
                    ops.Ciphertext(c0=c0, c1=c1, scale=ctx.scale)),
                ((big, "res"),) * 2 + ((row, "res"),),
            ),
            "keyswitch_fused": (
                lambda i, x, b, a: pallas_ntt.keyswitch_fused_pallas(
                    nt, x, b, a, interpret=i, **ks),
                lambda x, b, a: ops._keyswitch_coeff_xla(ctx, x, b, a),
                ((ksb, "res"),) + ((key, "res"),) * 2,
            ),
            "keyswitch_fused_eval_input": (
                lambda i, x, b, a: pallas_ntt.keyswitch_fused_pallas(
                    nt, x, b, a, eval_input=True, interpret=i, **ks),
                lambda x, b, a: ops._keyswitch_coeff_xla(
                    ctx, ntt_mod.ntt_inverse(nt, x), b, a),
                ((ksb, "res"),) + ((key, "res"),) * 2,
            ),
            "hoisted_rotations": (
                lambda i, *a: pallas_ntt.hoisted_rotations_pallas(
                    nt, *a, interpret=i),
                lambda *a: ops._hoisted_products_xla(ctx, *a),
                ((row, "res"), ((num_r, *row), "res"))
                + (((num_s, num_r, *row), "res"),) * 2,
            ),
            "transcipher_fused": (
                lambda i, *a: pallas_ntt.transcipher_fused_pallas(
                    nt, *a, interpret=i),
                lambda *a: hhe_tc._transcipher_core_xla(nt, *a),
                (((14, n), "word"),) * 2 + ((tc, "res"),) * 2,
            ),
        }
        return [
            KernelCase(f"{name}@{tag}", ctx, k, r, shapes)
            for name, (k, r, shapes) in cases.items()
            if only is None or name in only
        ]

    return ring_cases(CkksContext.create(), "n4096x3") + ring_cases(
        CkksContext.create(n=8192, num_primes=5), "n8192x5",
        only=("keyswitch_fused_eval_input", "hoisted_rotations"),
    )


def _case_args(case: KernelCase, seed: int) -> list:
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    p = np.asarray(case.ctx.ntt.p)[:, 0].astype(np.int64)[:, None]
    args = []
    for shape, kind in case.shapes:
        raw = rng.integers(0, 1 << 31, size=shape, dtype=np.int64)
        args.append(jnp.asarray(
            (raw % p if kind == "res" else raw).astype(np.uint32)
        ))
    return args


@contextlib.contextmanager
def _xla_reference_pins():
    """Trace a reference on the stage-unrolled XLA graph. The NTT and HE
    selectors are read per call at trace time; on a TPU their `auto`
    default would route the twin's transforms through the very kernels it
    is the reference for."""
    from hefl_tpu.ckks import backend as he_backend
    from hefl_tpu.ckks import ntt as ntt_mod

    prev = ntt_mod._BACKEND, he_backend._ENV
    ntt_mod._BACKEND, he_backend._ENV = "xla", "xla"
    try:
        yield
    finally:
        ntt_mod._BACKEND, he_backend._ENV = prev


def phase_kernels() -> None:
    import jax

    t0 = time.perf_counter()
    results = {}
    for idx, case in enumerate(kernel_cases()):
        args = _case_args(case, seed=idx)
        # interpret=None: the normal resolution, which on a TPU must reach
        # Mosaic — the lowered text then holds the kernel's custom call.
        kern = jax.jit(lambda *a, _c=case: _c.kernel(None, *a))
        text = kern.lower(*args).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(
                f"{case.name}: no tpu_custom_call in the lowered program — "
                "the kernel would run interpreted"
            )
        got = jax.tree_util.tree_leaves(kern(*args))
        with _xla_reference_pins():
            want = jax.tree_util.tree_leaves(jax.jit(case.reference)(*args))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=case.name
            )
        results[case.name] = "bitwise_equal"
    emit("kernels", cases=results, mosaic=True,
         seconds=round(time.perf_counter() - t0, 2))


# --------------------------------------------------------------------------
# flagship: the reference experiment through run_experiment
# --------------------------------------------------------------------------


def _flagship_config(events_path: str, **cuts):
    """`hefl_tpu/flagship.py`'s configuration as an ExperimentConfig:
    MedCNN, 256x256x3 synthetic medical images, batch 32, 10 epochs with
    the 2-epoch warmup, N=4096 CKKS, 2 clients, encrypted."""
    from hefl_tpu.experiment import ExperimentConfig, HEConfig
    from hefl_tpu.fl import TrainConfig

    cfg = ExperimentConfig(
        model="medcnn", dataset="medical", num_clients=2, rounds=3,
        encrypted=True, train=TrainConfig(warmup_steps=44), he=HEConfig(),
        seed=0, events_path=events_path,
    )
    return dataclasses.replace(cfg, **cuts)


def _rounds_from_events(events_path: str) -> list[dict]:
    """Per round, from the run's own event log: executables created while
    it ran, how many of those were loaded from the persistent cache, and
    the programs that were really compiled and took over a second (the
    cache's own threshold — on a warm cache this list is empty)."""
    from hefl_tpu.obs import events as obs_events

    def fresh() -> dict:
        return {"new_executables": 0, "persistent_cache_hits": 0,
                "compiled_over_1s": []}

    rounds, cur = [], fresh()
    for ev in obs_events.read_events(events_path):
        kind = ev.get("event")
        if kind == "experiment_start":
            rounds, cur = [], fresh()
        elif kind == "compile":
            cur["new_executables"] += 1
            if ev.get("cache_hit"):
                cur["persistent_cache_hits"] += 1
            elif ev.get("seconds", 0.0) >= 1.0:
                cur["compiled_over_1s"].append(
                    [ev.get("fun_name"), ev.get("seconds")]
                )
        elif kind == "round_end":
            rounds.append(cur)
            cur = fresh()
    return rounds


def phase_flagship(workdir: str, expect_params: int = 222_722, **cuts) -> None:
    import jax
    import jax.numpy as jnp

    from hefl_tpu.ckks.backend import he_backend_report
    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.experiment import run_experiment
    from hefl_tpu.fl import decrypt_average, secure_fedavg_round
    from hefl_tpu.models import count_params, create_model
    from hefl_tpu.parallel import client_sharding, make_mesh

    events_path = os.path.join(workdir, "flagship_events.jsonl")
    cfg = _flagship_config(events_path, **cuts)
    t0 = time.perf_counter()
    out = run_experiment(cfg, verbose=False)
    run_s = time.perf_counter() - t0
    history = out["history"]
    n_params = count_params(out["params"])
    require(expect_params in (None, n_params), n_params)
    require(out["packing"] is None and len(history) == cfg.rounds)
    per_round = _rounds_from_events(events_path)
    require(len(per_round) == cfg.rounds, per_round)
    rounds = []
    for rec, comp in zip(history, per_round):
        overflow = int(np.sum(rec["encode_overflow"]))
        require(overflow == 0, f"round {rec['round']}: encode_overflow {overflow}")
        require(np.isfinite(rec["accuracy"]) and np.all(
            np.isfinite(rec["val_loss"])), rec)
        rounds.append({
            "round": rec["round"],
            # PhaseTimer seconds; each phase ends in block_until_ready.
            "seconds": {k: round(v, 3) for k, v in rec["phases"].items()},
            "accuracy": rec["accuracy"],
            "val_loss": rec["val_loss"],
            "encode_overflow": overflow,
            **comp,
        })
    require(rounds[-1]["new_executables"] == 0, (
        "the last round created executables: " + json.dumps(rounds[-1])
    ))

    # Fidelity: run_experiment has no with_plain_reference mode, so one
    # more round on the same mesh, context and keys (same derivation:
    # key(seed) -> split -> keygen), decrypted against the in-program
    # plain mean of the SAME trained weights.
    (x, y), _, _ = make_dataset(
        cfg.dataset, seed=cfg.seed, n_train=cfg.n_train, n_test=cfg.n_test
    )
    module, proto = create_model(
        cfg.model, num_classes=cfg.train.num_classes,
        input_shape=tuple(int(d) for d in x.shape[1:]),
    )
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), cfg.num_clients))
    mesh = make_mesh(cfg.num_clients)
    place = client_sharding(mesh)
    xs_d, ys_d = jax.device_put(xs, place), jax.device_put(ys, place)
    ctx = cfg.he.build()
    _, k_he = jax.random.split(jax.random.key(cfg.seed))
    sk, pk = keygen(ctx, k_he)
    spec = PackSpec.for_params(proto, ctx.n)
    t0 = time.perf_counter()
    ct, _, ov, plain_ref = secure_fedavg_round(
        module, cfg.train, mesh, ctx, pk, out["params"], xs_d, ys_d,
        jax.random.fold_in(jax.random.key(cfg.seed), 1000),
        with_plain_reference=True,
    )
    enc_avg = decrypt_average(ctx, sk, ct, cfg.num_clients, spec)
    jax.block_until_ready(enc_avg)
    err = _max_abs_diff(enc_avg, plain_ref)
    require(int(np.sum(np.asarray(ov))) == 0)
    require(err <= ENC_AVG_YARDSTICK, f"decrypt vs plain mean: {err:.3e}")
    require(all(
        bool(jnp.all(jnp.isfinite(leaf)))
        for leaf in jax.tree_util.tree_leaves(enc_avg)
    ))
    emit(
        "flagship", model=cfg.model, params=n_params,
        image=list(x.shape[1:]), images_train=int(len(x)),
        clients=cfg.num_clients, epochs=cfg.train.epochs,
        batch=cfg.train.batch_size, n_ct=spec.n_ct, ring_n=ctx.n,
        cuts=_cut_record(cuts),
        rounds=rounds, run_experiment_seconds=round(run_s, 2),
        fidelity={"decrypt_vs_plain_max_abs": err,
                  "yardstick": ENC_AVG_YARDSTICK,
                  "seconds": round(time.perf_counter() - t0, 2)},
        selections={"he_backend": he_backend_report(),
                    "augment_backend": out["augment_backend"],
                    "client_fusion": out["client_fusion"]},
    )


# --------------------------------------------------------------------------
# stream: the packed b=8 uplink through StreamEngine, direct vs HHE twin
# --------------------------------------------------------------------------


def phase_stream(**cuts) -> None:
    from hefl_tpu.experiment import ExperimentConfig, HEConfig, run_experiment
    from hefl_tpu.fl import PackingConfig, StreamConfig, TrainConfig
    from hefl_tpu.hhe import HheConfig

    base = dict(
        model="medcnn", dataset="medical", num_clients=2, encrypted=True,
        he=HEConfig(), seed=0, events_path="",
        train=TrainConfig(warmup_steps=44),
        # bench.py's packed flagship geometry: 55 -> 14 ciphertext rows.
        packing=PackingConfig(bits=8, interleave=4, clip=0.5),
        stream=StreamConfig(quorum=1.0),   # cohort-only training: default
    )
    base.update(cuts)
    direct_cfg = ExperimentConfig(**base)
    hhe_cfg = dataclasses.replace(
        direct_cfg,
        stream=dataclasses.replace(direct_cfg.stream, upload_kind="hhe"),
        hhe=HheConfig(key_seed=0),
    )
    t0 = time.perf_counter()
    direct = run_experiment(direct_cfg, verbose=False)
    t1 = time.perf_counter()
    hrun = run_experiment(hhe_cfg, verbose=False)
    t2 = time.perf_counter()
    sha_d, sha_h = _sha(direct["params"]), _sha(hrun["params"])
    require(sha_d == sha_h, f"HHE twin differs: {sha_h[:16]} != {sha_d[:16]}")
    wire = hrun["hhe"]
    require(wire["expansion_hhe"] <= 1.1, wire)
    want = direct_cfg.num_clients * direct_cfg.rounds
    got = hrun["obs"]["metrics"].get("hhe.uploads_transciphered", 0)
    require(got == want, f"uploads transciphered {got} != {want}")
    for run in (direct, hrun):
        require(all(h["stream"]["committed"] for h in run["history"]))
        require(np.isfinite(run["final_metrics"]["accuracy"]))
    emit(
        "stream", model=direct_cfg.model,
        packing=direct["packing"], cohort_only=direct_cfg.stream.cohort_only,
        cuts=_cut_record(cuts),
        params_sha256=sha_d, sha256_equal=True,
        expansion_hhe=wire["expansion_hhe"],
        reduction_vs_ckks=wire["reduction_vs_ckks"],
        uploads_transciphered=got,
        seconds={"direct": round(t1 - t0, 2), "hhe": round(t2 - t1, 2)},
    )


# --------------------------------------------------------------------------
# serve: one encrypted score through each BSGS scorer
# --------------------------------------------------------------------------


def phase_serve(n_linear: int = 4096, n_mlp: int = 8192,
                mlp_shape: tuple = (64, 16)) -> None:
    """bench_inference.py's non-smoke shapes: a d = slots/4, K = 10 linear
    layer at N=4096 and a 64 -> 16 -> 10 squared-activation MLP at
    N=8192 / 5 primes; `rotation_mode` left at its default."""
    import jax

    from hefl_tpu import he_inference as hei
    from hefl_tpu.ckks import encoding
    from hefl_tpu.ckks.keys import CkksContext, gen_relin_key, keygen

    rng = np.random.default_rng(42)
    num_k = 10
    t0 = time.perf_counter()
    ctx = CkksContext.create(n=n_linear)
    sk, pk = keygen(ctx, jax.random.key(0))
    slots = encoding.num_slots(ctx.ntt)
    d = slots // 4
    w, b = rng.normal(0, 0.3, (num_k, d)), rng.normal(0, 0.2, num_k)
    x1 = rng.normal(0, 0.5, d)
    plan = hei.bsgs_plan(slots, d, num_k)
    gks = hei.gen_rotation_keys_for_steps(
        ctx, sk, jax.random.key(2), plan.rotation_steps_needed
    )
    scorer = hei.BsgsLinearScorer(ctx, w, b, gks)
    t_keys = time.perf_counter()
    out = scorer.score(hei.encrypt_features(ctx, pk, x1, jax.random.key(100)))
    got = hei.decrypt_class_scores(ctx, sk, out, num_k)
    want = x1 @ w.T + b
    lin_err, lin_bound = _score_check("linear", got, want)
    t1 = time.perf_counter()

    ctx2 = CkksContext.create(n=n_mlp, num_primes=5)
    sk2, pk2 = keygen(ctx2, jax.random.key(10))
    rlk2 = gen_relin_key(ctx2, sk2, jax.random.key(12))
    d2, hidden = mlp_shape
    w1, b1 = rng.normal(0, 0.3, (hidden, d2)), rng.normal(0, 0.2, hidden)
    w2, b2 = rng.normal(0, 0.3, (num_k, hidden)), rng.normal(0, 0.2, num_k)
    xm = rng.normal(0, 0.4, d2)
    plan1, plan2 = hei.bsgs_mlp_plans(
        encoding.num_slots(ctx2.ntt), d2, hidden, num_k
    )
    gks1 = hei.gen_rotation_keys_for_steps(
        ctx2, sk2, jax.random.key(13), plan1.rotation_steps_needed
    )
    sub = hei.mlp_sub_context(ctx2, 2)
    sk_sub = hei.slice_secret_key(sk2, sub.num_primes)
    gks2 = hei.gen_rotation_keys_for_steps(
        sub, sk_sub, jax.random.key(14), plan2.rotation_steps_needed
    )
    mlp = hei.BsgsMlpScorer(ctx2, w1, b1, w2, b2, gks1, rlk2, gks2)
    t_keys2 = time.perf_counter()
    out = mlp.score(hei.encrypt_features(ctx2, pk2, xm, jax.random.key(110)))
    sk_dec = hei.slice_secret_key(sk2, mlp.sub_ctx.num_primes)
    got = hei.decrypt_class_scores(mlp.sub_ctx, sk_dec, out, num_k)
    want = ((xm @ w1.T + b1) ** 2) @ w2.T + b2
    mlp_err, mlp_bound = _score_check("mlp", got, want)
    t_end = time.perf_counter()
    emit(
        "serve", tolerance_of_largest_score=SCORE_TOLERANCE,
        linear={"ring_n": n_linear, "d": d, "classes": num_k,
                "keyswitches": scorer.plan.num_keyswitches,
                "max_abs_err": lin_err, "bound": lin_bound,
                "seconds": {"keys_and_plan": round(t_keys - t0, 2),
                            "encrypt_score_decrypt": round(t1 - t_keys, 2)}},
        mlp={"ring_n": n_mlp, "primes": 5, "shape": [d2, hidden, num_k],
             "keyswitches": mlp.num_keyswitches,
             "max_abs_err": mlp_err, "bound": mlp_bound,
             "seconds": {"keys_and_plan": round(t_keys2 - t1, 2),
                         "encrypt_score_decrypt": round(t_end - t_keys2, 2)}},
    )


# --------------------------------------------------------------------------
# --chips 4: what exists only across chips
# --------------------------------------------------------------------------


def _bytes_in_use(devices) -> list:
    return [
        (dev.memory_stats() or {}).get("bytes_in_use") for dev in devices
    ]


def phase_multichip(n_chips: int = 4, model: str = "medcnn",
                    dataset: str = "medical", expect_params: int = 222_722,
                    per_client: int = 128, epochs: int = 1,
                    ring_n: int = 4096) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hefl_tpu.ckks.keys import CkksContext, keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl import TrainConfig, decrypt_average, secure_fedavg_round
    from hefl_tpu.models import count_params, create_model
    from hefl_tpu.parallel import (
        CLIENT_AXIS, client_sharding, make_mesh, make_mesh_2d, shard_map,
    )
    from hefl_tpu.parallel.collectives import psum_mod

    devs = jax.devices()[:n_chips]
    ctx = CkksContext.create(n=ring_n)

    # (1) psum_mod over the real interconnect vs the host modular sum.
    mesh = make_mesh(n_chips)
    require(dict(mesh.shape) == {CLIENT_AXIS: n_chips}, mesh.shape)
    p_col = np.asarray(ctx.ntt.p)
    rng = np.random.default_rng(0)
    res = (rng.integers(0, 1 << 31, (n_chips, 55, ctx.num_primes, ctx.n),
                        dtype=np.int64) % p_col.astype(np.int64)
           ).astype(np.uint32)
    summed = jax.jit(shard_map(
        lambda r: psum_mod(r[0], jnp.asarray(p_col), CLIENT_AXIS),
        mesh, P(CLIENT_AXIS), P(),
    ))(jax.device_put(res, client_sharding(mesh)))
    host = (res.astype(np.uint64).sum(0) % p_col.astype(np.uint64))
    np.testing.assert_array_equal(np.asarray(summed), host.astype(np.uint32))
    emit("psum_mod", devices=n_chips, shape=list(res.shape[1:]),
         bitwise_equal_host_sum=True)

    # (2) one n-client encrypted round at full model width per topology.
    num_clients = n_chips
    (x, y), _, _ = make_dataset(
        dataset, seed=0, n_train=num_clients * per_client, n_test=8
    )
    cfg = TrainConfig(warmup_steps=4, epochs=epochs)
    module, params = create_model(
        model, num_classes=cfg.num_classes,
        input_shape=tuple(int(d) for d in x.shape[1:]),
        rng=jax.random.key(123),
    )
    n_params = count_params(params)
    require(expect_params in (None, n_params), n_params)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), num_clients))
    sk, pk = keygen(ctx, jax.random.key(99))
    spec = PackSpec.for_params(params, ctx.n)
    key = jax.random.key(5)

    # Placement: where the federated arrays live before any round runs.
    base = _bytes_in_use(devs)
    default_put = jax.block_until_ready(jax.device_put(jnp.asarray(xs)))
    after_default = _bytes_in_use(devs)
    default_put.delete()
    def placed(before, after) -> dict:
        return {
            "bytes_in_use": after,
            "delta": [
                None if a is None else a - b for a, b in zip(after, before)
            ],
        }

    placement = {
        "federated_bytes": int(xs.nbytes),
        # What `jax.device_put(jnp.asarray(a))` (no sharding) does.
        "default_device_put": placed(base, after_default),
    }

    topologies = {
        f"1d_{n_chips}": make_mesh(num_clients),
        "2x2": make_mesh_2d(num_clients, 2),
        "1d_2": make_mesh(num_clients, devices=devs[:2]),
        "1d_1": make_mesh(num_clients, devices=devs[:1]),
    }
    require(dict(topologies["2x2"].shape) == {CLIENT_AXIS: n_chips // 2, "ct": 2})
    runs = {}
    for name, mesh in topologies.items():
        place = client_sharding(mesh)
        before = _bytes_in_use(devs)
        xs_d, ys_d = jax.device_put(xs, place), jax.device_put(ys, place)
        jax.block_until_ready((xs_d, ys_d))
        placement[f"client_sharding_{name}"] = placed(
            before, _bytes_in_use(devs)
        )
        t0 = time.perf_counter()
        ct, _, ov, plain_ref = secure_fedavg_round(
            module, cfg, mesh, ctx, pk, params, xs_d, ys_d, key,
            with_plain_reference=True,
        )
        dec = decrypt_average(ctx, sk, ct, num_clients, spec)
        jax.block_until_ready(dec)
        err = _max_abs_diff(dec, plain_ref)
        require(int(np.sum(np.asarray(ov))) == 0)
        require(err <= ENC_AVG_YARDSTICK, f"{name}: decrypt vs plain {err:.3e}")
        runs[name] = {
            "c0": np.asarray(ct.c0), "c1": np.asarray(ct.c1), "dec": dec,
            "record": {
                "mesh": {k: int(v) for k, v in mesh.shape.items()},
                "decrypt_vs_plain_max_abs": err,
                "seconds_with_compile": round(time.perf_counter() - t0, 2),
            },
        }
        xs_d.delete()
        ys_d.delete()

    def same(a: str, b: str) -> bool:
        return bool(
            np.array_equal(runs[a]["c0"], runs[b]["c0"])
            and np.array_equal(runs[a]["c1"], runs[b]["c1"])
        )

    flat = f"1d_{n_chips}"
    # The structural equality (PR 15): the ct axis shards the encrypt rows
    # of the SAME client layout, so 2x2 equals the 1-D two-row mesh bit
    # for bit. Against a different per-device client count the trained
    # floats may differ (a vmap width of 1 lowers ungrouped), so those
    # pairs are reported and held to the error bound above only.
    require(same("2x2", "1d_2"), "2x2 aggregate differs from its 1-D layout")
    emit(
        "multichip_round", model=model, params=n_params,
        clients=num_clients, ring_n=ctx.n, n_ct=spec.n_ct,
        cuts={"images_per_client": per_client, "epochs": epochs},
        topologies={k: v["record"] for k, v in runs.items()},
        bitwise_equal={
            "2x2_vs_1d_2": True,
            f"2x2_vs_{flat}": same("2x2", flat),
            f"1d_1_vs_{flat}": same("1d_1", flat),
        },
        decrypted_max_abs_vs_one_device={
            k: _max_abs_diff(runs[k]["dec"], runs["1d_1"]["dec"])
            for k in (flat, "2x2")
        },
        yardstick=ENC_AVG_YARDSTICK,
    )
    emit("placement", **placement)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the cross-chip phase")
    args = ap.parse_args(argv)

    import jax

    from hefl_tpu import native
    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.utils.device import (
        compile_cache_dir, select_platform, setup_compile_cache,
    )

    select_platform("chip_smoke.py", cpu=False)   # exits 1 without a TPU
    devs = jax.devices()
    dev = devs[0]
    if len(devs) < args.chips:
        print(f"chip_smoke.py: needs {args.chips} TPU chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    setup_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    emit("device", **device, compile_cache=compile_cache_dir(),
         native_crt_decoder=native.available(), jax=jax.__version__)
    if args.chips == 4:
        phase_multichip(4)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
            phase_kernels()
            phase_flagship(workdir)
            # Cuts (width untouched): 2 x 128 training images, 2 local
            # epochs, 2 rounds per twin.
            phase_stream(
                n_train=256, n_test=64, rounds=2,
                train=TrainConfig(warmup_steps=4, epochs=2),
            )
            phase_serve()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
