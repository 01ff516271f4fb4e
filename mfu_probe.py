"""Train-step MFU probe: is the MedCNN SGD step compute- or latency-bound?

VERDICT r3 next #7 asks either for a measured speedup of the steady train
phase or a trace-backed explanation of why MFU sits near 0.02. This harness
answers it directly: it times ONE jitted train step (grad + Adam, the exact
math `fl/client.py`'s train step runs inside its lax.scan) across a
batch-size ladder and reports images/s and MFU per point, using XLA's own
`cost_analysis()['flops']` for the numerator rather than a hand FLOP model.
Peak-FLOPs lookup and the MFU arithmetic come from
`hefl_tpu.utils.roofline` — the same module every bench/profile artifact
sources its MFU columns from.

The diagnostic logic: the reference trains at batch 32
(/root/reference/FLPyfhelin.py:184-196 via model.fit defaults in the driver).
If step latency is ~flat from batch 8 to 256 while images/s scales ~linearly,
the step is dispatch/bandwidth-latency bound at small batch and MFU at
batch 32 is a property of the problem size, not a kernel deficiency; if
images/s is flat, the step is compute-bound and worth kernel work.

Usage: python mfu_probe.py            (markdown table to stdout, mfu_probe.json)
       MFU_SMOKE=1 python mfu_probe.py   (CPU shakeout, tiny ladder)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def main() -> None:
    smoke = os.environ.get("MFU_SMOKE") == "1"
    import jax

    from hefl_tpu.utils.device import select_platform, setup_compile_cache

    select_platform("mfu_probe.py", cpu=smoke)
    import jax.numpy as jnp

    setup_compile_cache()

    from hefl_tpu.data.augment import backend_report, random_augment, rescale
    from hefl_tpu.fl.config import TrainConfig
    from hefl_tpu.fl.loss import loss_fn
    from hefl_tpu.fl.optimizer import adam_init, adam_update
    from hefl_tpu.models.cnn import MedCNN
    from hefl_tpu.utils import roofline

    dev = jax.devices()[0]
    kind = roofline.device_kind(dev)
    peak = roofline.peak_flops(dev)  # None on the CPU smoke: MFU is null
    print(
        f"device: {kind} (peak bf16 "
        + (f"~{peak / 1e12:.0f} TFLOP/s)" if peak else "not defined)"),
        file=sys.stderr,
    )

    module = MedCNN()
    cfg = TrainConfig()
    key = jax.random.key(0)
    hw = 256  # 6 pool stages need the full input; smaller collapses to 0
    params = module.init(key, jnp.zeros((1, hw, hw, 3), jnp.float32))["params"]

    ladder = [2, 4] if smoke else [8, 16, 32, 64, 128, 256]
    rows = []
    for bs in ladder:
        x_u8 = jnp.asarray(
            np.random.default_rng(0).integers(0, 256, (bs, hw, hw, 3), np.uint8)
        )
        y = jnp.asarray(np.random.default_rng(1).integers(0, 2, (bs,), np.int32))

        def step(p, opt, x_u8, y, k):
            xb = random_augment(
                k, rescale(x_u8), shear=cfg.aug_shear, zoom=cfg.aug_zoom,
                flip=cfg.aug_flip,
            )
            oh = jax.nn.one_hot(y, cfg.num_classes, dtype=jnp.float32)
            grads, _ = jax.grad(
                lambda q: loss_fn(module, q, xb, oh, p, cfg.prox_mu), has_aux=True
            )(p)
            return adam_update(grads, opt, p, cfg.lr, cfg.lr_decay, jnp.float32(1.0))

        opt = adam_init(params)
        # ONE compile per ladder point: AOT-compile the donated jit and use
        # the compiled object for both cost analysis and the timed loop (a
        # second donation-free jit would recompile the whole step just to
        # read its FLOP count).
        compiled = (
            jax.jit(step, donate_argnums=(0, 1))
            .lower(params, opt, x_u8, y, key)
            .compile()
        )
        flops = roofline.program_flops(compiled=compiled) or 0.0
        jstep = compiled

        p, o = jax.tree_util.tree_map(jnp.copy, (params, opt))
        for _ in range(2):  # warmup
            p, o = jstep(p, o, x_u8, y, key)
        jax.block_until_ready(p)
        reps = 1 if smoke else 30
        t0 = time.perf_counter()
        for _ in range(reps):
            p, o = jstep(p, o, x_u8, y, key)
        jax.block_until_ready(p)
        dt = (time.perf_counter() - t0) / reps
        rows.append(
            {
                "batch": bs,
                "step_ms": round(dt * 1e3, 3),
                "images_per_s": round(bs / dt, 1),
                "xla_flops": flops,
                "mfu": roofline.mfu(flops, dt, dev),
            }
        )
        print(f"  batch {bs}: {dt * 1e3:.2f} ms", file=sys.stderr)

    print("| batch | step (ms) | images/s | XLA GFLOP/step | MFU |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['batch']} | {r['step_ms']:.3f} | {r['images_per_s']:.0f} "
            f"| {r['xla_flops'] / 1e9:.1f} | "
            + ("null |" if r["mfu"] is None else f"{r['mfu']:.4f} |")
        )
    lat = rows[0]["step_ms"]
    big = rows[-1]["step_ms"]
    verdict = (
        "latency-bound at small batch (step time grows "
        f"{big / lat:.1f}x over a {rows[-1]['batch'] // rows[0]['batch']}x "
        "batch ladder)"
        if big / lat < rows[-1]["batch"] / rows[0]["batch"] / 2
        else "compute-bound (step time tracks batch size)"
    )
    print(f"\nverdict: {verdict}")
    with open("mfu_probe.json", "w") as f:
        json.dump(
            {
                "device": kind,
                "peak_flops": peak,
                "augment_backend": backend_report(),
                "rows": rows,
                "verdict": verdict,
            },
            f,
            indent=2,
        )


if __name__ == "__main__":
    main()
