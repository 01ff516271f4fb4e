"""Flagship-shape HE-fidelity evidence, multi-seed, device-independent.

The full same-program fidelity artifact (bench.py cell-6,
`with_plain_reference`) needs a trained flagship model and therefore a
chip. This harness pins the HE PATH's fidelity at the exact
flagship shapes without the training: for each seed it packs a
MedCNN-sized parameter pytree (222,722 weights -> 55 ciphertexts at
N=4096) of realistic magnitude (|w| <= ~0.75, matching the committed
max_abs_trained_weight of real runs), encrypts per client, aggregates by
homomorphic sum, decrypts the average, and compares against the plaintext
mean. Encoder-saturation counts are asserted zero.

What this does and does not claim: it measures encode+encrypt+sum+decrypt
+decode error at flagship scale — the whole cryptographic path — on any
backend (accuracy of the TRAINED model is a separate, training-dependent
question that bench.py answers). Reference counterpart: the notebook's
plaintext-vs-encrypted spot check (`Encrypted FL Main-Rel.ipynb` cell 6,
FLPyfhelin.py:382-389), generalized to multi-seed and exact statistics.

Usage: python fidelity_check.py    (markdown + fidelity_check.json;
       FIDELITY_PLATFORM=cpu pins the host CPU; otherwise a TPU is
       required)
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    import jax

    from hefl_tpu.utils.device import select_platform, setup_compile_cache

    select_platform(
        "fidelity_check.py", cpu=os.environ.get("FIDELITY_PLATFORM") == "cpu"
    )
    import jax.numpy as jnp
    import numpy as np

    setup_compile_cache()

    from hefl_tpu.ckks import ops
    from hefl_tpu.ckks.encoding import encode_overflow_count
    from hefl_tpu.ckks.keys import CkksContext, keygen
    from hefl_tpu.ckks.packing import PackSpec, pack_pytree
    from hefl_tpu.fl import aggregate_encrypted, decrypt_average, encrypt_params
    from hefl_tpu.models import count_params, create_model

    num_clients = 2
    ctx = CkksContext.create()           # flagship params: N=4096, L=3
    dev = jax.devices()[0]
    rows = []
    for seed in (0, 1, 2):
        module, proto = create_model("medcnn", rng=jax.random.key(seed + 123))
        assert count_params(proto) == 222_722
        spec = PackSpec.for_params(proto, ctx.n)
        assert spec.n_ct == 55
        sk, pk = keygen(ctx, jax.random.key(1000 + seed))
        # Realistic trained-magnitude weights: init * 3 + bias offsets gives
        # |w| up to ~0.7 with full mantissas (harder than round numbers).
        rng = np.random.default_rng(seed)
        trees = []
        for c in range(num_clients):
            t = jax.tree_util.tree_map(
                lambda x: jnp.asarray(
                    rng.normal(0.0, 0.15, x.shape).astype(np.float32)
                    * 3.0
                ).clip(-0.75, 0.75),
                proto,
            )
            trees.append(t)
        cts = [
            encrypt_params(ctx, pk, t, jax.random.key(2000 + seed * 10 + c))
            for c, t in enumerate(trees)
        ]
        stacked = ops.Ciphertext(
            c0=jnp.stack([c.c0 for c in cts]),
            c1=jnp.stack([c.c1 for c in cts]),
            scale=cts[0].scale,
        )
        ct_sum = aggregate_encrypted(ctx, stacked)
        avg = decrypt_average(ctx, sk, ct_sum, num_clients, spec)
        avg_exact = decrypt_average(
            ctx, sk, ct_sum, num_clients, spec, exact=True
        )
        expect = jax.tree_util.tree_map(
            lambda *xs: sum(xs) / num_clients, *trees
        )
        diff = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(avg),
                jax.tree_util.tree_leaves(expect),
            )
        )
        diff_exact = max(
            float(jnp.max(jnp.abs(jnp.asarray(a) - b)))
            for a, b in zip(
                jax.tree_util.tree_leaves(avg_exact),
                jax.tree_util.tree_leaves(expect),
            )
        )
        overflow = sum(
            int(encode_overflow_count(pack_pytree(t, ctx.n), ctx.scale))
            for t in trees
        )
        rows.append(
            {"seed": seed, "max_abs_diff": diff,
             "max_abs_diff_exact_decode": diff_exact,
             "encode_overflow": overflow}
        )
        print(
            f"seed {seed}: max|enc_avg - plain_avg| = {diff:.2e} "
            f"(exact decode {diff_exact:.2e}), overflow {overflow}",
            file=sys.stderr,
        )

    worst = max(r["max_abs_diff"] for r in rows)
    ok = worst <= 1e-5 and all(r["encode_overflow"] == 0 for r in rows)
    print("| seed | enc-vs-plain max diff | exact-decode diff | overflow |")
    print("|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['seed']} | {r['max_abs_diff']:.2e} "
            f"| {r['max_abs_diff_exact_decode']:.2e} "
            f"| {r['encode_overflow']} |"
        )
    print(
        f"\nworst-case {worst:.2e} over {len(rows)} seeds at flagship shapes "
        f"(55 cts, N=4096, 2 clients) — bound 1e-5: {'PASS' if ok else 'FAIL'}"
    )
    with open("fidelity_check.json", "w") as f:
        json.dump(
            {"device": getattr(dev, "device_kind", str(dev)),
             "n_ct": 55, "n": ctx.n, "num_primes": ctx.num_primes,
             "num_clients": num_clients, "rows": rows,
             "worst_max_abs_diff": worst, "bound": 1e-5, "pass": ok},
            f, indent=2,
        )
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
