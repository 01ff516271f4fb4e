"""Encrypted-FedAvg tests (SURVEY.md §4 property tests):

  * pack/unpack round-trip
  * decrypt(Σ enc(wᵢ)) / N  ≈  mean(wᵢ)   — the core HE-FedAvg property
  * secure round ≈ plaintext round        — encrypted path is a drop-in
  * trust split: aggregation output is not decodable without sk
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hefl_tpu.ckks import encoding, ops
from hefl_tpu.ckks.keys import CkksContext, keygen
from hefl_tpu.ckks.packing import PackSpec, pack_pytree, unpack_blocks
from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
from hefl_tpu.fl import (
    TrainConfig,
    aggregate_encrypted,
    decrypt_average,
    encrypt_params,
    fedavg_round,
    secure_fedavg_round,
)
from hefl_tpu.models import SmallCNN
from hefl_tpu.parallel import make_host_mesh, make_mesh


@pytest.fixture(scope="module")
def ctx_keys():
    ctx = CkksContext.create(n=256)  # small ring: fast CI, same code path
    sk, pk = keygen(ctx, jax.random.key(42))
    return ctx, sk, pk


def _rand_pytree(key, scale=0.5):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "conv": {"kernel": jax.random.normal(k1, (3, 3, 4, 8)) * scale,
                 "bias": jax.random.normal(k2, (8,)) * scale},
        "dense": {"kernel": jax.random.normal(k3, (32, 10)) * scale},
    }


def test_pack_unpack_roundtrip():
    params = _rand_pytree(jax.random.key(0))
    spec = PackSpec.for_params(params, 256)
    total = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    assert spec.total == total
    assert spec.n_ct == -(-total // 256)
    blocks = pack_pytree(params, 256)
    assert blocks.shape == (spec.n_ct, 256)
    back = unpack_blocks(blocks, spec)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_encrypted_average_matches_plain_mean(ctx_keys):
    # decrypt(avg(enc(w_i))) ≈ mean(w_i) within encoder precision — the
    # property the reference spot-checked by hand (FLPyfhelin.py:382).
    ctx, sk, pk = ctx_keys
    num_clients = 4
    trees = [_rand_pytree(jax.random.key(i + 1)) for i in range(num_clients)]
    spec = PackSpec.for_params(trees[0], ctx.n)
    cts = [
        encrypt_params(ctx, pk, t, jax.random.key(100 + i))
        for i, t in enumerate(trees)
    ]
    stacked = ops.Ciphertext(
        c0=jnp.stack([c.c0 for c in cts]),
        c1=jnp.stack([c.c1 for c in cts]),
        scale=cts[0].scale,
    )
    ct_sum = aggregate_encrypted(ctx, stacked)
    avg = decrypt_average(ctx, sk, ct_sum, num_clients, spec)
    expected = jax.tree_util.tree_map(lambda *xs: sum(xs) / num_clients, *trees)
    for a, b in zip(jax.tree_util.tree_leaves(avg), jax.tree_util.tree_leaves(expected)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_exact_decode_path_matches_jit_decode(ctx_keys):
    ctx, sk, pk = ctx_keys
    params = _rand_pytree(jax.random.key(7))
    spec = PackSpec.for_params(params, ctx.n)
    ct = encrypt_params(ctx, pk, params, jax.random.key(8))
    fast = decrypt_average(ctx, sk, ct, 1, spec)
    gold = decrypt_average(ctx, sk, ct, 1, spec, exact=True)
    for a, b in zip(jax.tree_util.tree_leaves(fast), jax.tree_util.tree_leaves(gold)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_decrypt_without_sk_yields_garbage(ctx_keys):
    # The psum output must be semantically hidden: decoding c0 alone (what a
    # server without sk could try) must NOT recover the plaintext.
    ctx, sk, pk = ctx_keys
    params = _rand_pytree(jax.random.key(11))
    spec = PackSpec.for_params(params, ctx.n)
    ct = encrypt_params(ctx, pk, params, jax.random.key(12))
    from hefl_tpu.ckks.ntt import ntt_inverse

    res = ntt_inverse(ctx.ntt, ct.c0)
    leak = encoding.decode(ctx.ntt, res, ct.scale)
    flat_true = np.asarray(pack_pytree(params, ctx.n))
    # correlation between "decrypted-without-sk" and truth should be ~0
    a, b = np.asarray(leak).ravel(), flat_true.ravel()
    corr = np.corrcoef(a, b)[0, 1]
    assert abs(corr) < 0.05


def test_secure_round_matches_plain_round_end_to_end():
    # Full SPMD program on the 8-device CPU mesh: train + encrypt + psum +
    # owner decrypt must equal the plaintext fedavg round (same RNG key) to
    # within CKKS noise — the notebook cell-6 plain-vs-encrypted comparison.
    num_clients = 4
    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=num_clients * 24, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=8, num_classes=10, augment=False,
                      val_fraction=0.25)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create()  # full-size ring (4096)
    sk, pk = keygen(ctx, jax.random.key(99))
    spec = PackSpec.for_params(params, ctx.n)
    key = jax.random.key(5)

    ct_sum, metrics, overflow = secure_fedavg_round(
        model, cfg, mesh, ctx, pk, params, jnp.asarray(xs), jnp.asarray(ys), key
    )
    assert metrics.shape == (num_clients, 1, 4)
    assert overflow.shape == (num_clients,)
    assert int(np.sum(np.asarray(overflow))) == 0  # no encoder saturation
    enc_avg = decrypt_average(ctx, sk, ct_sum, num_clients, spec)

    k_train, _ = jax.random.split(key)  # plaintext round trains with k_train
    plain_avg, _ = fedavg_round(
        model, cfg, mesh, params, jnp.asarray(xs), jnp.asarray(ys), k_train
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(enc_avg), jax.tree_util.tree_leaves(plain_avg)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_secure_round_on_host_mesh_matches_flat_mesh():
    # Multi-host topology (SURVEY.md §2.13 distributed backend): the same 8
    # clients on a 2x4 ("hosts", "clients") mesh — intra-host lazy psum over
    # ICI, then the cross-host DCN fold — must produce the same aggregated
    # model as the flat 8-device mesh (identical client RNG streams).
    num_clients = 8
    (x, y), _, _ = make_dataset("mnist", seed=1, n_train=num_clients * 16, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=8, num_classes=10, augment=False,
                      val_fraction=0.25)
    ctx = CkksContext.create(n=512)
    sk, pk = keygen(ctx, jax.random.key(9))
    spec = PackSpec.for_params(params, ctx.n)
    key = jax.random.key(6)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)

    results = []
    for mesh in (make_host_mesh(2, 4), make_mesh(num_clients)):
        ct_sum, metrics, overflow = secure_fedavg_round(
            model, cfg, mesh, ctx, pk, params, xs_d, ys_d, key
        )
        assert metrics.shape == (num_clients, 1, 4)
        assert overflow.shape == (num_clients,)
        results.append(ct_sum)
    host_ct, flat_ct = results
    # Same per-client trainings and encryption keys, and the mod-p ciphertext
    # sum is exact integer arithmetic independent of reduction grouping: the
    # two topologies must agree BITWISE, on the ciphertext and therefore on
    # the decrypted model.
    np.testing.assert_array_equal(np.asarray(host_ct.c0), np.asarray(flat_ct.c0))
    np.testing.assert_array_equal(np.asarray(host_ct.c1), np.asarray(flat_ct.c1))
    host_avg = decrypt_average(ctx, sk, host_ct, num_clients, spec)
    flat_avg = decrypt_average(ctx, sk, flat_ct, num_clients, spec)
    for a, b in zip(
        jax.tree_util.tree_leaves(host_avg), jax.tree_util.tree_leaves(flat_avg)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_program_compiles_once_across_rounds():
    # VERDICT r3: feeding a decrypt_average output back as the next round's
    # global params must NOT recompile the round program (round 1 used to
    # pay a second full XLA compile because fresh-model params are
    # SingleDeviceSharding while decrypt outputs carry a NamedSharding).
    from hefl_tpu.fl.secure import _build_secure_round_fn

    # The factory is lru_cached on value-equal (module, cfg, mesh, ctx):
    # another test using the same config with different data shapes would
    # share this jit and pollute the count — isolate it.
    _build_secure_round_fn.cache_clear()
    num_clients = 2
    (x, y), _, _ = make_dataset("mnist", seed=3, n_train=num_clients * 8, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, augment=False,
                      val_fraction=0.25)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    sk, pk = keygen(ctx, jax.random.key(1))
    spec = PackSpec.for_params(params, ctx.n)
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)

    cur = params
    for r in range(3):
        ct, _, _ = secure_fedavg_round(
            model, cfg, mesh, ctx, pk, cur, xs_d, ys_d,
            jax.random.fold_in(jax.random.key(2), r),
        )
        cur = decrypt_average(ctx, sk, ct, num_clients, spec)
    fn = _build_secure_round_fn(model, cfg, mesh, ctx, False)
    assert fn._cache_size() == 1, (
        f"secure round program compiled {fn._cache_size()} times across 3 "
        "rounds; params sharding must be canonicalized (fedavg.replicate_on)"
    )


def test_sharded_he_bitwise_matches_replicated(ctx_keys):
    # ISSUE 4: the ciphertext batch sharded over the virtual 8-device "ct"
    # mesh must produce BITWISE the same ciphertexts and decrypt residues
    # as the replicated path — sharding is throughput only, the per-row
    # math and the sampling key derivation are identical.
    ctx, sk, pk = ctx_keys
    from hefl_tpu.ckks import ops as ckks_ops
    from hefl_tpu.fl.secure import decrypt_sharded, encrypt_params_sharded
    from hefl_tpu.parallel import make_ct_mesh

    params = _rand_pytree(jax.random.key(31))
    spec = PackSpec.for_params(params, ctx.n)
    key = jax.random.key(32)
    mesh = make_ct_mesh()
    assert mesh.devices.size == 8  # the conftest virtual topology

    ct_rep = encrypt_params(ctx, pk, params, key)
    ct_sh = encrypt_params_sharded(ctx, pk, params, key, mesh)
    np.testing.assert_array_equal(np.asarray(ct_sh.c0), np.asarray(ct_rep.c0))
    np.testing.assert_array_equal(np.asarray(ct_sh.c1), np.asarray(ct_rep.c1))

    res_rep = ckks_ops.decrypt(ctx, sk, ct_rep)
    res_sh = decrypt_sharded(ctx, sk, ct_rep, mesh)
    np.testing.assert_array_equal(np.asarray(res_sh), np.asarray(res_rep))

    # decrypt_average(mesh=...) — the owner-side entry point — end to end.
    avg_rep = decrypt_average(ctx, sk, ct_rep, 1, spec)
    avg_sh = decrypt_average(ctx, sk, ct_rep, 1, spec, mesh=mesh)
    for a, b in zip(
        jax.tree_util.tree_leaves(avg_sh), jax.tree_util.tree_leaves(avg_rep)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_round_compiles_once_under_pallas_interpret_backend():
    # No-new-compile guard for the masked secure round under the new
    # backend selection (ISSUE 4): per-round participation masks are traced
    # values, so 3 masked rounds with three DIFFERENT masks must share one
    # executable — with the NTT selector pinned to the new
    # "pallas-interpret" mode (kernels where tileable, silent XLA fallback
    # on this small test ring) so the dispatch layer itself is on the path.
    from hefl_tpu.ckks import ntt as ntt_mod
    from hefl_tpu.fl.secure import _build_secure_round_fn

    _build_secure_round_fn.cache_clear()
    num_clients = 2
    (x, y), _, _ = make_dataset("mnist", seed=6, n_train=num_clients * 8, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, augment=False,
                      val_fraction=0.25)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=256)
    sk, pk = keygen(ctx, jax.random.key(1))
    xs_d, ys_d = jnp.asarray(xs), jnp.asarray(ys)

    prev = ntt_mod._BACKEND
    ntt_mod._BACKEND = "pallas-interpret"
    try:
        masks = ([1, 1], [1, 0], [0, 1])
        for r, m in enumerate(masks):
            ct, _, _, meta = secure_fedavg_round(
                model, cfg, mesh, ctx, pk, params, xs_d, ys_d,
                jax.random.fold_in(jax.random.key(3), r),
                participation=jnp.asarray(m, jnp.int32),
            )
            assert meta.surviving == sum(m)
        fn = _build_secure_round_fn(
            model, cfg, mesh, ctx, False, None, num_clients, masked=True
        )
        assert fn._cache_size() == 1, (
            f"masked secure round compiled {fn._cache_size()} times for 3 "
            "different participation masks under the new backend; masks "
            "must stay traced values"
        )
    finally:
        ntt_mod._BACKEND = prev


def test_train_clients_weights_agree_with_both_aggregators(ctx_keys):
    # The bench cell-6 artifact path: train_clients' stacked weight trees
    # pushed through (a) the plain mean and (b) vmapped encrypt -> lazy
    # modular sum -> decrypt must agree to encoder precision, because both
    # consume the IDENTICAL trained weights.
    ctx, sk, pk = ctx_keys
    from hefl_tpu.fl import train_clients
    from hefl_tpu.fl.secure import encrypt_stack

    num_clients = 2
    (x, y), _, _ = make_dataset("mnist", seed=4, n_train=num_clients * 8, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, augment=False,
                      val_fraction=0.25)
    mesh = make_mesh(num_clients)
    spec = PackSpec.for_params(params, ctx.n)
    key = jax.random.key(11)

    p_out, metrics = train_clients(
        model, cfg, mesh, params, jnp.asarray(xs), jnp.asarray(ys), key
    )
    assert metrics.shape == (num_clients, 1, 4)
    plain = jax.tree_util.tree_map(lambda t: jnp.mean(t, axis=0), p_out)
    enc_keys = jax.random.split(jax.random.key(12), num_clients)
    cts = encrypt_stack(ctx, pk, p_out, enc_keys)
    enc_avg = decrypt_average(
        ctx, sk, aggregate_encrypted(ctx, cts), num_clients, spec
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(enc_avg), jax.tree_util.tree_leaves(plain)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_with_plain_reference_isolates_he_error():
    # The bench cell-6 mode: the production secure round with a 4th output —
    # the plaintext pmean of the SAME in-program trained weights. The
    # decrypted aggregate must match that reference to encoder precision
    # (pure HE error), validating the full production pipeline including
    # the hierarchical psum collective at the same program shape.
    num_clients = 4
    (x, y), _, _ = make_dataset("mnist", seed=5, n_train=num_clients * 8, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), num_clients))
    model = SmallCNN(num_classes=10)
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10, augment=False,
                      val_fraction=0.25)
    mesh = make_mesh(num_clients)
    ctx = CkksContext.create(n=512)
    sk, pk = keygen(ctx, jax.random.key(21))
    spec = PackSpec.for_params(params, ctx.n)

    ct, mets, ov, plain_ref = secure_fedavg_round(
        model, cfg, mesh, ctx, pk, params, jnp.asarray(xs), jnp.asarray(ys),
        jax.random.key(22), with_plain_reference=True,
    )
    assert mets.shape == (num_clients, 1, 4)
    assert int(np.sum(np.asarray(ov))) == 0
    enc_avg = decrypt_average(ctx, sk, ct, num_clients, spec)
    for a, b in zip(
        jax.tree_util.tree_leaves(enc_avg),
        jax.tree_util.tree_leaves(plain_ref),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_owner_decrypt_takes_a_single_device_copy():
    # A round output is replicated over the round's mesh; the owner-side
    # decrypt must be a ONE-device program (on a TPU a Mosaic kernel over a
    # multi-device array is refused: "cannot be automatically partitioned").
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hefl_tpu.ckks.ops import Ciphertext
    from hefl_tpu.fl.secure import _on_one_device

    mesh = make_mesh(4)
    assert mesh.devices.size == 4
    x = np.arange(4 * 6, dtype=np.uint32).reshape(4, 6)
    rep = jax.device_put(x, NamedSharding(mesh, P()))
    split = jax.device_put(x, NamedSharding(mesh, P("clients")))
    out = _on_one_device(Ciphertext(c0=rep, c1=split, scale=2.0))
    for got in (out.c0, out.c1):
        assert len(got.sharding.device_set) == 1
        np.testing.assert_array_equal(np.asarray(got), x)
    assert out.scale == 2.0
    # already on one device: passed through untouched
    single = jnp.asarray(x)
    assert _on_one_device(Ciphertext(c0=single, c1=single, scale=1.0)).c0 is single


# ------------------------------------------------- the owner's compiled decode
# (PR 30) `decrypt_average`'s float path is ONE program of the decrypted
# residues: digits, float32 recombination, unpack. The eager decode stays
# the reference, with the pre-PR recombination written out below.


def _decode_as_before(ntt, res, scale):
    """`encoding.decode` as it stood before it was split into coefficients
    and body: each factor formed in float64 and rounded by `jnp.float32`."""
    digits = encoding._mixed_radix_digits(ntt, res)
    p = np.asarray(ntt.p)[:, 0]
    inv_scale = 1.0 / float(scale)
    out = digits[0].astype(jnp.float32) * jnp.float32(inv_scale)
    radix = 1.0
    for i in range(1, len(digits)):
        radix *= float(int(p[i - 1]))
        out = out + digits[i].astype(jnp.float32) * jnp.float32(radix * inv_scale)
    return out


def _one_rounding(ntt, res, scale):
    """float32 eps x the sum of the recombination's |terms|: what a
    compiler that contracts a multiply-add (one rounding, not two) may
    move a decoded value by."""
    digits = encoding._mixed_radix_digits(ntt, res)
    coeffs = encoding.decode_coefficients(ntt, scale).astype(np.float64)
    mag = sum(np.abs(np.asarray(d, np.float64)) * c for d, c in zip(digits, coeffs))
    return np.finfo(np.float32).eps * mag


@pytest.fixture(scope="module")
def bench_ring():
    """The benchmark's ring (`HEConfig()`: N 4096, 3 primes, scale 2^30) and
    a 55-row parameter tree (222,786 values, the tail row padded)."""
    ctx = CkksContext.create()
    assert ctx.n == 4096 and np.asarray(ctx.ntt.p).shape[0] == 3
    template = {
        "conv": {"kernel": np.zeros((3, 3, 32, 64), np.float32),
                 "bias": np.zeros((64,), np.float32)},
        "dense": {"kernel": np.zeros((1596, 128), np.float32),
                  "bias": np.zeros((2,), np.float32)},
    }
    spec = PackSpec.for_params(template, ctx.n)
    assert spec.n_ct == 55 and spec.total % ctx.n != 0
    return ctx, spec


def _residues(ntt, v):
    """Exact integers int64[n_ct, N] -> canonical residues uint32[n_ct, L, N]."""
    p = np.asarray(ntt.p)[:, 0].astype(np.int64)
    return jnp.asarray(
        np.stack([np.mod(v, pi) for pi in p], axis=-2).astype(np.uint32)
    )


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_compiled_decode_is_the_eager_decode_on_an_aggregate(bench_ring):
    from hefl_tpu.fl.secure import _decode_unpack

    ctx, spec = bench_ring
    ntt, clients = ctx.ntt, 4
    rng = np.random.default_rng(30)
    # four clients' weights |w| < 1 at scale 2^30, summed, plus decrypt noise
    w = rng.uniform(-1, 1, (clients, spec.n_ct, ctx.n))
    v = np.rint(w * ctx.scale).astype(np.int64).sum(0)
    v += np.rint(rng.normal(0, 8, v.shape)).astype(np.int64)
    res, scale = _residues(ntt, v), ctx.scale * clients
    got = _decode_unpack(
        ntt, spec, res, encoding.decode_coefficients(ntt, scale)
    )
    eager = unpack_blocks(encoding.decode(ntt, res, scale), spec)
    before = unpack_blocks(_decode_as_before(ntt, res, scale), spec)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(eager)
    for a, b, c in zip(_leaves(got), _leaves(eager), _leaves(before)):
        assert a.shape == b.shape and a.dtype == np.float32
        np.testing.assert_array_equal(b, c)   # the split moved no bit
        np.testing.assert_array_equal(a, b)   # nor did compiling it
    gold = encoding.decode_exact(ntt, np.asarray(res), scale)
    flat = np.concatenate([a.ravel() for a in _leaves(got)])
    np.testing.assert_allclose(
        flat, gold.reshape(-1)[: spec.total], rtol=2.0**-19, atol=0
    )
    # and the true mean, to the noise: 8 / (4 * 2^30)
    np.testing.assert_allclose(
        flat, w.mean(0).reshape(-1)[: spec.total], atol=1e-7
    )


def test_compiled_decode_on_random_full_size_residues(bench_ring):
    from hefl_tpu.fl.secure import _decode_unpack

    ctx, spec = bench_ring
    ntt = ctx.ntt
    rng = np.random.default_rng(31)
    p = np.asarray(ntt.p)[:, 0].astype(np.int64)
    res = jnp.asarray(
        np.stack(
            [rng.integers(0, pi, (spec.n_ct, ctx.n)) for pi in p], axis=-2
        ).astype(np.uint32)
    )
    # the digits are exact integer arithmetic: compiled == eager, bit for bit
    eager_digits = encoding._mixed_radix_digits(ntt, res)
    jit_digits = jax.jit(lambda r: tuple(encoding._mixed_radix_digits(ntt, r)))(res)
    for a, b in zip(jit_digits, eager_digits):
        assert a.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the floats: within one float32 rounding of the eager recombination
    scale = ctx.scale * 4
    got = _decode_unpack(
        ntt, spec, res, encoding.decode_coefficients(ntt, scale)
    )
    eager = np.asarray(encoding.decode(ntt, res, scale))
    np.testing.assert_array_equal(
        eager, np.asarray(_decode_as_before(ntt, res, scale))
    )
    flat = np.concatenate([a.ravel() for a in _leaves(got)])
    room = _one_rounding(ntt, res, scale).reshape(-1)[: spec.total]
    assert np.all(np.abs(flat - eager.reshape(-1)[: spec.total]) <= room)
    gold = encoding.decode_exact(ntt, np.asarray(res), scale)
    np.testing.assert_allclose(
        flat, gold.reshape(-1)[: spec.total], rtol=2.0**-19, atol=0
    )


@pytest.fixture(scope="module")
def masked_sum(ctx_keys):
    """Four clients' encrypted sum, one PackSpec for every case below, and
    the compiled decode's cache size before any of them ran."""
    from hefl_tpu.fl.secure import _decode_unpack

    ctx, sk, pk = ctx_keys
    # a tree no other test of this process packs: PackSpecs of equal trees
    # compare equal and would find the program already traced
    trees = [
        {"w": jax.random.normal(jax.random.key(300 + i), (7, 11, 5)) * 0.5,
         "b": jax.random.normal(jax.random.key(310 + i), (13,)) * 0.5}
        for i in range(4)
    ]
    spec = PackSpec.for_params(trees[0], ctx.n)
    cts = [
        encrypt_params(ctx, pk, t, jax.random.key(400 + i))
        for i, t in enumerate(trees)
    ]
    ct_sum = aggregate_encrypted(
        ctx,
        ops.Ciphertext(
            c0=jnp.stack([c.c0 for c in cts]),
            c1=jnp.stack([c.c1 for c in cts]),
            scale=cts[0].scale,
        ),
    )
    return spec, ct_sum, _decode_unpack._cache_size()


@pytest.mark.parametrize("surviving", [1, 3, 4])
def test_one_decode_program_serves_every_surviving_count(
    ctx_keys, masked_sum, surviving
):
    # Partial participation changes the decode's denominator from round to
    # round; the scale is data of the program, so nothing compiles again.
    from hefl_tpu.fl.faults import EXCLUDED_SCHEDULED, RoundMeta
    from hefl_tpu.fl.secure import _decode_unpack

    ctx, sk, _ = ctx_keys
    spec, ct_sum, programs_before = masked_sum
    bits = [0] * surviving + [EXCLUDED_SCHEDULED] * (4 - surviving)
    meta = RoundMeta.from_bits(bits)
    assert meta.surviving == surviving
    got = decrypt_average(ctx, sk, ct_sum, spec=spec, meta=meta)
    assert _decode_unpack._cache_size() == programs_before + 1
    res = ops.decrypt(ctx, sk, ct_sum)
    want = unpack_blocks(
        encoding.decode(ctx.ntt, res, ct_sum.scale * surviving), spec
    )
    room = _one_rounding(ctx.ntt, res, ct_sum.scale * surviving)
    room = unpack_blocks(jnp.asarray(room, jnp.float32), spec)
    for a, b, r in zip(_leaves(got), _leaves(want), _leaves(room)):
        assert np.all(np.abs(a - b) <= r)
    # a sum of four decoded over s is 4/s times the mean of four
    full = decrypt_average(ctx, sk, ct_sum, 4, spec)
    for a, b in zip(_leaves(got), _leaves(full)):
        np.testing.assert_allclose(a, b * (4 / surviving), rtol=1e-6, atol=1e-7)


def test_decode_programs_counts_the_compiled_float_path_alone(ctx_keys):
    from hefl_tpu.ckks.packing import PackedSpec
    from hefl_tpu.ckks.quantize import PackingConfig
    from hefl_tpu.fl import encrypt_stack_packed
    from hefl_tpu.obs import metrics as obs_metrics

    ctx, sk, pk = ctx_keys
    counter = obs_metrics.counter("he.decode_programs")
    params = _rand_pytree(jax.random.key(21))
    spec = PackSpec.for_params(params, ctx.n)
    ct = encrypt_params(ctx, pk, params, jax.random.key(22))
    n0 = counter.value
    decrypt_average(ctx, sk, ct, 1, spec)
    assert counter.value == n0 + 1
    decrypt_average(ctx, sk, ct, 1, spec)
    assert counter.value == n0 + 2
    # the host bignum export and the packed integers do not go through it
    decrypt_average(ctx, sk, ct, 1, spec, exact=True)
    assert counter.value == n0 + 2
    trees = [
        jax.tree_util.tree_map(
            lambda t: t + 0.05 * jax.random.normal(jax.random.key(60 + i), t.shape),
            params,
        )
        for i in range(2)
    ]
    pspec = PackedSpec.for_params(
        params, ctx, PackingConfig(bits=8, interleave=2, clip=0.25), 2
    )
    cts, _ = encrypt_stack_packed(
        ctx, pk, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees),
        params, jax.random.split(jax.random.key(23), 2), pspec,
    )
    decrypt_average(
        ctx, sk, aggregate_encrypted(ctx, cts), 2,
        packing=pspec, base_params=params,
    )
    assert counter.value == n0 + 2
