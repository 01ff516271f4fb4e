"""Serialization, checkpoint, and timer tests (SURVEY.md §5 subsystems)."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hefl_tpu.ckks import ops
from hefl_tpu.ckks.encoding import encode
from hefl_tpu.ckks.keys import CkksContext, keygen
from hefl_tpu.utils import (
    CheckpointError,
    PhaseTimer,
    load_checkpoint,
    load_ciphertext,
    load_params,
    load_public_material,
    load_secret_key,
    save_checkpoint,
    save_ciphertext,
    save_params,
    save_public_material,
    save_secret_key,
)


@pytest.fixture(scope="module")
def ctx_keys():
    ctx = CkksContext.create(n=128)
    sk, pk = keygen(ctx, jax.random.key(0))
    return ctx, sk, pk


def test_public_material_roundtrip(tmp_path, ctx_keys):
    ctx, sk, pk = ctx_keys
    path = str(tmp_path / "public.npz")
    save_public_material(path, ctx, pk)
    ctx2, pk2 = load_public_material(path)
    assert ctx2 == ctx  # bit-identical context (twiddles travel on the wire)
    np.testing.assert_array_equal(np.asarray(pk2.b_mont), np.asarray(pk.b_mont))
    # ciphertext made with the restored material decrypts under the original sk
    vals = jnp.linspace(-1, 1, ctx.n, dtype=jnp.float32)
    ct = ops.encrypt(ctx2, pk2, encode(ctx2.ntt, vals, ctx2.scale), jax.random.key(1))
    from hefl_tpu.ckks.encoding import decode

    out = decode(ctx.ntt, ops.decrypt(ctx, sk, ct), ctx.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(vals), atol=1e-3)


def test_secret_key_file_contains_no_public_material(tmp_path, ctx_keys):
    ctx, sk, _ = ctx_keys
    path = str(tmp_path / "secret.npz")
    save_secret_key(path, sk)
    with np.load(path) as z:
        assert set(z.files) == {"header", "s_mont"}
    sk2 = load_secret_key(path)
    np.testing.assert_array_equal(np.asarray(sk2.s_mont), np.asarray(sk.s_mont))


def test_galois_key_roundtrip(tmp_path, ctx_keys):
    from hefl_tpu.ckks.galois import galois_elt_rotation
    from hefl_tpu.ckks.keys import gen_galois_key
    from hefl_tpu.utils import load_galois_key, save_galois_key

    ctx, sk, _ = ctx_keys
    g = galois_elt_rotation(ctx.n, 1)
    gk = gen_galois_key(ctx, sk, jax.random.key(77), g)
    path = str(tmp_path / "galois.npz")
    save_galois_key(path, gk)
    gk2 = load_galois_key(path)
    assert gk2.g == gk.g
    np.testing.assert_array_equal(np.asarray(gk2.b_mont), np.asarray(gk.b_mont))
    np.testing.assert_array_equal(np.asarray(gk2.a_mont), np.asarray(gk.a_mont))


def test_relin_key_roundtrip(tmp_path, ctx_keys):
    from hefl_tpu.ckks.keys import gen_relin_key
    from hefl_tpu.utils import load_relin_key, save_relin_key

    ctx, sk, _ = ctx_keys
    rlk = gen_relin_key(ctx, sk, jax.random.key(78))
    path = str(tmp_path / "relin.npz")
    save_relin_key(path, rlk)
    rlk2 = load_relin_key(path)
    np.testing.assert_array_equal(np.asarray(rlk2.b_mont), np.asarray(rlk.b_mont))


def test_ciphertext_wire_carries_no_keys(tmp_path, ctx_keys):
    ctx, sk, pk = ctx_keys
    vals = jnp.full((ctx.n,), 0.25, jnp.float32)
    ct = ops.encrypt(ctx, pk, encode(ctx.ntt, vals, ctx.scale), jax.random.key(2))
    path = str(tmp_path / "ct.npz")
    save_ciphertext(path, ct)
    with np.load(path) as z:
        # the wart the reference had (pickling HE object with keys,
        # FLPyfhelin.py:232-234) must be structurally impossible here
        assert set(z.files) == {"header", "c0", "c1"}
    ct2 = load_ciphertext(path)
    assert ct2.scale == ct.scale
    np.testing.assert_array_equal(np.asarray(ct2.c0), np.asarray(ct.c0))


def test_kind_mismatch_rejected(tmp_path, ctx_keys):
    ctx, sk, _ = ctx_keys
    path = str(tmp_path / "secret.npz")
    save_secret_key(path, sk)
    with pytest.raises(ValueError, match="expected kind"):
        load_ciphertext(path)


def test_params_roundtrip(tmp_path):
    params = {"dense": {"kernel": jnp.arange(6.0).reshape(2, 3), "bias": jnp.ones(3)}}
    path = str(tmp_path / "params.npz")
    save_params(path, params)
    out = load_params(path, jax.tree_util.tree_map(jnp.zeros_like, params))
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_params_shape_mismatch_rejected(tmp_path):
    params = {"w": jnp.ones((2, 3))}
    path = str(tmp_path / "p.npz")
    save_params(path, params)
    with pytest.raises(ValueError, match="shape mismatch"):
        load_params(path, {"w": jnp.ones((3, 2))})


def test_checkpoint_roundtrip(tmp_path):
    params = {"w": jnp.float32(3.5), "b": jnp.arange(4.0)}
    key = jax.random.key(7)
    path = str(tmp_path / "ckpt.npz")
    save_checkpoint(path, params, 5, key, meta={"model": "smallcnn"})
    p2, rnd, key2, meta = load_checkpoint(path, params)
    assert rnd == 5
    assert meta["model"] == "smallcnn"
    np.testing.assert_array_equal(
        jax.random.key_data(key2), jax.random.key_data(key)
    )
    np.testing.assert_array_equal(np.asarray(p2["b"]), np.asarray(params["b"]))


def test_checkpoint_content_hash_rejects_tamper(tmp_path):
    # ISSUE 9 satellite: the zip container only catches STRUCTURAL
    # damage; the header's content sha256 must reject a payload that
    # decompresses cleanly but was altered after the write.
    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(4)}
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, params, 2, jax.random.key(1))
    z = dict(np.load(path))
    assert "sha256" in json.loads(bytes(z["header"]).decode())
    z["param:w"] = z["param:w"] + 1.0   # valid zip, wrong content
    np.savez(path, **z)
    with pytest.raises(CheckpointError, match="content hash"):
        load_checkpoint(path, params)
    # a checkpoint without the digest field (pre-ISSUE-9) still loads
    z["param:w"] = z["param:w"] - 1.0
    header = json.loads(bytes(z["header"]).decode())
    header.pop("sha256")
    z["header"] = np.frombuffer(json.dumps(header).encode(), dtype=np.uint8)
    np.savez(path, **z)
    _, rnd, _, _ = load_checkpoint(path, params)
    assert rnd == 2


def test_phase_timer_accumulates():
    t = PhaseTimer()
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    with t.phase("a"):
        pass
    s = t.summary()
    assert list(s) == ["a", "b", "total"]
    assert s["total"] >= s["a"] + s["b"] - 1e-6
    t.record("decrypt", 1.5)
    assert t.summary()["decrypt"] == 1.5


def test_checkpoint_extensionless_path_roundtrips(tmp_path):
    # np.savez appends .npz to bare paths; load must still find the file
    params = {"w": jnp.ones(3)}
    path = str(tmp_path / "ck")  # no extension
    save_checkpoint(path, params, 1, jax.random.key(0))
    p2, rnd, _, _ = load_checkpoint(path, params)
    assert rnd == 1


def test_he_roofline_rows_are_non_null():
    # ISSUE 4: the HE int-op/bandwidth roofline must produce fully-populated
    # rows (no null int_ops / rates) whenever seconds are supplied — the
    # schema run_perf_smoke.sh gates on every artifact.
    from hefl_tpu.utils import roofline

    rows = roofline.he_roofline(
        {"encrypt": 0.05, "aggregate": 0.001, "decrypt": 0.02},
        n=4096, num_limbs=3, n_ct=55, num_clients=2, encrypt_clients=1,
        device="TPU v5 lite",
    )
    cpu_rows = roofline.he_roofline(
        {"encrypt": 0.05, "aggregate": 0.001, "decrypt": 0.02},
        n=4096, num_limbs=3, n_ct=55, num_clients=2, encrypt_clients=1,
        device="cpu",
    )
    for phase in ("encrypt", "aggregate", "decrypt"):
        row = rows[phase]
        for field in ("seconds", "int_ops", "bytes", "int_ops_per_s", "bytes_per_s"):
            assert row[field] is not None, (phase, field)
            assert cpu_rows[phase][field] == row[field]
        assert row["int_ops"] > 0 and row["bytes"] > 0
        # The VPU int peak is an estimate and must say so; a CPU has no
        # peak at all, so its utilization is null.
        assert row.get("peak_is_estimate") is True
        assert cpu_rows[phase]["util_vs_peak_int_ops"] is None
    # Encrypt dominates decrypt at the same geometry (4 NTTs vs 1).
    assert rows["encrypt"]["int_ops"] > rows["decrypt"]["int_ops"]
    geo = rows["geometry"]
    assert geo == {"n": 4096, "num_limbs": 3, "n_ct": 55,
                   "num_clients": 2, "encrypt_clients": 1}
    # Missing seconds keep analytic counts but null the rates.
    rows2 = roofline.he_roofline(
        {}, n=4096, num_limbs=3, n_ct=55, num_clients=2, device="cpu"
    )
    assert rows2["encrypt"]["int_ops"] > 0
    assert rows2["encrypt"]["int_ops_per_s"] is None
