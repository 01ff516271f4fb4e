"""The check of a cell whose model is an image classifier that every client
trains whole: what a round's work counts, and the numbers that decide
`correct`, against the plain reference `<path>/reference/<model>.py`.

A configuration names its check with the key `check` (a module of
`<path>/checks/`); one without the key gets this one. `benchmarks/run.py`
calls `round_work` when the window has closed and `numbers` after it, with
the cell (`config`, `traffic`, `paths`, and `module(kind, name)`, which finds
a file of the cell by name), the built `ExperimentConfig` and the arrays
`make_dataset` returned. `benchmarks/controls.py` reads `control_data` and
`control_numbers`. This check follows a synchronous, unpacked, IID round on a
1-D mesh and refuses any other.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math

CHECK_STEPS = 3   # optimizer steps the plain reference follows


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def _norms(tree):
    import jax
    import numpy as np

    return [float(np.linalg.norm(np.asarray(leaf, np.float64)))
            for leaf in jax.tree_util.tree_leaves(tree)]


def norm_gap(got, want) -> float:
    """Worst leaf: |norm(got) - norm(want)| against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    ref = _norms(want)
    floor = sorted(ref)[len(ref) // 2]
    return max(abs(g - r) / max(r, floor) for g, r in zip(_norms(got), ref))


def whole_norm_gap(got, want) -> float:
    """|norm(got) - norm(want)| / norm(want) over all leaves as one vector."""
    whole = lambda t: math.sqrt(sum(n * n for n in _norms(t)))  # noqa: E731
    return abs(whole(got) - whole(want)) / whole(want)


@functools.lru_cache(maxsize=None)
def _grad_fns(module, ref, quant):
    """(reference, system) jitted value-and-grad of the loss with the logits,
    over (params, batch, onehot): compiled once for a cell, whatever the
    seed, so the persistent cache serves every later run."""
    import jax

    from hefl_tpu.fl.loss import loss_fn

    def system(p, x, onehot):  # at the program's own precision
        if quant is not None:
            return ref.loss(p, x, onehot, quant)
        return (loss_fn(module, p, x, onehot)[0],
                module.apply({"params": p}, x))

    def reference(p, x, onehot):
        with jax.default_matmul_precision("highest"):
            return ref.loss(p, x, onehot)

    return (jax.jit(jax.value_and_grad(reference, has_aux=True)),
            jax.jit(jax.value_and_grad(system, has_aux=True)))


def model_numbers(module, ref, x, onehot, seed: int, quant=None) -> dict:
    """The system's own loss and gradient (`fl.loss.loss_fn`, what the SGD
    step differentiates) against the plain float32 reference, on one timed
    batch of the seed's images and the reference's seeded weights. The
    logits' widest error is given in units of the widest error that the
    reference makes when it is computed in float8, on the same weights and
    batch: how far a precision moves the logits swings sixfold with the
    seed, the ratio of two precisions far less. The loss is held to the
    cross-entropy of the system's own logits, the gradient to the
    reference's, leaf by leaf. With `quant` the reference computed in that
    precision stands in the system's place: the control."""
    import numpy as np

    params = ref.init(seed, x.shape[1:], onehot.shape[-1])
    ref_fn, sys_fn = _grad_fns(module, ref, quant)
    (_, z_ref), g_ref = ref_fn(params, x, onehot)
    (l_sys, z_sys), g_sys = sys_fn(params, x, onehot)
    (_, z_fp8), _ = _grad_fns(module, ref, fp8_quant)[1](params, x, onehot)
    z_ref = np.asarray(z_ref, np.float64)
    err = lambda z: float(np.max(np.abs(  # noqa: E731
        np.asarray(z, np.float64) - z_ref)))
    # The loss arithmetic apart from the forward's precision: the system's
    # loss against the cross-entropy of its own logits in float64.
    z = np.asarray(z_sys, np.float64)
    z = z - z.max(-1, keepdims=True)
    ce = float(np.mean(np.log(np.exp(z).sum(-1)) - (z * onehot).sum(-1)))
    return {
        "loss_gap": abs(float(l_sys) - ce) / ce,
        "logit_err_vs_fp8": err(z_sys) / err(z_fp8),
        "grad_norm_gap": norm_gap(g_sys, g_ref),
        "logit_err_max": err(z_sys),
    }


def fp8_quant(a):
    """The control's precision: float8 (e4m3) values forward, the identity
    backward, where the configuration states bfloat16."""
    import jax
    import jax.numpy as jnp

    return a + jax.lax.stop_gradient(
        a.astype(jnp.float8_e4m3fn).astype(jnp.float32) - a)


@functools.lru_cache(maxsize=None)
def _ref_loss(ref):
    import jax

    def loss(p, x, onehot):
        with jax.default_matmul_precision("highest"):
            return ref.loss(p, x, onehot)[0]

    return jax.jit(loss)


def train_numbers(cfg, module, ref, adam, x, y, steps: int = CHECK_STEPS) -> dict:
    """One round through the timed entry points (`secure_fedavg_round`, then
    `decrypt_average`) with the cell's clients, batch, client lowering and HE
    parameters, from the reference's seeded weights. Its local scan is cut to
    `steps` optimizer steps of one epoch on the head of each client's shard,
    and its random warp is off: the plain reference cannot follow the
    program's augmentation. Compared: the in-program plain mean against a
    plain float32 Adam run over the same batches (the norm of the
    parameters' change, as one vector and by the worst leaf), each client's
    validation loss at its trained weights against the reference's, and the
    decrypted average against the plain mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.ckks.packing import PackSpec
    from hefl_tpu.data import iid_contiguous, stack_federated
    from hefl_tpu.fl import decrypt_average, secure_fedavg_round
    from hefl_tpu.fl.client import epoch_index_streams, train_batch_geometry
    from hefl_tpu.fl.fedavg import pad_federated
    from hefl_tpu.parallel import client_mesh_size, client_sharding, make_mesh

    if (cfg.partition != "iid" or cfg.mesh_ct > 1 or cfg.stream is not None
            or (cfg.packing is not None and cfg.packing.enabled)):
        raise NotImplementedError(
            "this check follows a synchronous, unpacked, IID round on a 1-D "
            "mesh; a cell of another kind of round names a check of its own "
            "(`<path>/checks/<name>.py`), proved on the chip in the PR that "
            "adds it")
    n_cl, classes = cfg.num_clients, cfg.train.num_classes
    tc = dataclasses.replace(cfg.train, epochs=1, augment=False)
    m = next(k for k in range(steps * tc.batch_size, len(y) // n_cl + 1)
             if train_batch_geometry(tc, k)[2] == steps)
    n_tr, grp, _ = train_batch_geometry(tc, m)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), n_cl))
    xs, ys = np.asarray(xs[:, :m]), np.asarray(ys[:, :m])
    shape = tuple(int(d) for d in xs.shape[2:])
    params0 = ref.init(cfg.seed, shape, classes)

    # ---- the system: one round of the timed entry, then the owner's decrypt
    mesh = make_mesh(n_cl)
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    place = client_sharding(mesh)
    ctx = cfg.he.build()
    _, k_he = jax.random.split(jax.random.key(cfg.seed))
    sk, pk = keygen(ctx, k_he)
    key = jax.random.fold_in(jax.random.key(cfg.seed), 1000)
    gp = jax.tree_util.tree_map(jnp.asarray, params0)
    outs = secure_fedavg_round(
        module, tc, mesh, ctx, pk, gp, jax.device_put(xs_p, place),
        jax.device_put(ys_p, place), key, with_plain_reference=True,
        num_real_clients=num_real)
    ct, mets, overflow, plain = outs[0], outs[1], outs[2], outs[-1]
    avg = decrypt_average(ctx, sk, ct, n_cl, PackSpec.for_params(gp, ctx.n),
                          meta=outs[3] if len(outs) == 5 else None,
                          base_params=gp)
    val_sys = np.asarray(mets, np.float64)[:n_cl, 0, 0]

    # ---- the reference: the same batches, client after client. The batches
    # are the program's own shuffle of the round key (secure_fedavg_round
    # splits it into a training and an encryption key, then per client).
    train_keys = jax.random.split(jax.random.split(key)[0], n_cl)
    perms = np.asarray(epoch_index_streams(tc, train_keys, m)[0])
    eye = np.eye(classes, dtype=np.float32)
    scaled = lambda a: np.asarray(a, np.float32) / 255.0  # noqa: E731
    ref_vg = _grad_fns(module, ref, None)[0]
    total, short, val_gaps, untrained, first_losses = None, None, [], [], []
    as_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t)
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        np.add, acc, t)
    for c in range(n_cl):
        if len(set(perms[c].ravel().tolist())) != steps * grp:
            raise RuntimeError("the check's batches repeat a row")
        x_tr, y_tr = xs[c, m - n_tr:], ys[c, m - n_tr:]
        trail, losses = adam.steps(
            ref_vg, params0, [(scaled(x_tr[i]), eye[y_tr[i]]) for i in perms[c]],
            tc.lr, tc.lr_decay, tc.warmup_steps)
        total, short = add(total, trail[-1]), add(short, trail[-2])
        first_losses.append(losses[0])
        val = scaled(xs[c, :m - n_tr]), eye[ys[c, :m - n_tr]]
        want = float(_ref_loss(ref)(as_f32(trail[-1]), *val))
        val_gaps.append(abs(val_sys[c] - want) / want)
        untrained.append(abs(float(_ref_loss(ref)(as_f32(params0), *val))
                             - want) / want)
    mean = lambda t: jax.tree_util.tree_map(lambda a: a / n_cl, t)  # noqa: E731
    moved = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - b, t, params0)
    leaves = lambda t: [np.asarray(v, np.float64)  # noqa: E731
                        for v in jax.tree_util.tree_leaves(t)]
    err = max(float(np.max(np.abs(a - b)))
              for a, b in zip(leaves(avg), leaves(plain)))
    say(check_round={"clients": n_cl, "images_a_client": m, "steps": steps,
                     "batch": grp, "validation_rows": m - n_tr},
        reference_first_step_loss=first_losses)
    d_sys, d_ref = moved(plain), moved(mean(total))
    return {
        "step_norm_gap": whole_norm_gap(d_sys, d_ref),
        "leaf_step_gap": norm_gap(d_sys, d_ref),
        "val_loss_gap": float(max(val_gaps)),
        "he_avg_err": err if math.isfinite(err) else float("inf"),
        "check_round_overflow": int(np.sum(np.asarray(overflow))),
        # for the record, what two faults read on the reference's side: an
        # optimizer step that returns its state unchanged (`step_norm_gap`),
        # a validation loss taken at the round's input weights
        "skipped_step_reads": whole_norm_gap(moved(mean(short)), d_ref),
        "untrained_val_reads": float(max(untrained)),
    }


# --------------------------------------------------------------------------
# what the harness and controls.py call
# --------------------------------------------------------------------------


def _parts(cell, cfg, data):
    """The reference, the optimizer it shares, the system's model and one
    timed batch of the seed's images with its one-hot labels."""
    import numpy as np

    from hefl_tpu.models import create_model

    (x, y) = data[0]
    ref = cell["module"]("reference", cell["config"]["reference"])
    adam = cell["module"]("reference", "adam")
    shape = tuple(int(d) for d in x.shape[1:])
    module, _ = create_model(cfg.model, num_classes=cfg.train.num_classes,
                             input_shape=shape)
    bs = cfg.train.batch_size
    xb = np.asarray(x[:bs], np.float32) / 255.0
    onehot = np.eye(cfg.train.num_classes, dtype=np.float32)[y[:bs]]
    return module, ref, adam, xb, onehot


def round_work(cell, cfg, data) -> dict:
    """A round's work: the training samples all clients complete, and 3 x
    the reference's forward FLOPs of them."""
    from hefl_tpu.fl.client import train_batch_geometry

    (x, y) = data[0]
    _, grp, steps = train_batch_geometry(
        cfg.train, len(y) // cfg.num_clients)
    samples_round = cfg.num_clients * cfg.train.epochs * steps * grp
    ref = cell["module"]("reference", cell["config"]["reference"])
    shape = tuple(int(d) for d in x.shape[1:])
    return {
        "samples_per_round": samples_round,
        "train_flops_per_round": 3 * samples_round * ref.forward_flops(
            shape, cfg.train.num_classes),
    }


def numbers(cell, cfg, data) -> dict:
    """The numbers judged against the configuration's `limits`; what is read
    for the record only is printed here. `encode_overflow` is the check
    round's: the harness adds the window's."""
    (x, y) = data[0]
    module, ref, adam, xb, onehot = _parts(cell, cfg, data)
    got = model_numbers(module, ref, xb, onehot, cfg.seed)
    say(logit_err_max=got.pop("logit_err_max"))  # for the record
    he = train_numbers(cfg, module, ref, adam, x, y)
    overflow = he.pop("check_round_overflow")
    say(skipped_step_would_read=he.pop("skipped_step_reads"),
        untrained_val_would_read=he.pop("untrained_val_reads"))
    got.update(he, encode_overflow=overflow)
    return got


def control_data(cfg):
    """The rows one seed's sound and control readings need: enough for the
    check round, not the cell's whole dataset."""
    from hefl_tpu.data import make_dataset

    n_train = cfg.num_clients * 2 * (CHECK_STEPS + 1) * cfg.train.batch_size
    return make_dataset(cfg.dataset, seed=cfg.seed, n_train=n_train, n_test=2)


def control_numbers(cell, cfg, data) -> dict:
    """Every reading of a sound run, and the control's: the reference
    computed in float8 in the system's place."""
    (x, y) = data[0]
    module, ref, adam, xb, onehot = _parts(cell, cfg, data)
    sound = model_numbers(module, ref, xb, onehot, cfg.seed)
    sound.update(train_numbers(cfg, module, ref, adam, x, y))
    return {"sound": sound,
            "control_fp8": model_numbers(module, ref, xb, onehot, cfg.seed,
                                         quant=fp8_quant)}
