"""The check of a cell whose model is a language model with a frozen base:
every client holds the base (bfloat16, never trained, never sent) and
trains, encrypts and sends a subset of the model's own parameters (here the
routers and the RMSNorm gains of `hefl_tpu/models/lm.py`). What a round's
work counts, and the numbers that decide `correct`, against the plain
float32 reference `<path>/reference/<reference>.py` (`init`, `forward`,
`loss`, `forward_flops` over the configuration file's own keys).

What `benchmarks/README.md` says of a check holds here (`round_work`,
`numbers`, `control_data`, `control_numbers`; the cell, the built
`ExperimentConfig`, the arrays of `make_dataset`). What is of this kind:

- **The weights are the reference's** (`ref.init(seed, conf)`): its base is
  planted as the system's (`models.set_frozen_base`), so one base is on the
  chip at a time, and its trained subset is the round's input.
- **One timed batch** (`cfg.train.batch_size` sequences, the system all at
  once, the reference a sequence at a time): `logit_err_vs_fp8`, the widest
  error of both heads' logits against the reference in units of the widest
  error the reference makes with its base matrix products in float8, each
  over the tokens whose routing agrees with the float32 reference's in every
  expert layer (a token routed elsewhere is another function of the input);
  `route_agree_share`, the share of the reference's (layer, token, slot)
  selections that the system's router makes *from the reference's own router
  inputs* (so it reads the router's arithmetic and not the layers before
  it; the share over the whole forward is printed as `route_agree_forward`);
  `loss_gap`, the system's loss against CE_main + w CE_mtp of its own logits;
  `grad_norm_gap`, worst trained leaf; `dropped_pairs`, (token, held expert)
  pairs the selections name and the grouped product was not given.
- **The check round**: one round of the cell's own geometry (clients, shard,
  batch, steps, client lowering, HE parameters) through
  `secure_fedavg_round` and `decrypt_average`, against `reference/adam.py`
  over the float32 reference on the same batches: `step_norm_gap`,
  `leaf_step_gap`, `val_loss_gap`, `he_avg_err`, and `base_moved`: base
  leaves whose bits differ after the round (by a wrapping sum of each leaf's
  words before and after).
- **Controls** (`control_numbers`): the reference in the system's place with
  its base products in float8, its router in bfloat16, the prediction loss
  left out, one held expert's output dropped; from the check round, a
  skipped step, a router left out of the update and the validation loss at
  the round's input weights; `controls.py --he-scale-bits 26` for the CKKS
  scale. PERF.md gives the readings and the limit each fails.

This check follows a synchronous, unpacked, IID round on a 1-D mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math


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
    whole = lambda t: math.sqrt(sum(n * n for n in _norms(t)))  # noqa: E731
    return abs(whole(got) - whole(want)) / whole(want)


def fp8_quant(a):
    """float8 (e4m3) values forward, the identity backward."""
    import jax
    import jax.numpy as jnp

    return a + jax.lax.stop_gradient(
        a.astype(jnp.float8_e4m3fn).astype(jnp.float32) - a)


# The reference in the system's place, one departure each.
VARIANTS = {
    "control_fp8": {"quant": fp8_quant},
    "control_router_bf16": {"router_dtype": "bfloat16"},
    "control_no_mtp": {"mtp": False},
    "control_dropped_expert": {"drop_expert": 0},
}


# Rows the reference gathers for one expert, as multiples of the mean load:
# with seeded weights one expert of 128 is routed up to nine times the mean
# (1,101 of 4,096 tokens, PR 27), so twelve, then twenty-four; a reading is
# kept only if the load it reports fits (`_ref_call`).
CAP_LADDER = (12, 24)
VG, LOSS, FULL = 0, 1, 2


@functools.lru_cache(maxsize=None)
def _ref_fns(ref, conf_json: str, variant: str | None, cap: int):
    """Jitted reference over (params, base, tokens [1, S + 2]): value-and-grad
    of the loss -> ((loss, (z_main, z_mtp, aux)), grads); the loss alone ->
    (loss, max_load); the loss with its logits. Compiled once a cell,
    whatever the seed."""
    import jax

    conf = json.loads(conf_json)
    kw = dict(VARIANTS[variant]) if variant else {}

    def loss(p, base, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.loss({"params": p, "base": base}, tokens, conf,
                            cap=cap, **kw)

    def loss_alone(p, base, tokens):
        value, (_, _, aux) = loss(p, base, tokens)
        return value, aux["max_load"]

    return (jax.jit(jax.value_and_grad(loss, has_aux=True)),
            jax.jit(loss_alone), jax.jit(loss))


def _ref_call(ref, conf, variant, which: int, p, base, tokens):
    """The reference on one sequence, at the first rung of `CAP_LADDER` that
    holds its busiest expert's rows (so the reading is exact)."""
    key = json.dumps(conf, sort_keys=True)
    t = int(tokens.shape[1]) - 2
    mean = t * conf["num_experts_per_tok"] / conf["held"]["router_width"]
    load = 0
    for mult in CAP_LADDER:
        cap = int(min(t, max(8, mult * mean)))
        out = _ref_fns(ref, key, variant, cap)[which](p, base, tokens)
        load = int(out[1] if which == LOSS else
                   (out[0] if which == VG else out)[1][2]["max_load"])
        if load <= cap:
            return out
        del out   # 0.7 GB a sequence at joyai's size, before the next rung
    raise RuntimeError(f"one expert was routed {load} of {t} tokens, more "
                       f"than {CAP_LADDER[-1]} times the mean: widen CAP_LADDER")


@functools.lru_cache(maxsize=None)
def _sys_fns(module):
    import jax

    from hefl_tpu.fl.loss import token_loss_fn

    def loss(p, base, tokens):
        return token_loss_fn(module.bind(base), p, tokens)[0]

    def logits(p, base, tokens):
        return module.apply({"params": p, "base": base}, tokens, routed=True)

    return jax.jit(jax.value_and_grad(loss)), jax.jit(logits)


@functools.lru_cache(maxsize=None)
def _compare_fns(module, conf_json: str):
    """On the device: agreement of two sets of selections, the logits' widest
    error over a token mask, the cross-entropy of given logits, and the
    system's router on the reference's router inputs."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.models import lm

    conf = json.loads(conf_json)

    def agree(sel, want):   # [L, T, k] each -> (share, all-layers token mask)
        hit = jnp.any(sel[..., :, None] == want[..., None, :], axis=-1)
        return jnp.mean(hit.astype(jnp.float32)), jnp.all(hit, axis=(0, 2))

    def err(z, want, mask):   # [1, S, V] x2, mask [S]
        d = jnp.max(jnp.abs(z - want), axis=-1).reshape(-1)
        return jnp.max(jnp.where(mask, d, 0.0))

    def ce(z_main, z_mtp, tokens):
        s = tokens.shape[1] - 2

        def one(z, t):
            lse = jax.nn.logsumexp(z, -1)
            hit = jnp.take_along_axis(z, t[..., None], -1)[..., 0]
            return jnp.mean(lse - hit)

        return (one(z_main, tokens[:, 1:s + 1]),
                one(z_mtp, tokens[:, 2:s + 2]))

    def routes(p, base, router_in):   # the system's router, layer by layer
        blocks = [(g, w) for g, w in zip(p["blocks"], base["blocks"])
                  if "router" in g] + [(p["mtp"]["block"], base["mtp"]["block"])]
        return jnp.stack([
            lm.route(module.arch, g["router"], w["bias"], x)[0]
            for (g, w), x in zip(blocks, router_in)])

    def held_pairs(sel):   # [L, T, k] -> pairs a layer that name a held expert
        lo = conf["held"]["first_expert"]
        here = (sel >= lo) & (sel < lo + conf["n_routed_experts"])
        return jnp.sum(here, axis=(1, 2))

    return tuple(jax.jit(f) for f in (agree, err, ce, routes, held_pairs))


def _leaf_sums(tree):
    """A wrapping sum of the words of every leaf: equal bits, equal sums."""
    import jax
    import jax.numpy as jnp

    def one(a):
        words = jax.lax.bitcast_convert_type(
            a, jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32)
        return jnp.sum(words.astype(jnp.uint32) * jnp.uint32(2654435761)
                       + jnp.arange(words.size, dtype=jnp.uint32).reshape(
                           words.shape))

    return [int(v) for v in jax.jit(
        lambda t: [one(a) for a in jax.tree_util.tree_leaves(t)])(tree)]


def model_numbers(module, ref, conf, variables, tokens, variant=None) -> dict:
    """One timed batch: the system (or, with `variant`, the reference with
    that departure in the system's place) against the float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = json.dumps(conf, sort_keys=True)
    p, base = variables["params"], variables["base"]
    call = functools.partial(_ref_call, ref, conf)
    agree, err, ce, routes, held_pairs = _compare_fns(module, key)
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    # the system (or its stand-in) on the whole batch
    if variant is None:
        sys_vg, sys_logits = _sys_fns(module)
        # What the system returns waits on the host, the reference's gradient
        # is summed there and a sequence's arrays go at the end of its turn
        # below, so that the chip holds the base alone when a reference
        # program loads: the gradient at the ladder's second rung reserves
        # 6.71 GB at the bottom of a chip of 16.9 beside the 6.64 GB base,
        # and with the batch's outputs there 6.07 were free (PERF.md, PR 45).
        l_sys, g_sys = jax.device_get(sys_vg(p, base, tokens))
        z1, z2, (loads, sel_sys) = jax.device_get(sys_logits(p, base, tokens))
        dropped = int(np.sum(np.abs(
            np.asarray(held_pairs(sel_sys)) - loads.sum(-1))))
    else:
        # forward only: a stand-in's gradient is not read
        dropped, g_sys = 0, None
        outs = [call(variant, FULL, p, base, tokens[i:i + 1]) for i in range(n)]
        l_sys = sum(float(o[0]) for o in outs) / n
    t_seq = tokens.shape[1] - 2
    worst, worst_fp8, shares, fwd_shares, ce_parts = 0.0, 0.0, [], [], []
    g_ref, kept = None, []
    for i in range(n):
        seq = tokens[i:i + 1]
        (_, (r1, r2, aux)), g = call(None, VG, p, base, seq)
        g = jax.device_get(g)
        g_ref = g if g_ref is None else jax.tree_util.tree_map(
            np.add, g_ref, g)
        if variant is None:
            s1, s2 = z1[i:i + 1], z2[i:i + 1]
            sel = sel_sys[:, i * t_seq:(i + 1) * t_seq]
            sel_router = routes(p, base, aux["router_in"])
        else:
            _, (s1, s2, v_aux) = outs[i]
            sel = sel_router = v_aux["experts"]
        share_fwd, mask = agree(sel, aux["experts"])
        shares.append(float(agree(sel_router, aux["experts"])[0]))
        fwd_shares.append(float(share_fwd))
        kept.append(float(jnp.mean(mask)))
        worst = max(worst, float(err(s1, r1, mask)), float(err(s2, r2, mask)))
        _, (f1, f2, f_aux) = call("control_fp8", FULL, p, base, seq)
        _, f_mask = agree(f_aux["experts"], aux["experts"])
        worst_fp8 = max(worst_fp8, float(err(f1, r1, f_mask)),
                        float(err(f2, r2, f_mask)))
        ce_parts.append([float(v) for v in ce(s1, s2, seq)])
        del _, r1, r2, aux, s1, s2, sel, sel_router, share_fwd, mask
        del f1, f2, f_aux, f_mask
    g_ref = jax.tree_util.tree_map(lambda a: a / n, g_ref)
    want = float(np.mean([a + conf["held"]["mtp_loss_weight"] * b
                          for a, b in ce_parts]))
    return {
        "logit_err_vs_fp8": worst / worst_fp8,
        "route_agree_share": min(shares),
        "loss_gap": abs(float(l_sys) - want) / want,
        **({} if g_sys is None else {"grad_norm_gap": norm_gap(g_sys, g_ref)}),
        "dropped_pairs": dropped,
        # for the record
        "route_agree_forward": min(fwd_shares),
        "tokens_compared_share": min(kept),
        "logit_err_max": worst,
    }


RECORD_ONLY = ("route_agree_forward", "tokens_compared_share", "logit_err_max",
               "skipped_step_reads", "router_left_out_reads",
               "untrained_val_reads")   # printed, not judged


def train_numbers(cfg, module, ref, adam, conf, variables, x, y) -> dict:
    """The check round: the cell's own round (clients, shard, batch, steps,
    client lowering, HE parameters) from the reference's seeded weights
    through `secure_fedavg_round` and `decrypt_average`, against a plain
    float32 Adam run over the same batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.ckks import packing
    from hefl_tpu.data import iid_contiguous, stack_federated
    from hefl_tpu.fl import decrypt_average, secure_fedavg_round
    from hefl_tpu.fl.client import epoch_index_streams, train_batch_geometry
    from hefl_tpu.fl.fedavg import pad_federated
    from hefl_tpu.models import set_frozen_base
    from hefl_tpu.parallel import client_mesh_size, client_sharding, make_mesh

    if (cfg.partition != "iid" or cfg.mesh_ct > 1 or cfg.stream is not None
            or (cfg.packing is not None and cfg.packing.enabled)):
        raise NotImplementedError(
            "this check follows a synchronous, unpacked, IID round on a 1-D "
            "mesh; a cell of another kind of round names a check of its own")
    n_cl = cfg.num_clients
    tc = dataclasses.replace(cfg.train, epochs=1)
    xs, ys = stack_federated(x, y, iid_contiguous(len(y), n_cl))
    m = int(xs.shape[1])
    n_tr, grp, steps = train_batch_geometry(tc, m)
    p0, base = variables["params"], variables["base"]
    set_frozen_base(module, base)
    before = _leaf_sums(base)

    mesh = make_mesh(n_cl)
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    place = client_sharding(mesh)
    ctx = cfg.he.build()
    _, k_he = jax.random.split(jax.random.key(cfg.seed))
    sk, pk = keygen(ctx, k_he)
    key = jax.random.fold_in(jax.random.key(cfg.seed), 1000)
    outs = secure_fedavg_round(
        module, tc, mesh, ctx, pk, p0, jax.device_put(xs_p, place),
        jax.device_put(ys_p, place), key, with_plain_reference=True,
        num_real_clients=num_real)
    ct, mets, overflow, plain = outs[0], outs[1], outs[2], outs[-1]
    spec = packing.spec_for(p0, ctx.n)   # of the trained subset alone
    if int(ct.c0.shape[0]) != -(-spec.total // ctx.n):
        raise RuntimeError(f"{ct.c0.shape[0]} ciphertext rows for "
                           f"{spec.total} trained parameters at N {ctx.n}")
    avg = decrypt_average(ctx, sk, ct, n_cl, spec,
                          meta=outs[3] if len(outs) == 5 else None,
                          base_params=p0)
    val_sys = np.asarray(mets, np.float64)[:n_cl, 0, 0]
    moved = sum(a != b for a, b in zip(before, _leaf_sums(base)))

    # ---- the reference: the same batches, client after client
    train_keys = jax.random.split(jax.random.split(key)[0], n_cl)
    perms = np.asarray(epoch_index_streams(tc, train_keys, m)[0])
    call = functools.partial(_ref_call, ref, conf, None)

    def batch_vg(p, rows, _):   # the mean over a batch's sequences
        got = [call(VG, p, base, jnp.asarray(rows[i:i + 1]))
               for i in range(len(rows))]
        loss = sum(float(g[0][0]) for g in got) / len(got)
        grads = jax.tree_util.tree_map(lambda *a: sum(a) / len(a),
                                       *[g[1] for g in got])
        return (loss, None), grads

    as_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t)
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        np.add, acc, t)
    total, short, val_gaps, untrained, first_losses = None, None, [], [], []
    for c in range(n_cl):
        if len(set(perms[c].ravel().tolist())) != steps * grp:
            raise RuntimeError("the check's batches repeat a row")
        x_tr = xs[c, m - n_tr:]
        trail, losses = adam.steps(
            batch_vg, p0, [(x_tr[i], None) for i in perms[c]],
            tc.lr, tc.lr_decay, tc.warmup_steps)
        total = add(total, trail[-1])
        short = add(short, trail[-2] if steps > 1 else as_f32(p0))
        first_losses.append(losses[0])
        val = jnp.asarray(xs[c, :m - n_tr])
        val_of = lambda p: float(np.mean([  # noqa: E731
            float(call(LOSS, as_f32(p), base, val[i:i + 1])[0])
            for i in range(len(val))]))
        want = val_of(trail[-1])
        val_gaps.append(abs(val_sys[c] - want) / want)
        untrained.append(abs(val_of(p0) - want) / want)
    mean = lambda t: jax.tree_util.tree_map(lambda a: a / n_cl, t)  # noqa: E731
    delta = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        t, p0)
    leaves = lambda t: [np.asarray(v, np.float64)  # noqa: E731
                        for v in jax.tree_util.tree_leaves(t)]
    err = max(float(np.max(np.abs(a - b)))
              for a, b in zip(leaves(avg), leaves(plain)))
    d_sys, d_ref = delta(plain), delta(mean(total))
    lost = jax.tree_util.tree_map(lambda a: a, d_sys)
    lost["blocks"][-1]["router"] = np.zeros_like(lost["blocks"][-1]["router"])
    say(check_round={"clients": n_cl, "sequences_a_client": m, "steps": steps,
                     "batch": grp, "validation_rows": m - n_tr,
                     "ciphertext_rows_a_client": int(ct.c0.shape[0])},
        reference_first_step_loss=first_losses)
    return {
        "step_norm_gap": whole_norm_gap(d_sys, d_ref),
        "leaf_step_gap": norm_gap(d_sys, d_ref),
        "val_loss_gap": float(max(val_gaps)),
        "he_avg_err": err if math.isfinite(err) else float("inf"),
        "base_moved": int(moved),
        "check_round_overflow": int(np.sum(np.asarray(overflow))),
        # what three faults would read, on the reference's side
        "skipped_step_reads": whole_norm_gap(delta(mean(short)), d_ref),
        "router_left_out_reads": norm_gap(lost, d_ref),
        "untrained_val_reads": float(max(untrained)),
    }


# --------------------------------------------------------------------------
# what the harness and controls.py call
# --------------------------------------------------------------------------


def _conf(cell) -> dict:
    """The configuration file's own keys, as the reference takes them: the
    published numbers at the top level and the group `held`."""
    return {k: v for k, v in cell["config"].items()
            if not isinstance(v, (dict, list, str)) or k == "held"}


def _parts(cell, cfg):
    """The reference, the optimizer, the configuration's own keys, the
    system's module (its seed fixed: the base is planted, and one module
    serves every seed of a process) and the reference's seeded weights."""
    from hefl_tpu.models import lm, set_frozen_base

    conf = _conf(cell)
    ref = cell["module"]("reference", cell["config"]["reference"])
    adam = cell["module"]("reference", "adam")
    module = lm.JoyAIFlash(num_classes=cfg.train.num_classes,
                           arch=lm.PRESETS[cfg.model], seed=0)
    set_frozen_base(module, None)   # the run's base goes before the check's comes
    variables = ref.init(cfg.seed, conf)
    return module, ref, adam, conf, variables


def round_work(cell, cfg, data) -> dict:
    """A round's work: a sample is a trained sequence; the operations are
    those of the train phase, 2 x forward of every trained token (forward and
    the activations' gradient: no weight gradient of the frozen base is
    formed, and the recomputed forward does not count) plus one forward of
    every validation token."""
    from hefl_tpu.fl.client import train_batch_geometry

    (x, y) = data[0]
    m = len(y) // cfg.num_clients
    n_tr, grp, steps = train_batch_geometry(cfg.train, m)
    trained = cfg.num_clients * cfg.train.epochs * steps * grp
    positions = int(x.shape[1]) - 2
    conf = _conf(cell)
    ref = cell["module"]("reference", cell["config"]["reference"])
    fwd = ref.forward_flops(conf, positions)["total"] * positions
    validated = cfg.num_clients * cfg.train.epochs * (m - n_tr)
    return {"samples_per_round": trained,
            "train_flops_per_round": (2 * trained + validated) * fwd}


def numbers(cell, cfg, data) -> dict:
    (x, y) = data[0]
    module, ref, adam, conf, variables = _parts(cell, cfg)
    bs = cfg.train.batch_size
    m = len(y) // cfg.num_clients
    batch = x[m - bs:m]   # two trained sequences of the first client's shard
    got = model_numbers(module, ref, conf, variables, batch)
    got.update(train_numbers(cfg, module, ref, adam, conf, variables, x, y))
    say(**{k: got.pop(k) for k in RECORD_ONLY})
    got["encode_overflow"] = got.pop("check_round_overflow")
    return got


def control_data(cfg):
    from hefl_tpu.data import make_dataset

    return make_dataset(cfg.dataset, seed=cfg.seed, n_train=cfg.n_train,
                        n_test=1)


def control_numbers(cell, cfg, data) -> dict:
    """Every reading of a sound run, and each control's."""
    (x, y) = data[0]
    module, ref, adam, conf, variables = _parts(cell, cfg)
    bs = cfg.train.batch_size
    m = len(y) // cfg.num_clients
    batch = x[m - bs:m]
    sound = model_numbers(module, ref, conf, variables, batch)
    sound.update(train_numbers(cfg, module, ref, adam, conf, variables, x, y))
    out = {"sound": sound}
    for name in VARIANTS:
        out[name] = model_numbers(module, ref, conf, variables, batch, name)
    return out
