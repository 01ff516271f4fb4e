"""The check of a cell whose model is a frozen-base language model with
grouped-query attention of two kinds, window layers with a trained sink a
head among them (`hefl_tpu/models/lm.py` with `kv_heads`; reference
`<path>/reference/mimo_v2_flash.py`): `checks/lm_subset.py`'s check, from
which it takes what the two share (`cell["module"]("checks", "lm_subset")`:
the check round `train_numbers` whole, the norms, the float8 stand-in), with
the window's edge, the sinks' gradient and one head (no prediction module)
held to the reference as well.

What `lm_subset`'s docstring says holds here: the weights are the
reference's, its base planted as the system's; one timed batch; the check
round through `secure_fedavg_round` and `decrypt_average`. What differs:

- **The sinks are planted away from 0** (ln(window) + normal(1) from the
  seed, so that a sink weighs as much as a whole window of equal scores on
  average, a seventh of it or seven times it a head: `sink_mass_share`,
  printed for the record, reads about a half): at 0, where training starts
  them, a sink is one key among 128 and leaving it out would move nothing
  a tolerance could see.
- `sink_grad_gap`: the gradient of the system's loss with respect to the
  sinks of every window layer against the reference's, the norm of the
  difference over the reference's norm.
- `window_leak`: the system's window layer (`lm.grouped_attention`) on the
  reference's own layer input, and again with every position <= t - window
  replaced by noise (so their keys and values are): the largest change of
  any output at positions >= t, worst window layer. Exactly 0.
- `window_edge_gap`: the same layer's output against the reference's layer
  output (`aux["attn_out"]`), the norm of the difference over the
  reference's norm, worst window layer: a window one key wider or narrower
  moves it by percents, bfloat16 by parts in a thousand.
- The logits (one head) are compared over the tokens whose *held* experts
  are chosen as the reference chooses them in every expert layer
  (`lm_sparse_subset`'s rule: 1 expert in 16 is held, and two absent
  experts changing places move nothing but a sum of near-ties), in units of
  what the float8 reference errs over its own such tokens. `loss_gap` is the
  system's loss against the cross-entropy of its own logits.
- One sequence at a time and in three calls of the reference (its gradient;
  what its routers and attention layers saw and gave; the float8 stand-in).

Controls (`control_numbers`), each the reference in the system's place with
one departure: `lm_subset`'s float8 base products, bfloat16 router and a
held expert's output dropped; the sink left out; a window of 129; of 127;
every layer masked as the other kind is; the two RoPE bases exchanged;
every dim of a head rotated; the value scale left out; query head j reading
KV head j mod G. PERF.md gives the readings and the limit each fails.

This check follows a synchronous, unpacked, IID round on a 1-D mesh.
"""

from __future__ import annotations

import functools
import json


def _shared(cell):
    return cell["module"]("checks", "lm_subset")


# The reference in the system's place, one departure each (`wider`,
# `narrower` and `head` are filled in by `_variants`).
WINDOW_VARIANTS = {
    "control_no_sink": {"sink": False},
    "control_window_129": {"window": "wider"},
    "control_window_127": {"window": "narrower"},
    "control_kinds_exchanged": {"flip_kinds": True},
    "control_thetas_exchanged": {"swap_thetas": True},
    "control_all_rotated": {"rotary_dims": "head"},
    "control_no_value_scale": {"value_scale": 1.0},
    "control_kv_head_mod": {"kv_map": "mod"},
}
SHARED_VARIANTS = ("control_fp8", "control_router_bf16",
                   "control_dropped_expert")


def _variants(shared, conf) -> dict:
    fill = {"wider": conf["sliding_window"] + 1,
            "narrower": conf["sliding_window"] - 1, "head": conf["head_dim"]}
    out = {name: dict(shared.VARIANTS[name]) for name in SHARED_VARIANTS}
    for name, kw in WINDOW_VARIANTS.items():
        out[name] = {k: fill.get(v, v) if isinstance(v, str) else v
                     for k, v in kw.items()}
    return out


def _window_layers(conf) -> list:
    """The held layers of the window kind, by their place among the blocks."""
    held = conf["hybrid_layer_pattern"][:conf["num_hidden_layers"]]
    return [i for i, kind in enumerate(held) if kind]


INPUTS, FULL = 0, 1
RECORD_ONLY = ("sink_mass_share",)   # with `lm_subset.RECORD_ONLY`
LAYER_KW = ("sink", "window", "flip_kinds", "swap_thetas", "rotary_dims",
            "value_scale", "kv_map")   # the departures one layer can show


@functools.lru_cache(maxsize=None)
def _ref_fns(ref, shared, conf_json: str, variant: str | None, cap: int):
    """Jitted reference over (params, base, tokens [1, S + 2]), for what
    `lm_subset._ref_fns` has not: what its routers saw, what its window
    layers saw and gave; the loss with its logits and aux under a variant."""
    import jax
    import jax.numpy as jnp

    conf = json.loads(conf_json)
    kw = dict(_variants(shared, conf)[variant]) if variant else {}

    def loss(p, base, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.loss({"params": p, "base": base}, tokens, conf,
                            cap=cap, **kw)

    def inputs(p, base, tokens):
        with jax.default_matmul_precision("highest"):
            aux = ref.forward({"params": p, "base": base}, tokens, conf,
                              cap=cap, keep_inputs=True)[1]
        mine = jnp.asarray(_window_layers(conf))
        return ((aux["router_in"], aux["attn_in"][mine], aux["attn_out"][mine]),
                aux["max_load"])

    return jax.jit(inputs), jax.jit(loss)


def _ref_call(ref, shared, conf, variant, which: int, p, base, tokens):
    """The reference on one sequence, at the first rung of `CAP_LADDER` that
    holds its busiest expert's rows (`lm_subset._ref_call`'s rule)."""
    key = json.dumps(conf, sort_keys=True)
    t = int(tokens.shape[1]) - 2
    mean = t * conf["num_experts_per_tok"] / conf["held"]["router_width"]
    load = 0
    for mult in shared.CAP_LADDER:
        cap = int(min(t, max(8, mult * mean)))
        out = _ref_fns(ref, shared, key, variant, cap)[which](p, base, tokens)
        load = int(out[1] if which == INPUTS else out[1][2]["max_load"])
        if load <= cap:
            return out
        del out
    raise RuntimeError(f"one expert was routed {load} of {t} tokens, more "
                       f"than {shared.CAP_LADDER[-1]} times the mean")


@functools.lru_cache(maxsize=None)
def _sys_fns(module):
    import jax

    def loss(p, base, tokens):
        return module.loss({"params": p, "base": base}, tokens)[0]

    def logits(p, base, tokens):
        z, _, seen = module.apply({"params": p, "base": base}, tokens,
                                  routed=True)
        return z, seen

    return jax.jit(jax.value_and_grad(loss)), jax.jit(logits)


@functools.lru_cache(maxsize=None)
def _compare_fns(module, ref, shared, conf_json: str):
    """On the device: agreement of two sets of selections, the logits'
    widest error over a token mask, the cross-entropy of given logits, the
    system's router on the reference's router inputs, and one window layer
    (the system's, or the reference's with a departure) on the reference's
    layer input: its gap to the reference's output and what leaks past the
    window's edge."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.models import lm

    conf = json.loads(conf_json)
    z = ref._sizes(conf)
    first, n_held = conf["held"]["first_expert"], conf["n_routed_experts"]
    held = lambda e: (e >= first) & (e < first + n_held)  # noqa: E731

    def agree(sel, want):
        """[L, T, k] each -> (the share of `sel`'s slots that `want` has
        too, the tokens whose held experts are the same in every layer)."""
        hit = jnp.any(sel[..., :, None] == want[..., None, :], axis=-1)
        back = jnp.any(want[..., :, None] == sel[..., None, :], axis=-1)
        apart = (held(sel) & ~hit) | (held(want) & ~back)
        return jnp.mean(hit.astype(jnp.float32)), ~jnp.any(apart, axis=(0, 2))

    def err(got, want, mask):   # [1, S, V] x2, mask [S]
        d = jnp.max(jnp.abs(got - want), axis=-1).reshape(-1)
        return jnp.max(jnp.where(mask, d, 0.0))

    def ce(logits, tokens):
        s = tokens.shape[1] - 2
        lse = jax.nn.logsumexp(logits, -1)
        hit = jnp.take_along_axis(logits, tokens[:, 1:s + 1, None], -1)[..., 0]
        return jnp.mean(lse - hit)

    def routes(p, base, router_in):   # the system's router, layer by layer
        blocks = [(g, w) for g, w in zip(p["blocks"], base["blocks"])
                  if "router" in g]
        return jnp.stack([
            lm.route(module.arch, g["router"], w["bias"], x)[0]
            for (g, w), x in zip(blocks, router_in)])

    def held_pairs(sel):   # [L, T, k] -> pairs a layer that name a held expert
        return jnp.sum(held(sel), axis=(1, 2))

    def edge(layer, w, g, x, want, noise):
        """-> (gap to the reference's output, the largest change at
        positions >= t when positions <= t - window hold noise)."""
        s = x.shape[1]
        t = s // 2 + 5
        out = layer(w, g, x)
        behind = (jnp.arange(s) <= t - z["window"])[None, :, None]
        moved = layer(w, g, jnp.where(behind, noise, x))
        gap = jnp.linalg.norm(out - want) / jnp.linalg.norm(want)
        return gap, jnp.max(jnp.abs(moved[:, t:] - out[:, t:]))

    def layer_of(variant):
        if variant is None:   # the system's window layer
            return lambda w, g, x: lm.grouped_attention(module.arch, 1, w, g, x)
        kw = _variants(shared, conf)[variant]
        mm = ref._Products(kw.get("quant"))
        kw = {k: v for k, v in kw.items() if k in LAYER_KW}

        def layer(w, g, x):
            with jax.default_matmul_precision("highest"):
                return ref.attention(z, 1, w, g, x, mm, **kw)

        return layer

    edges = {name: jax.jit(functools.partial(edge, layer_of(name)))
             for name in (None, *_variants(shared, conf))}
    return (*(jax.jit(f) for f in (agree, err, ce, routes, held_pairs)), edges)


def plant_sinks(variables, seed: int, window: int):
    """`variables` with every sink at ln(window) + normal(1) from the seed."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), 77)
    blocks = [
        dict(g, sink=jnp.log(float(window)) + jax.random.normal(
            jax.random.fold_in(key, i), g["sink"].shape, jnp.float32))
        if "sink" in g else g
        for i, g in enumerate(variables["params"]["blocks"])]
    return {"base": variables["base"],
            "params": dict(variables["params"], blocks=blocks)}


class _Readings:
    """One kind's numbers (the system's, or a control's) over the batch's
    sequences: the worst of each."""

    def __init__(self):
        self.err = self.edge = self.leak = 0.0
        self.shares, self.fwd_shares, self.kept = [], [], []
        self.ces, self.losses = [], []
        self.dropped = 0
        self.grads = None

    def numbers(self, shared, fp8_err, g_ref) -> dict:
        import jax
        import numpy as np

        want = float(np.mean(self.ces))
        out = {
            "logit_err_vs_fp8": self.err / fp8_err,
            "route_agree_share": min(self.shares),
            "loss_gap": abs(float(np.mean(self.losses)) - want) / want,
            "dropped_pairs": self.dropped,
            "window_leak": self.leak,
            "window_edge_gap": self.edge,
            # for the record
            "route_agree_forward": min(self.fwd_shares),
            "tokens_compared_share": min(self.kept),
            "logit_err_max": self.err,
        }
        if self.grads is not None:
            out["grad_norm_gap"] = shared.norm_gap(self.grads, g_ref)
            sinks = lambda t: np.concatenate([  # noqa: E731
                np.asarray(g["sink"], np.float64).ravel()
                for g in jax.tree_util.tree_map(np.asarray, t)["blocks"]
                if "sink" in g])
            got, ref_g = sinks(self.grads), sinks(g_ref)
            out["sink_grad_gap"] = float(
                np.linalg.norm(got - ref_g) / np.linalg.norm(ref_g))
        return out


def model_numbers(shared, module, ref, conf, variables, tokens,
                  variants=()) -> dict:
    """One timed batch, a sequence at a time: {"sound": the system against
    the float32 reference, and for each name of `variants` the reference
    with that departure in the system's place}. The reference's own side of
    a sequence (its logits, selections and gradient, what its layers saw
    and gave, and the float8 stand-in's error, the unit of the logits'
    number) is read once and serves every kind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = json.dumps(conf, sort_keys=True)
    p, base = variables["params"], variables["base"]
    call = functools.partial(_ref_call, ref, shared, conf)
    agree, err, ce, routes, held_pairs, edges = _compare_fns(
        module, ref, shared, key)
    tokens = jnp.asarray(tokens)
    window_layers = _window_layers(conf)
    layer_departs = {None: True, **{
        name: any(k in LAYER_KW or k == "quant" for k in kw)
        for name, kw in _variants(shared, conf).items()}}
    kinds = {name: _Readings() for name in ("sound", *variants)}
    fp8_err, g_ref, mass = 0.0, None, []
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        jnp.add, acc, t)

    def compare(kind, loss, logits, sel, seq, want, aux):
        kind.losses.append(float(loss))
        share_fwd, mask = agree(sel, aux["experts"])
        kind.fwd_shares.append(float(share_fwd))
        kind.kept.append(float(jnp.mean(mask)))
        kind.err = max(kind.err, float(err(logits, want, mask)))
        kind.ces.append(float(ce(logits, seq)))

    def at_the_edge(kind, name, attn_in, attn_out, noise):
        if not layer_departs[name]:   # the reference's own layer: 0 and 0
            return
        for n, i in enumerate(window_layers):
            gap, leak = edges[name](base["blocks"][i]["attn"], p["blocks"][i],
                                    attn_in[n], attn_out[n], noise)
            kind.edge = max(kind.edge, float(gap))
            kind.leak = max(kind.leak, float(leak))

    for i in range(tokens.shape[0]):
        seq = tokens[i:i + 1]
        (_, (want, _, aux)), g = shared._ref_call(ref, conf, None, shared.VG, p,
                                                  base, seq)
        g_ref = add(g_ref, g)
        # ---- the system (its step's working set before the layers' inputs)
        kind = kinds["sound"]
        sys_vg, sys_logits = _sys_fns(module)
        l_sys, g = sys_vg(p, base, seq)
        kind.grads = add(kind.grads, g)
        logits, (loads, sel) = sys_logits(p, base, seq)
        kind.dropped += int(np.sum(np.abs(
            np.asarray(held_pairs(sel)) - np.asarray(loads).sum(-1))))
        compare(kind, l_sys, logits, sel, seq, want, aux)
        del logits, sel
        (router_in, attn_in, attn_out), _ = call(None, INPUTS, p, base, seq)
        kind.shares.append(float(agree(routes(p, base, router_in),
                                       aux["experts"])[0]))
        del router_in
        noise = jax.random.normal(jax.random.key(i), attn_in[0].shape,
                                  jnp.float32)
        at_the_edge(kind, None, attn_in, attn_out, noise)
        # ---- the float8 stand-in: the unit, and the first control
        for name in dict.fromkeys(("control_fp8", *variants)):
            if name in shared.VARIANTS:
                loss, (logits, _, v_aux) = shared._ref_call(
                    ref, conf, name, shared.FULL, p, base, seq)
            else:
                loss, (logits, _, v_aux) = call(name, FULL, p, base, seq)
            if name == "control_fp8":
                _, f_mask = agree(v_aux["experts"], aux["experts"])
                fp8_err = max(fp8_err, float(err(logits, want, f_mask)))
            if name in kinds:   # a stand-in's gradient is not read
                compare(kinds[name], loss, logits, v_aux["experts"], seq, want,
                        aux)
                kinds[name].shares.append(kinds[name].fwd_shares[-1])
                at_the_edge(kinds[name], name, attn_in, attn_out, noise)
            del logits, v_aux
        del want, aux, attn_in, attn_out
    n = tokens.shape[0]
    g_ref = jax.tree_util.tree_map(lambda a: a / n, g_ref)
    kinds["sound"].grads = jax.tree_util.tree_map(lambda a: a / n,
                                                  kinds["sound"].grads)
    got = {name: kind.numbers(shared, fp8_err, g_ref)
           for name, kind in kinds.items()}
    # for the record: the share of a head's mass a planted sink would take of
    # a window of equal scores
    sinks = np.concatenate([np.asarray(g["sink"]).ravel()
                            for g in p["blocks"] if "sink" in g])
    got["sound"]["sink_mass_share"] = float(np.mean(
        np.exp(sinks) / (np.exp(sinks) + conf["sliding_window"])))
    return got


# --------------------------------------------------------------------------
# what the harness and controls.py call
# --------------------------------------------------------------------------


def _conf(cell) -> dict:
    """The configuration file's own keys, as the reference takes them: the
    published numbers at the top level, the two published lists a layer and
    the group `held`."""
    return {k: v for k, v in cell["config"].items()
            if not isinstance(v, (dict, list, str))
            or k in ("held", "hybrid_layer_pattern", "moe_layer_freq")}


def _parts(cell, cfg):
    from hefl_tpu.models import lm, set_frozen_base

    conf = _conf(cell)
    ref = cell["module"]("reference", cell["config"]["reference"])
    adam = cell["module"]("reference", "adam")
    module = lm.FrozenBaseLM(num_classes=cfg.train.num_classes,
                             arch=lm.PRESETS[cfg.model], seed=0)
    set_frozen_base(module, None)   # the run's base goes before the check's comes
    variables = plant_sinks(ref.init(cfg.seed, conf), cfg.seed,
                            conf["sliding_window"])
    return module, ref, adam, conf, variables


def round_work(cell, cfg, data) -> dict:
    """`lm_subset.round_work` over this reference's `forward_flops` (the
    model's own count: a window layer's attention over the pairs its window
    allows, a global layer's over the causal ones)."""
    from hefl_tpu.fl.client import train_batch_geometry

    (x, y) = data[0]
    m = len(y) // cfg.num_clients
    n_tr, grp, steps = train_batch_geometry(cfg.train, m)
    trained = cfg.num_clients * cfg.train.epochs * steps * grp
    positions = int(x.shape[1]) - 2
    ref = cell["module"]("reference", cell["config"]["reference"])
    fwd = ref.forward_flops(_conf(cell), positions)["total"] * positions
    validated = cfg.num_clients * cfg.train.epochs * (m - n_tr)
    return {"samples_per_round": trained,
            "train_flops_per_round": (2 * trained + validated) * fwd}


def _numbers(cell, cfg, data, controls: bool) -> dict:
    shared = _shared(cell)
    (x, y) = data[0]
    module, ref, adam, conf, variables = _parts(cell, cfg)
    bs = cfg.train.batch_size
    m = len(y) // cfg.num_clients
    batch = x[m - bs:m]   # the trained sequences of the first client's batch
    got = model_numbers(shared, module, ref, conf, variables, batch,
                        tuple(_variants(shared, conf)) if controls else ())
    got["sound"].update(shared.train_numbers(cfg, module, ref, adam, conf,
                                             variables, x, y))
    return got


def numbers(cell, cfg, data) -> dict:
    shared = _shared(cell)
    got = _numbers(cell, cfg, data, controls=False)["sound"]
    shared.say(**{k: got.pop(k) for k in (*shared.RECORD_ONLY, *RECORD_ONLY)})
    got["encode_overflow"] = got.pop("check_round_overflow")
    return got


def control_data(cfg):
    from hefl_tpu.data import make_dataset

    return make_dataset(cfg.dataset, seed=cfg.seed, n_train=cfg.n_train,
                        n_test=1)


def control_numbers(cell, cfg, data) -> dict:
    """Every reading of a sound run, and each control's."""
    return _numbers(cell, cfg, data, controls=True)
