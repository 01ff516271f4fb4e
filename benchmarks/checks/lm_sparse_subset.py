"""The check of a cell whose model is a frozen-base language model with
learned sparse attention (`hefl_tpu/models/lm.py` with an indexer; reference
`<path>/reference/deepseek_v32.py`): `checks/lm_subset.py`'s check, from
which it takes what the two share (`cell["module"]("checks", "lm_subset")`:
the check round `train_numbers` whole, the norms, the float8 stand-in), with
the selection held to the reference as well.

What `lm_subset`'s docstring says holds here: the weights are the
reference's, its base planted as the system's; one timed batch; the check
round through `secure_fedavg_round` and `decrypt_average`. What is added:

- `select_agree_share`: of the reference's selected (query, key) pairs at
  queries past position `index_topk - 1` (earlier queries select every causal
  key), the share the system's indexer selects *from the reference's own
  indexer inputs* (x and c_q of each layer, so it reads the indexer's
  arithmetic and the selection, not the layers before it), worst layer.
- `selected_outside_causal`: pairs the system selects with key > query;
  `selected_count_gap`: the widest difference between the pairs a layer
  selects and min(t + 1, index_topk) summed over its queries, over those
  selections and over the counts of the system's own forward. Both 0.
- The logits are compared twice, each in units of what the float8 reference
  errs over its own such tokens: `logit_err_vs_fp8` over positions below
  `index_topk`, where every causal key is selected in every layer;
  `logit_err_late_vs_fp8` over the later ones, where a key the system and
  the reference rank differently at the edge of a selection moves a query's
  attention. The tokens compared are those whose *held* experts are selected
  as the reference selects them in every expert layer. `lm_subset` asks that
  of every slot; here 1 expert in 32 is held and 7% of the slots of a whole
  forward differ from the reference's (near-ties in bfloat16), so that rule
  would keep a fifth of the tokens, few of them late ones, and of the float8
  stand-in's none (my chip run, PR 31). Two absent experts changing places
  move nothing but the sum the weights are divided by, by the difference of
  two scores that tie: the output stays the same function of the input.
- One sequence at a time and in three calls of the reference (its gradient;
  what its routers and indexers saw; the float8 stand-in), so that at 8,192
  positions of hidden 7,168 each fits beside the 7.84 GB base.

Controls (`control_numbers`): `lm_subset`'s four (float8 base products,
bfloat16 router, prediction loss left out, a held expert's output dropped)
and, each the reference in the system's place with one departure: the
indexer's products in float8, a selection of half the keys, the selection
ignored (dense causal attention), the indexer's ReLU left out, plain top-k
routing without groups, unscaled RoPE. PERF.md gives the readings and the
limit each fails.

This check follows a synchronous, unpacked, IID round on a 1-D mesh.
"""

from __future__ import annotations

import functools
import json
import math


def _shared(cell):
    return cell["module"]("checks", "lm_subset")


def fp8_values(a):
    """float32 values at float8's (e4m3) three mantissa bits, to nearest
    even, by integer operations on the bits. `lm_subset.fp8_quant`'s float32
    -> float8 -> float32 round trip is the identity on the chip wherever no
    product takes the float8 value directly (my chip run, PR 31: under `jit`
    it returned its input, and this control read as the reference); the
    compiler cannot drop these. The exponent stays float32's (e4m3 would
    flush below 2^-9 and stop at 448: a normed q or k is far from both)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    odd = (bits >> 20) & jnp.uint32(1)
    kept = (bits + jnp.uint32(0x7FFFF) + odd) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(kept, jnp.float32)


# The reference in the system's place, one departure each (`fp8` is
# `fp8_values`, filled in by `_variants`).
SPARSE_VARIANTS = {
    "control_index_fp8": {"index_quant": "fp8"},
    "control_top_half": {"index_topk": "half"},
    "control_dense": {"dense": True},
    "control_no_relu": {"index_relu": False},
    "control_no_groups": {"groups": False},
    "control_no_yarn": {"yarn": False},
}


def _variants(shared, conf) -> dict:
    fill = {"fp8": fp8_values, "half": conf["index_topk"] // 2}
    out = dict(shared.VARIANTS)
    for name, kw in SPARSE_VARIANTS.items():
        out[name] = {k: fill.get(v, v) if isinstance(v, str) else v
                     for k, v in kw.items()}
    return out


@functools.lru_cache(maxsize=None)
def _ref_fns(ref, shared, conf_json: str, variant: str | None, cap: int):
    """Jitted reference over (params, base, tokens [1, S + 2]), for what
    `lm_subset._ref_fns` has not: what its routers and its indexers saw;
    the loss with its logits and aux under one of `SPARSE_VARIANTS`."""
    import jax

    conf = json.loads(conf_json)
    kw = dict(_variants(shared, conf)[variant]) if variant else {}

    def loss(p, base, tokens):
        with jax.default_matmul_precision("highest"):
            return ref.loss({"params": p, "base": base}, tokens, conf,
                            cap=cap, **kw)

    def inputs(p, base, tokens):
        with jax.default_matmul_precision("highest"):
            aux = ref.forward({"params": p, "base": base}, tokens, conf,
                              cap=cap, keep_inputs=True)[2]
        return aux["router_in"], aux["index_in"], aux["max_load"]

    return jax.jit(inputs), jax.jit(loss)


INPUTS, FULL = 0, 1
RECORD_ONLY = ("tokens_compared_late_share",)   # with `lm_subset.RECORD_ONLY`


def _ref_call(ref, shared, conf, variant, which: int, p, base, tokens):
    """The reference on one sequence, at the first rung of `CAP_LADDER` that
    holds its busiest expert's rows (`lm_subset._ref_call`'s rule; the
    float32 reference and `lm_subset`'s own variants go through that)."""
    if which == FULL and variant in shared.VARIANTS:
        return shared._ref_call(ref, conf, variant, shared.FULL, p, base, tokens)
    key = json.dumps(conf, sort_keys=True)
    t = int(tokens.shape[1]) - 2
    mean = t * conf["num_experts_per_tok"] / conf["held"]["router_width"]
    load = 0
    for mult in shared.CAP_LADDER:
        cap = int(min(t, max(8, mult * mean)))
        out = _ref_fns(ref, shared, key, variant, cap)[which](p, base, tokens)
        load = int(out[2] if which == INPUTS else out[1][2]["max_load"])
        if load <= cap:
            return out
        del out
    raise RuntimeError(f"one expert was routed {load} of {t} tokens, more "
                       f"than {shared.CAP_LADDER[-1]} times the mean")


@functools.lru_cache(maxsize=None)
def _sys_fns(module):
    import jax

    def loss(p, base, tokens):
        total, (_, _, counts) = module.loss({"params": p, "base": base}, tokens)
        return total, counts

    def logits(p, base, tokens):
        return module.apply({"params": p, "base": base}, tokens, routed=True)

    return jax.jit(jax.value_and_grad(loss, has_aux=True)), jax.jit(logits)


@functools.lru_cache(maxsize=None)
def _compare_fns(module, conf_json: str):
    """On the device: `lm_subset._compare_fns`'s comparisons, the logits'
    error over two token masks at once, and the system's indexer on one
    layer of the reference's indexer inputs."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.models import lm

    conf = json.loads(conf_json)
    topk = conf["index_topk"]

    first, n_held = conf["held"]["first_expert"], conf["n_routed_experts"]
    held = lambda e: (e >= first) & (e < first + n_held)  # noqa: E731

    def agree(sel, want):
        """[L, T, k] each -> (the share of `sel`'s slots that `want` has
        too, the tokens whose held experts are the same in every layer)."""
        hit = jnp.any(sel[..., :, None] == want[..., None, :], axis=-1)
        back = jnp.any(want[..., :, None] == sel[..., None, :], axis=-1)
        apart = (held(sel) & ~hit) | (held(want) & ~back)
        return jnp.mean(hit.astype(jnp.float32)), ~jnp.any(apart, axis=(0, 2))

    def errs(z1, z2, r1, r2, mask):   # [1, S, V] x4, mask [S] -> early, late
        d = jnp.maximum(jnp.max(jnp.abs(z1 - r1), axis=-1),
                        jnp.max(jnp.abs(z2 - r2), axis=-1)).reshape(-1)
        early = jnp.arange(d.shape[0]) < topk
        return (jnp.max(jnp.where(mask & early, d, 0.0)),
                jnp.max(jnp.where(mask & ~early, d, 0.0)))

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
        return jnp.sum(held(sel), axis=(1, 2))

    def picks(w_index, x, c_q, want):
        """One layer: the system's selection on the reference's indexer
        inputs against the reference's selection `want` [B, S, S] -> (the
        reference's pairs past `topk` queries, those the system has too, the
        system's pairs above the diagonal, the system's pairs)."""
        got = lm.select_keys(module.arch, w_index, x, c_q)
        s = got.shape[-1]
        late = (jnp.arange(s) >= topk)[None, :, None]
        above = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
        count = lambda m: jnp.sum(m, dtype=jnp.int32)  # noqa: E731
        return (count(want & late), count(got & want & late),
                count(got & above[None]), count(got))

    def picked_agree(got, want):   # a stand-in's selections [L, B, S, S]
        s = got.shape[-1]
        late = (jnp.arange(s) >= topk)[None, None, :, None]
        n = lambda m: jnp.sum(m, axis=(1, 2, 3), dtype=jnp.int32)  # noqa: E731
        return n(want & late), n(got & want & late), n(got)

    return tuple(jax.jit(f) for f in (agree, errs, ce, routes, held_pairs,
                                      picks, picked_agree))


def _pairs_a_layer(ref, conf, tokens) -> int:
    return int(tokens.shape[0]) * ref.selected_pairs(
        int(tokens.shape[1]) - 2, conf["index_topk"])


class _Readings:
    """One kind's numbers (the system's, or a control's) over the batch's
    sequences: the worst of each."""

    def __init__(self):
        self.err = {"early": 0.0, "late": 0.0}
        self.shares, self.fwd_shares, self.picked, self.kept = [], [], [], []
        self.kept_late = []
        self.ce_parts, self.losses = [], []
        self.outside = self.count_gap = self.dropped = 0
        self.grads = None

    def numbers(self, shared, conf, fp8_err, g_ref) -> dict:
        import numpy as np

        want = float(np.mean([a + conf["held"]["mtp_loss_weight"] * b
                              for a, b in self.ce_parts]))
        # a sequence no longer than `index_topk` has no late position (0);
        # late positions none of which could be compared decide nothing (inf)
        late = 0.0
        if self.kept_late:
            compared = min(self.kept_late) > 0 and fp8_err["late"] > 0
            late = self.err["late"] / fp8_err["late"] if compared else math.inf
        out = {
            "logit_err_vs_fp8": self.err["early"] / fp8_err["early"],
            "logit_err_late_vs_fp8": late,
            "route_agree_share": min(self.shares),
            "select_agree_share": min(self.picked),
            "selected_outside_causal": self.outside,
            "selected_count_gap": self.count_gap,
            "loss_gap": abs(float(np.mean(self.losses)) - want) / want,
            "dropped_pairs": self.dropped,
            # for the record
            "route_agree_forward": min(self.fwd_shares),
            "tokens_compared_share": min(self.kept),
            "tokens_compared_late_share": min(self.kept_late, default=1.0),
            "logit_err_max": max(self.err.values()),
        }
        if self.grads is not None:
            out["grad_norm_gap"] = shared.norm_gap(self.grads, g_ref)
        return out


def model_numbers(shared, module, ref, conf, variables, tokens,
                  variants=()) -> dict:
    """One timed batch, a sequence at a time: {"sound": the system against
    the float32 reference, and for each name of `variants` the reference
    with that departure in the system's place}. The reference's own side of
    a sequence (its logits, selections and gradient, and the float8
    stand-in's error, the unit of both logit numbers) is read once and
    serves every kind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = json.dumps(conf, sort_keys=True)
    p, base = variables["params"], variables["base"]
    call = functools.partial(_ref_call, ref, shared, conf)
    agree, errs, ce, routes, held_pairs, picks, picked_agree = _compare_fns(
        module, key)
    tokens = jnp.asarray(tokens)
    layers = base["blocks"] + [base["mtp"]["block"]]
    want_pairs = _pairs_a_layer(ref, conf, tokens[:1])
    topk = conf["index_topk"]
    kinds = {name: _Readings() for name in ("sound", *variants)}
    fp8_err, g_ref = {"early": 0.0, "late": 0.0}, None
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        jnp.add, acc, t)

    def compare(kind, loss, s1, s2, sel, sel_router, seq, r1, r2, aux):
        kind.losses.append(float(loss))
        share_fwd, mask = agree(sel, aux["experts"])
        kind.shares.append(float(agree(sel_router, aux["experts"])[0]))
        kind.fwd_shares.append(float(share_fwd))
        kind.kept.append(float(jnp.mean(mask)))
        if mask.shape[0] > topk:
            kind.kept_late.append(float(jnp.mean(mask[topk:])))
        early, late = errs(s1, s2, r1, r2, mask)
        kind.err["early"] = max(kind.err["early"], float(early))
        kind.err["late"] = max(kind.err["late"], float(late))
        kind.ce_parts.append([float(v) for v in ce(s1, s2, seq)])

    for i in range(tokens.shape[0]):
        seq = tokens[i:i + 1]
        (_, (r1, r2, aux)), g = shared._ref_call(ref, conf, None, shared.VG, p,
                                                 base, seq)
        g_ref = add(g_ref, g)
        # ---- the system
        kind = kinds["sound"]
        router_in, (xs, c_qs), _ = call(None, INPUTS, p, base, seq)
        sel_router = routes(p, base, router_in)
        del router_in
        got = np.asarray([picks(layers[k]["attn"]["index"], xs[k], c_qs[k],
                                aux["picked"][k]) for k in range(len(layers))],
                         np.int64)
        del xs, c_qs
        kind.picked.append(float(np.min(got[:, 1] / np.maximum(got[:, 0], 1))))
        kind.outside += int(got[:, 2].sum())
        kind.count_gap = max(kind.count_gap,
                             int(np.max(np.abs(got[:, 3] - want_pairs))))
        sys_vg, sys_logits = _sys_fns(module)
        (l_sys, counts), g = sys_vg(p, base, seq)
        kind.grads = add(kind.grads, g)
        counts = np.asarray(counts, np.int64)
        kind.count_gap = max(kind.count_gap, abs(
            int(counts[:, -2].sum()) - len(layers) * want_pairs))
        s1, s2, (loads, sel) = sys_logits(p, base, seq)
        kind.dropped += int(np.sum(np.abs(
            np.asarray(held_pairs(sel)) - np.asarray(loads).sum(-1))))
        compare(kind, l_sys, s1, s2, sel, sel_router, seq, r1, r2, aux)
        del s1, s2, sel
        # ---- the float8 stand-in: the unit, and the first control
        for name in dict.fromkeys(("control_fp8", *variants)):
            loss, (s1, s2, v_aux) = call(name, FULL, p, base, seq)
            if name == "control_fp8":
                _, f_mask = agree(v_aux["experts"], aux["experts"])
                early, late = errs(s1, s2, r1, r2, f_mask)
                fp8_err["early"] = max(fp8_err["early"], float(early))
                fp8_err["late"] = max(fp8_err["late"], float(late))
            if name in kinds:   # a stand-in's gradient is not read
                kind = kinds[name]
                ref_late, both_late, pairs = (
                    np.asarray(a, np.int64) for a in picked_agree(
                        v_aux["picked"], aux["picked"]))
                kind.picked.append(
                    float(np.min(both_late / np.maximum(ref_late, 1))))
                kind.count_gap = max(kind.count_gap,
                                     int(np.max(np.abs(pairs - want_pairs))))
                compare(kind, loss, s1, s2, v_aux["experts"], v_aux["experts"],
                        seq, r1, r2, aux)
            del s1, s2, v_aux
        del r1, r2, aux
    g_ref = jax.tree_util.tree_map(lambda a: a / tokens.shape[0], g_ref)
    if kinds["sound"].grads is not None:
        kinds["sound"].grads = jax.tree_util.tree_map(
            lambda a: a / tokens.shape[0], kinds["sound"].grads)
    return {name: kind.numbers(shared, conf, fp8_err, g_ref)
            for name, kind in kinds.items()}


# --------------------------------------------------------------------------
# what the harness and controls.py call
# --------------------------------------------------------------------------


def _conf(cell) -> dict:
    """The configuration file's own keys, as the reference takes them: the
    published numbers at the top level and the groups `held` and
    `rope_scaling`."""
    return {k: v for k, v in cell["config"].items()
            if not isinstance(v, (dict, list, str))
            or k in ("held", "rope_scaling")}


def _parts(cell, cfg):
    from hefl_tpu.models import lm, set_frozen_base

    conf = _conf(cell)
    ref = cell["module"]("reference", cell["config"]["reference"])
    adam = cell["module"]("reference", "adam")
    module = lm.FrozenBaseLM(num_classes=cfg.train.num_classes,
                             arch=lm.PRESETS[cfg.model], seed=0)
    set_frozen_base(module, None)   # the run's base goes before the check's comes
    variables = ref.init(cfg.seed, conf)
    return module, ref, adam, conf, variables


def round_work(cell, cfg, data) -> dict:
    """`lm_subset.round_work` over this reference's `forward_flops` (the
    model's own count: attention over the selected pairs, the indexer over
    the causal ones)."""
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
