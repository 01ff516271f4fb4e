"""Plain reference of DeepSeek-V3.2-Exp (config.json of huggingface.co/
deepseek-ai/DeepSeek-V3.2-Exp, `model_type` `deepseek_v32`; the equations of
arXiv:2412.19437 section 2 with the sparse attention of the V3.2-Exp report):
`init`, `forward`, `loss`, `forward_flops` in straightforward `jax.numpy`,
float32, no kernel. Imports nothing of the program. The caller sets
`jax.default_matmul_precision("highest")`.

`conf` is the configuration file's object: the published keys under their
published names (`rope_scaling` the published group), with the three that are
cut giving what is held here (`num_hidden_layers`, `n_routed_experts`,
`vocab_size`) and `held` giving the rest (`router_width`, `first_expert`,
`mtp_loss_weight`, `init_std`, and `dense_layers`: of the published
`first_k_dense_replace` leading dense layers, those among the layers held).

The layer (pre-norm; x = RMSNorm(h)):

- latent attention as DeepSeek-V3's, RoPE on interleaved pairs at YaRN's
  frequencies (`_yarn`), scores scaled by (d_qk)^-1/2 * m^2, m = 0.1 *
  mscale_all_dim * ln(factor) + 1 (cos and sin unscaled);
- the lightning indexer, one a layer: qI[t, j] = (c_q[t] W_iq)_j from the
  main queries' normed latent, kI[s] = LayerNorm(x[s] W_ik), the first
  `qk_rope_head_dim` dims of both turned half-split (pair i is (x_i,
  x_i+d/2)) at the same frequencies, w[t, j] = (x[t] W_iw)_j * Hi^-1/2 *
  di^-1/2, I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <= t; query t
  attends S_t, its min(t + 1, index_topk) keys of largest I (`jax.lax.top_k`:
  the lower index first among equals), every head the same; the indexer's
  inputs are detached;
- the router: s = sigmoid(W_r x), s' = s + b, `n_group` groups, a group's
  score the sum of its two largest s', the `topk_group` best groups stay, the
  `num_experts_per_tok` largest s' among their experts, weights
  routed_scaling_factor * s_e / sum of the selected s;
- dense layer, shared expert, held experts, the prediction module (its block
  has an indexer of its own), heads and CE + w CE_mtp as
  `reference/joyai_llm_flash.py`, at this model's widths.

Departures from the published model, each also under `assumed` in the
configuration's file: (1) the published indexer turns qI and kI by a
Hadamard matrix and quantises them to FP8; the turn is orthogonal and leaves
qI . kI as it is, and the products are float32 here (bfloat16 with float32
accumulation in the program: the v5e has no FP8 matrix unit); (2) the
indexer is not trained (the published alignment term, a KL towards the main
attention's distribution, is not in config.json): it is part of the frozen
base; (3) LayerNorm's epsilon is `rms_norm_eps` (config.json gives none).

Parameters are two pytrees, `{"base": ..., "params": ...}`, as the joyai
reference's: the base holds every matrix with bfloat16 *values*, `params`
the trained subset (routers, RMSNorm gains), float32. The expert layer is
given the same share as the system (`held`). For memory only: attention a
few heads and 512 queries at a time, the indexer 128 queries at a time, the
dense MLP a quarter of the tokens at a time, each made again for the
gradient.

Departures for the check's controls only (all off by default): `quant`,
`router_dtype`, `mtp=False`, `drop_expert` as the joyai reference's;
`index_quant` (the indexer's q and k through it: float8), `index_topk` (a
smaller selection), `dense=True` (the selection ignored), `index_relu=False`,
`groups=False` (plain top-k routing), `yarn=False` (unscaled RoPE and
softmax scale). `cap` bounds the rows gathered for one expert, as there.
`keep_inputs=True` adds to `aux` what each router and each indexer saw.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEADS_A_TIME = 8


def _sizes(conf):
    held = conf["held"]
    return dict(
        d=conf["hidden_size"], h=conf["num_attention_heads"],
        rq=conf["q_lora_rank"], rkv=conf["kv_lora_rank"],
        dn=conf["qk_nope_head_dim"], dr=conf["qk_rope_head_dim"],
        dv=conf["v_head_dim"], ff=conf["intermediate_size"],
        fe=conf["moe_intermediate_size"], held=conf["n_routed_experts"],
        width=held["router_width"], first=held["first_expert"],
        k=conf["num_experts_per_tok"],
        dense=held.get("dense_layers", conf["first_k_dense_replace"]),
        layers=conf["num_hidden_layers"], vocab=conf["vocab_size"],
        eps=conf["rms_norm_eps"], theta=float(conf["rope_theta"]),
        scaling=conf["routed_scaling_factor"],
        n_group=conf["n_group"], topk_group=conf["topk_group"],
        hi=conf["index_n_heads"], di=conf["index_head_dim"],
        topk=conf["index_topk"], yarn=conf["rope_scaling"],
        mtp_weight=held["mtp_loss_weight"], std=held["init_std"])


def init(seed: int, conf) -> dict:
    """Seeded weights: matrices normal(std) rounded to bfloat16, gains 1,
    the router's bias buffer and the indexer's LayerNorm bias normal(std)
    in float32."""
    z = _sizes(conf)
    key = jax.random.key(seed)
    count = [0]

    def mat(*shape):
        count[0] += 1
        k = jax.random.fold_in(key, count[0])
        return (z["std"] * jax.random.normal(k, shape, F32)).astype(jnp.bfloat16)

    def vec(n):
        count[0] += 1
        return z["std"] * jax.random.normal(jax.random.fold_in(key, count[0]),
                                            (n,), F32)

    d, h = z["d"], z["h"]
    attn = lambda: {  # noqa: E731
        "q_a": mat(d, z["rq"]), "q_b": mat(z["rq"], h * (z["dn"] + z["dr"])),
        "kv_a": mat(d, z["rkv"] + z["dr"]),
        "kv_b": mat(z["rkv"], h * (z["dn"] + z["dv"])), "o": mat(h * z["dv"], d),
        "index": {"q": mat(z["rq"], z["hi"] * z["di"]), "k": mat(d, z["di"]),
                  "k_gain": jnp.ones(z["di"], F32), "k_bias": vec(z["di"]),
                  "w": mat(d, z["hi"])}}
    gains = lambda: {  # noqa: E731
        "ln_attn": jnp.ones(d, F32), "ln_mlp": jnp.ones(d, F32),
        "q_norm": jnp.ones(z["rq"], F32), "kv_norm": jnp.ones(z["rkv"], F32)}
    moe = lambda: {  # noqa: E731
        "attn": attn(),
        "experts": {"gate_up": mat(z["held"], d, 2 * z["fe"]),
                    "down": mat(z["held"], z["fe"], d)},
        "shared": {"gate_up": mat(d, 2 * z["fe"]), "down": mat(z["fe"], d)},
        "bias": vec(z["width"])}
    moe_g = lambda: dict(gains(), router=z["std"] * jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, 10_000 + count[0]), (z["width"], d), F32))
    dense = lambda: {"attn": attn(), "mlp": {  # noqa: E731
        "gate_up": mat(d, 2 * z["ff"]), "down": mat(z["ff"], d)}}
    n_moe = z["layers"] - z["dense"]
    base = {"embed": mat(z["vocab"], d), "head": mat(d, z["vocab"]),
            "blocks": [dense() for _ in range(z["dense"])]
            + [moe() for _ in range(n_moe)],
            "mtp": {"eh": mat(2 * d, d), "block": moe()}}
    params = {"blocks": [gains() for _ in range(z["dense"])]
              + [moe_g() for _ in range(n_moe)],
              "final_norm": jnp.ones(d, F32),
              "mtp": {"hnorm": jnp.ones(d, F32), "enorm": jnp.ones(d, F32),
                      "norm": jnp.ones(d, F32), "block": moe_g()}}
    return {"base": base, "params": params}


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def _yarn(d: int, theta: float, yarn) -> np.ndarray:
    """The d / 2 frequencies theta^(-2i/d), float64; with `yarn` (the
    published `rope_scaling` group) pair i is slowed by `factor` where it
    turns fewer than `beta_slow` times over the original positions, kept
    where more than `beta_fast` times, and mixed linearly in i between."""
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if yarn is None:
        return freq
    cd = lambda r: d * math.log(  # noqa: E731
        yarn["original_max_position_embeddings"] / (2 * math.pi * r)) / (
            2 * math.log(theta))
    low = max(math.floor(cd(yarn["beta_fast"])), 0)
    high = min(math.ceil(cd(yarn["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq / yarn["factor"] * ramp + freq * (1 - ramp)


def _rope(x, theta, yarn=None, interleaved=True):
    """x [B, S, H, d]: pairs as complex numbers, turned by pos * frequency.
    Pair i is (x_2i, x_2i+1), or half-split (x_i, x_i+d/2)."""
    d = x.shape[-1]
    ang = jnp.asarray(
        np.arange(x.shape[1])[:, None] * _yarn(d, theta, yarn)[None, :], F32)
    turn = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))[None, :, None, :]
    if interleaved:
        pairs = x.reshape(*x.shape[:-1], d // 2, 2)
        out = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * turn
        return jnp.stack([jnp.real(out), jnp.imag(out)], -1).reshape(x.shape)
    out = jax.lax.complex(x[..., :d // 2], x[..., d // 2:]) * turn
    return jnp.concatenate([jnp.real(out), jnp.imag(out)], -1)


def _softmax_scale(z, yarn):
    scale = 1.0 / math.sqrt(z["dn"] + z["dr"])
    if yarn is not None:
        scale *= (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"]) + 1) ** 2
    return scale


def _select(z, w, x, c_q, mm, yarn, index_quant, index_topk, index_relu):
    """The indexer: bool[B, S, S], true where query t attends key s."""
    b, s, _ = x.shape
    hi, di, dr = z["hi"], z["di"], z["dr"]
    turn = lambda t: jnp.concatenate(  # noqa: E731
        [_rope(t[..., :dr], z["theta"], yarn, False), t[..., dr:]], -1)
    q = turn(mm(c_q, w["q"]).reshape(b, s, hi, di))
    k = turn(_layer_norm(mm(x, w["k"]), w["k_gain"], w["k_bias"],
                         z["eps"])[:, :, None, :])[:, :, 0]
    weights = mm(x, w["w"]) * (hi ** -0.5 * di ** -0.5)
    if index_quant is not None:
        q, k = index_quant(q), index_quant(k)
    keep = min(z["topk"] if index_topk is None else index_topk, s)
    rows = 128 if s % 128 == 0 else s

    def block(lo):                                   # `rows` queries
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, rows, 1)  # noqa: E731
        sc = jnp.einsum("bqjd,bsd->bqjs", at(q), k)
        if index_relu:
            sc = jax.nn.relu(sc)
        score = jnp.sum(sc * at(weights)[..., None], axis=2)     # [B, rows, S]
        causal = (lo + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        _, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), keep)
        picked = jnp.zeros((b, rows, s), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(rows)[None, :, None],
            idx].set(True)
        return picked & causal

    blocks = jax.lax.map(block, jnp.arange(0, s, rows))      # [n, B, rows, S]
    return blocks.transpose(1, 0, 2, 3).reshape(b, s, s)


def _attention(z, w, g, x, mm, yarn=None, dense=False, index_quant=None,
               index_topk=None, index_relu=True):
    """-> (the attention layer's output, picked bool[B, S, S], (x, c_q):
    what the indexer saw)."""
    b, s, _ = x.shape
    h, dn, dr, dv = z["h"], z["dn"], z["dr"], z["dv"]
    c_q = _norm(mm(x, w["q_a"]), g["q_norm"], z["eps"])
    kv_a = mm(x, w["kv_a"])
    c_kv = _norm(kv_a[..., :z["rkv"]], g["kv_norm"], z["eps"])
    k_r = _rope(kv_a[..., z["rkv"]:][:, :, None, :], z["theta"], yarn)[:, :, 0]
    detach = jax.lax.stop_gradient
    picked = _select(z, w["index"], detach(x), detach(c_q), mm, yarn,
                     index_quant, index_topk, index_relu)
    if dense:   # the control: every causal key, whatever the indexer says
        picked = jnp.broadcast_to(np.tril(np.ones((s, s), bool)), (b, s, s))
    scale = _softmax_scale(z, yarn)
    grp = math.gcd(h, HEADS_A_TIME)
    rows = 512 if s % 512 == 0 else s

    @jax.checkpoint
    def heads(ws):              # `grp` heads, from the latents
        q_b, kv_b = ws          # [rq, grp * (dn + dr)], [rkv, grp * (dn + dv)]
        q = mm(c_q, q_b).reshape(b, s, grp, dn + dr)
        kv = mm(c_kv, kv_b).reshape(b, s, grp, dn + dv)
        q_n, q_r = q[..., :dn], _rope(q[..., dn:], z["theta"], yarn)
        k_n, v = kv[..., :dn], kv[..., dn:]

        @jax.checkpoint
        def attend(lo):
            at = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, rows, 1)  # noqa: E731
            sc = (jnp.einsum("bqhd,bkhd->bhqk", at(q_n), k_n)
                  + jnp.einsum("bqhd,bkd->bhqk", at(q_r), k_r)) * scale
            p = jax.nn.softmax(
                jnp.where(at(picked)[:, None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        outs = jax.lax.map(attend, jnp.arange(0, s, rows))   # [n, B, rows, grp, dv]
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, grp * dv)

    by_group = lambda m, per: m.reshape(  # noqa: E731
        m.shape[0], h // grp, grp * per).transpose(1, 0, 2)
    o = jax.lax.map(heads, (by_group(w["q_b"], dn + dr),
                            by_group(w["kv_b"], dn + dv)))   # [groups, B, S, grp*dv]
    o = o.transpose(1, 2, 0, 3).reshape(b, s, h * dv)
    return mm(o, w["o"]), picked, (x, c_q)


def _glu(w, x, mm):
    gu = mm(x, w["gate_up"])
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w["down"])


def _glu_by_parts(w, x, mm, parts: int = 4):
    """`_glu` over [B, S, D], the tokens a part at a time (memory only)."""
    b, s, d = x.shape
    if (b * s) % parts:
        return _glu(w, x, mm)
    one = jax.checkpoint(lambda xs: _glu(w, xs, mm))
    return jax.lax.map(one, x.reshape(parts, -1, d)).reshape(b, s, d)


def _route(z, router, bias, x, router_dtype, groups=True):
    if router_dtype is not None:
        logits = jnp.dot(x.astype(router_dtype), router.T.astype(router_dtype),
                         preferred_element_type=F32)
    else:
        logits = x @ router.T
    s = jax.nn.sigmoid(logits)
    choice = s + bias
    if groups and z["n_group"] > 1:
        t, n = choice.shape
        by_group = choice.reshape(t, z["n_group"], n // z["n_group"])
        score = jnp.sum(jax.lax.top_k(by_group, 2)[0], -1)        # [T, groups]
        _, best = jax.lax.top_k(score, z["topk_group"])
        stays = jnp.any(jnp.arange(z["n_group"])[None, :, None]
                        == best[:, None, :], -1)                   # [T, groups]
        choice = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, n)
    _, idx = jax.lax.top_k(choice, z["k"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, z["scaling"] * w / jnp.sum(w, -1, keepdims=True)


def _experts(z, w, x, idx, weights, mm, drop_expert, cap):
    """Sum over the held experts e of weight[t, e] * E_e(x[t]), an expert at
    a time over the rows routed to it."""
    t = x.shape[0]
    dense_w = jnp.zeros((t, z["width"]), F32).at[
        jnp.arange(t)[:, None], idx].add(weights)
    dense_w = dense_w[:, z["first"]:z["first"] + z["held"]]
    if drop_expert is not None:
        dense_w = dense_w.at[:, drop_expert].set(0.0)
    cap = t if cap is None else min(cap, t)
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), F32)])

    @jax.checkpoint   # an expert's rows are made again for the gradient
    def one(e):
        col = dense_w[:, e]
        rows = jnp.nonzero(col > 0, size=cap, fill_value=t)[0]
        we = {"gate_up": w["gate_up"][e], "down": w["down"][e]}
        ye = _glu(we, x_pad[rows], mm) * jnp.concatenate(
            [col, jnp.zeros((1,), F32)])[rows][:, None]
        return rows, ye, jnp.sum(col > 0)

    rows, ys, loads = jax.lax.map(one, jnp.arange(z["held"]))
    y = jnp.zeros_like(x).at[rows.reshape(-1)].add(
        ys.reshape(-1, x.shape[1]), mode="drop")
    return y, loads


def _block(z, w, g, h, mm, router_dtype, drop_expert, cap, attn_kw, groups):
    a, picked, index_in = _attention(
        z, w["attn"], g, _norm(h, g["ln_attn"], z["eps"]), mm, **attn_kw)
    h = h + a
    x = _norm(h, g["ln_mlp"], z["eps"])
    seen = {"picked": picked, "index_in": index_in}
    if "experts" not in w:
        return h + _glu_by_parts(w["mlp"], x, mm), seen
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = _route(z, g["router"], w["bias"], flat, router_dtype, groups)
    y, loads = _experts(z, w["experts"], flat, idx, weights, mm, drop_expert, cap)
    y = y + _glu(w["shared"], flat, mm)
    return h + y.reshape(b, s, d), dict(seen, experts=idx, router_in=flat,
                                        loads=loads)


class _Products:
    """x @ w over a base matrix (bfloat16 values, widened where it is used);
    with `quant` both operands go through it (the float8 control)."""

    def __init__(self, quant):
        self.quant = quant

    def __call__(self, x, w):
        w = w.astype(F32)
        return x @ w if self.quant is None else self.quant(x) @ self.quant(w)


def forward(variables, tokens, conf, quant=None, router_dtype=None,
            drop_expert=None, cap=None, keep_inputs=False, yarn=True,
            groups=True, **attn_kw):
    """tokens int[B, S + 2] -> (logits of the main head, of the prediction
    module, aux): position i predicts token i + 1 and token i + 2. aux:
    `experts` int[layers, T, k] (the selections of every expert layer, the
    prediction module's last), `picked` bool[attention layers, B, S, S] (each
    indexer's selection, the prediction module's last), `loads`, `max_load`
    (the most rows one held expert was given) and, with `keep_inputs`,
    `router_in` [layers, T, D] and `index_in` (x [layers, B, S, D], c_q
    [layers, B, S, q_lora_rank]): what each router and each indexer saw."""
    z = _sizes(conf)
    base, p = variables["base"], variables["params"]
    mm = _Products(quant)
    attn_kw = dict(attn_kw, yarn=z["yarn"] if yarn else None)
    s = tokens.shape[1] - 2
    emb = lambda t: base["embed"][t].astype(F32)  # noqa: E731
    blk = jax.checkpoint(
        lambda w, g, h: _block(z, w, g, h, mm, router_dtype, drop_expert, cap,
                               attn_kw, groups))
    h, seen = emb(tokens[:, :s]), []
    for w, g in zip(base["blocks"], p["blocks"]):
        h, aux = blk(w, g, h)
        seen.append(aux)
    z_main = mm(_norm(h, p["final_norm"], z["eps"]), base["head"])
    m, mb = p["mtp"], base["mtp"]
    both = jnp.concatenate(
        [_norm(h, m["hnorm"], z["eps"]),
         _norm(emb(tokens[:, 1:s + 1]), m["enorm"], z["eps"])], -1)
    h2, aux = blk(mb["block"], m["block"], mm(both, mb["eh"]))
    seen.append(aux)
    z_mtp = mm(_norm(h2, m["norm"], z["eps"]), base["head"])
    routed = [a for a in seen if "experts" in a]
    out = {"experts": jnp.stack([a["experts"] for a in routed]),
           "picked": jnp.stack([a["picked"] for a in seen]),
           "max_load": jnp.max(jnp.stack([a["loads"] for a in routed])),
           "loads": jnp.stack([a["loads"] for a in routed])}
    if keep_inputs:
        out["router_in"] = jnp.stack([a["router_in"] for a in routed])
        out["index_in"] = (jnp.stack([a["index_in"][0] for a in seen]),
                           jnp.stack([a["index_in"][1] for a in seen]))
    return z_main, z_mtp, out


def _ce(logits, targets):
    logits = logits.reshape(-1, logits.shape[-1])
    lse = jax.nn.logsumexp(logits, -1)
    hit = jnp.take_along_axis(logits, targets.reshape(-1, 1), -1)[:, 0]
    return jnp.mean(lse - hit)


def loss(variables, tokens, conf, mtp=True, **kw):
    """-> (CE_main + mtp_loss_weight * CE_mtp, (logits_main, logits_mtp,
    aux)), means over every position of every sequence. `kw`: `forward`'s."""
    z_main, z_mtp, aux = forward(variables, tokens, conf, **kw)
    s = tokens.shape[1] - 2
    total = _ce(z_main, tokens[:, 1:s + 1])
    if mtp:
        total = total + _sizes(conf)["mtp_weight"] * _ce(
            z_mtp, tokens[:, 2:s + 2])
    return total, (z_main, z_mtp, aux)


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's selections hold: min(t + 1, topk) a
    query."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def forward_flops(conf, seq: int) -> dict:
    """Forward FLOPs of one token at sequence length `seq`, by the model's own
    count, whatever form computes it: attention over the selected pairs at
    the published head sizes (a query attends `selected_pairs / seq` keys on
    average), the indexer's scores over the causal pairs ((seq + 1) / 2 keys
    a query, `index_n_heads` x `index_head_dim` each) with its projections,
    the held experts by their expected share of the selections. -> by part,
    and `total`."""
    z = _sizes(conf)
    d, h = z["d"], z["h"]
    proj = (d * z["rq"] + z["rq"] * h * (z["dn"] + z["dr"])
            + d * (z["rkv"] + z["dr"]) + z["rkv"] * h * (z["dn"] + z["dv"])
            + h * z["dv"] * d)
    keys = selected_pairs(seq, z["topk"]) / seq
    attend = 2 * h * (z["dn"] + z["dr"] + z["dv"]) * keys
    index_proj = z["rq"] * z["hi"] * z["di"] + d * z["di"] + d * z["hi"]
    indexer = 2 * index_proj + 2 * z["hi"] * z["di"] * (seq + 1) / 2
    attn = 2 * proj + attend + indexer
    expert = 2 * 3 * d * z["fe"]
    moe = (attn + z["k"] * z["held"] / z["width"] * expert + expert
           + 2 * z["width"] * d)
    dense = attn + 2 * 3 * d * z["ff"]
    parts = {
        "dense_layer": dense, "expert_layer": moe, "attention": attn,
        "attend_selected": attend, "indexer": indexer,
        "held_experts": z["k"] * z["held"] / z["width"] * expert,
        "shared_expert": expert, "router": 2 * z["width"] * d,
        "mtp_module": moe + 2 * 2 * d * d, "heads": 2 * 2 * d * z["vocab"]}
    parts["total"] = (z["dense"] * dense + (z["layers"] - z["dense"]) * moe
                      + parts["mtp_module"] + parts["heads"])
    return parts
