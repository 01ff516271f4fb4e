"""Plain reference of MiMo-V2-Flash (config.json of huggingface.co/XiaomiMiMo/
MiMo-V2-Flash, `model_type` `mimo_v2_flash`, 309B-A15B): `init`, `forward`,
`loss`, `forward_flops` in straightforward `jax.numpy`, float32, no kernel.
Imports nothing of the program. The caller sets
`jax.default_matmul_precision("highest")`.

`conf` is the configuration file's object: the published keys under their
published names (`hybrid_layer_pattern` and `moe_layer_freq` the published
lists, of which the first `num_hidden_layers` entries are the layers held),
with the three that are cut giving what is held here (`num_hidden_layers`,
`n_routed_experts`, `vocab_size`) and `held` giving the rest (`router_width`,
`first_expert`, `init_std`).

The layer (pre-norm, float32 residual stream; x = RMSNorm(h), eps
`layernorm_epsilon`):

- grouped-query attention of kind c = `hybrid_layer_pattern[layer]`, 0 global
  or 1 window; G = `num_key_value_heads` or `swa_num_key_value_heads` KV
  heads, theta = `rope_theta` or `swa_rope_theta`. q = x W_q [T, H, 192],
  k = x W_k [T, G, 192], v = `attention_value_scale` * x W_v [T, G, 128], no
  bias. The first int(`partial_rotary_factor` * 192) = 64 dims of every head
  of q and k are rotated, half-split pairs (i, i + 32), by t * theta^(-2i/64);
  the other 128 pass through. Query head j reads KV head j // (H / G).
  s[t, u] = q_t . k_u * 192^-1/2 over u <= t (global) or t - `sliding_window`
  < u <= t (window: 128 keys, the query's own among them). Global: p =
  softmax_u(s). Window, with the head's trained sink b_j: p[t, u] =
  exp(s[t, u]) / (exp(b_j) + sum_u' exp(s[t, u'])): the sink is one more
  column of the softmax, which is dropped (it takes mass and gives no
  value). o_t = sum_u p[t, u] v_u; the layer adds concat_j(o) W_o.
- layer 0 (`moe_layer_freq` 0): down(silu(gate x) * up x), `intermediate_size`
  wide. The others: s = sigmoid(W_r x), the `num_experts_per_tok` largest of
  s + b, weights s_e / sum of the selected s (`norm_topk_prob`;
  `routed_scaling_factor` null: none), y = sum over the held selected experts
  of w_e * down_e(silu(gate_e x) * up_e x). No shared expert.
- final RMSNorm, an untied head over the held slice of the vocabulary, loss =
  next-token cross-entropy alone.

Departures and assumptions, each also under `assumed` in the configuration's
file: (1) no prediction module: config.json has no key for one (the
catalog's `described_as`, a reader's note, speaks of three); (2) which dims
turn and in which pair layout: the first 64 of a head, half-split (the
family's `rotate_half` convention; config.json gives the factor only); (3)
the window counts the query's own key (128 keys in all); (4) the value scale
is applied to v (linear: the same number anywhere before W_o); (5)
`attention_chunk_size` 128 is a serving kernel's setting and changes no
equation; (6) weights normal(`init_std`) from the seed, sinks 0, the
router's `e_score_correction_bias` normal(`init_std`), frozen; (7) the
trained subset is every router matrix, every RMSNorm gain and every sink.

Parameters are two pytrees, `{"base": ..., "params": ...}`, as the other
token references': the base holds every matrix with bfloat16 *values*,
`params` the trained subset, float32. The expert layer is given the same
share as the system (`held`). For memory only: K and V are repeated to H
heads, attention runs a few heads and 512 queries at a time against a dense
[rows, S] mask built from the two inequalities, the dense MLP a quarter of
the tokens at a time, each made again for the gradient.

Departures for the check's controls only (all off by default): `quant`
(both operands of every base product through it: float8), `router_dtype`,
`drop_expert`; and of attention `sink=False` (the sink left out), `window`
(another width), `flip_kinds` (every layer masked as the other kind is:
global layers see a window, window layers every key), `swap_thetas` (the two
RoPE bases exchanged), `rotary_dims` (192: every dim turned), `value_scale`
(1.0: left out), `kv_map="mod"` (query head j reads KV head j mod G). `cap`
bounds the rows gathered for one expert. `keep_inputs=True` adds to `aux`
what each router and each attention layer saw, and what each attention
layer gave.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEADS_A_TIME = 8
GLOBAL, WINDOW = 0, 1


def _sizes(conf):
    held = conf["held"]
    n = conf["num_hidden_layers"]
    for key, same in (("swa_num_attention_heads", "num_attention_heads"),
                      ("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim")):
        if conf.get(key, conf[same]) != conf[same]:
            raise ValueError(f"{key} differs from {same}: two head sizes")
    return dict(
        d=conf["hidden_size"], h=conf["num_attention_heads"],
        dq=conf["head_dim"], dv=conf["v_head_dim"],
        kv=(conf["num_key_value_heads"], conf["swa_num_key_value_heads"]),
        theta=(float(conf["rope_theta"]), float(conf["swa_rope_theta"])),
        sink=(bool(conf["add_full_attention_sink_bias"]),
              bool(conf["add_swa_attention_sink_bias"])),
        window=conf["sliding_window"],
        rot=int(conf["partial_rotary_factor"] * conf["head_dim"]),
        vscale=conf["attention_value_scale"],
        pattern=tuple(conf["hybrid_layer_pattern"][:n]),
        moe=tuple(conf["moe_layer_freq"][:n]),
        ff=conf["intermediate_size"], fe=conf["moe_intermediate_size"],
        held=conf["n_routed_experts"], width=held["router_width"],
        first=held["first_expert"], k=conf["num_experts_per_tok"],
        layers=n, vocab=conf["vocab_size"], eps=conf["layernorm_epsilon"],
        scaling=conf.get("routed_scaling_factor") or 1.0, std=held["init_std"])


def init(seed: int, conf) -> dict:
    """Seeded weights: matrices normal(std) rounded to bfloat16, gains 1,
    sinks 0, the router's bias buffer normal(std) in float32."""
    z = _sizes(conf)
    key = jax.random.key(seed)
    count = [0]

    def normal(shape):
        count[0] += 1
        return z["std"] * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, F32)

    mat = lambda *shape: normal(shape).astype(jnp.bfloat16)  # noqa: E731
    d, h, dq, dv = z["d"], z["h"], z["dq"], z["dv"]
    base = {"embed": mat(z["vocab"], d), "head": mat(d, z["vocab"]),
            "blocks": []}
    params = {"blocks": [], "final_norm": jnp.ones(d, F32)}
    for kind, routed in zip(z["pattern"], z["moe"]):
        g = z["kv"][kind]
        w = {"attn": {"q": mat(d, h * dq), "k": mat(d, g * dq),
                      "v": mat(d, g * dv), "o": mat(h * dv, d)}}
        p = {"ln_attn": jnp.ones(d, F32), "ln_mlp": jnp.ones(d, F32)}
        if z["sink"][kind]:
            p["sink"] = jnp.zeros(h, F32)
        if routed:
            w["experts"] = {"gate_up": mat(z["held"], d, 2 * z["fe"]),
                            "down": mat(z["held"], z["fe"], d)}
            w["bias"] = normal((z["width"],))
            p["router"] = normal((z["width"], d))
        else:
            w["mlp"] = {"gate_up": mat(d, 2 * z["ff"]),
                        "down": mat(z["ff"], d)}
        base["blocks"].append(w)
        params["blocks"].append(p)
    return {"base": base, "params": params}


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope_first(x, rot: int, theta: float):
    """x [B, S, H, d]: the first `rot` dims of every head as `rot / 2`
    complex numbers (x_i, x_i+rot/2), turned by pos * theta^(-2i/rot); the
    other dims pass through."""
    freq = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.asarray(np.arange(x.shape[1])[:, None] * freq[None, :], F32)
    turn = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))[None, :, None, :]
    out = jax.lax.complex(x[..., :rot // 2], x[..., rot // 2:rot]) * turn
    return jnp.concatenate([jnp.real(out), jnp.imag(out), x[..., rot:]], -1)


def allowed(kind: int, window: int, rows, s: int):
    """bool[len(rows), s]: the keys u query t = rows[i] attends, from the two
    inequalities: u <= t, and in a window layer t - window < u."""
    t, u = rows[:, None], jnp.arange(s)[None, :]
    ok = u <= t
    return ok & (u > t - window) if kind == WINDOW else ok


def attention(z, kind: int, w, g, x, mm, sink=True, window=None,
              flip_kinds=False, swap_thetas=False, rotary_dims=None,
              value_scale=None, kv_map="block"):
    """One attention layer of kind `kind` on its normed input x [B, S, D]
    -> [B, S, D]. The keywords are the controls' departures."""
    b, s, _ = x.shape
    h, dq, dv, n_kv = z["h"], z["dq"], z["dv"], z["kv"][kind]
    theta = z["theta"][1 - kind if swap_thetas else kind]
    rot = z["rot"] if rotary_dims is None else rotary_dims
    masked_as = 1 - kind if flip_kinds else kind
    width = z["window"] if window is None else window
    q = _rope_first(mm(x, w["q"]).reshape(b, s, h, dq), rot, theta)
    k = _rope_first(mm(x, w["k"]).reshape(b, s, n_kv, dq), rot, theta)
    v = (z["vscale"] if value_scale is None else value_scale) * mm(
        x, w["v"]).reshape(b, s, n_kv, dv)
    reads = (np.arange(h) // (h // n_kv) if kv_map == "block"
             else np.arange(h) % n_kv)
    k, v = k[:, :, reads], v[:, :, reads]          # repeated to H heads
    bias = g["sink"] if sink and "sink" in g else None
    grp = math.gcd(h, HEADS_A_TIME)
    rows = 512 if s % 512 == 0 else s

    @jax.checkpoint
    def heads(part):                  # `grp` heads
        qs, ks, vs, bs = part         # [B, S, grp, .] x3, [grp]

        @jax.checkpoint
        def attend(lo):
            at = jax.lax.dynamic_slice_in_dim(qs, lo, rows, 1)
            sc = jnp.einsum("bqhd,bkhd->bhqk", at, ks) * dq ** -0.5
            ok = allowed(masked_as, width, lo + jnp.arange(rows), s)
            sc = jnp.where(ok[None, None], sc, -jnp.inf)
            if bias is not None:      # the sink: one more column, dropped
                col = jnp.broadcast_to(bs[None, :, None, None],
                                       (b, grp, rows, 1))
                sc = jnp.concatenate([sc, col], -1)
            p = jax.nn.softmax(sc, axis=-1)[..., :s]
            return jnp.einsum("bhqk,bkhd->bqhd", p, vs)

        outs = jax.lax.map(attend, jnp.arange(0, s, rows))
        return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, grp * dv)

    by_group = lambda t: t.reshape(  # noqa: E731
        b, s, h // grp, grp, t.shape[-1]).transpose(2, 0, 1, 3, 4)
    sinks = (jnp.zeros(h, F32) if bias is None else bias).reshape(-1, grp)
    o = jax.lax.map(heads, (by_group(q), by_group(k), by_group(v), sinks))
    return mm(o.transpose(1, 2, 0, 3).reshape(b, s, h * dv), w["o"])


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


def _route(z, router, bias, x, router_dtype=None):
    if router_dtype is not None:
        logits = jnp.dot(x.astype(router_dtype), router.T.astype(router_dtype),
                         preferred_element_type=F32)
    else:
        logits = x @ router.T
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, z["k"])
    w = jnp.take_along_axis(s, idx, -1)
    return idx, z["scaling"] * w / jnp.sum(w, -1, keepdims=True)


def _experts(z, w, x, idx, weights, mm, drop_expert=None, cap=None):
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


def _block(z, kind, w, g, h, mm, router_dtype, drop_expert, cap, attn_kw):
    x = _norm(h, g["ln_attn"], z["eps"])
    a = attention(z, kind, w["attn"], g, x, mm, **attn_kw)
    h = h + a
    seen = {"attn_in": x, "attn_out": a}
    x = _norm(h, g["ln_mlp"], z["eps"])
    if "experts" not in w:
        return h + _glu_by_parts(w["mlp"], x, mm), seen
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = _route(z, g["router"], w["bias"], flat, router_dtype)
    y, loads = _experts(z, w["experts"], flat, idx, weights, mm, drop_expert,
                        cap)
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
            drop_expert=None, cap=None, keep_inputs=False, **attn_kw):
    """tokens int[B, S + 2] (the program's rows; the last id labels nothing
    here) -> (logits f32[B, S, vocab]: position i predicts token i + 1, aux).
    aux: `experts` int[expert layers, T, k], `loads`, `max_load` (the most
    rows one held expert was given) and, with `keep_inputs`, `router_in`
    [expert layers, T, D], `attn_in` and `attn_out` [layers, B, S, D]: what
    each router and each attention layer saw, and what each attention layer
    added to the stream."""
    z = _sizes(conf)
    base, p = variables["base"], variables["params"]
    mm = _Products(quant)
    s = tokens.shape[1] - 2
    h, seen = base["embed"][tokens[:, :s]].astype(F32), []
    for kind, w, g in zip(z["pattern"], base["blocks"], p["blocks"]):
        blk = jax.checkpoint(
            lambda w, g, h, kind=kind: _block(
                z, kind, w, g, h, mm, router_dtype, drop_expert, cap, attn_kw))
        h, aux = blk(w, g, h)
        seen.append(aux)
    logits = mm(_norm(h, p["final_norm"], z["eps"]), base["head"])
    routed = [a for a in seen if "experts" in a]
    out = {"experts": jnp.stack([a["experts"] for a in routed]),
           "loads": jnp.stack([a["loads"] for a in routed]),
           "max_load": jnp.max(jnp.stack([a["loads"] for a in routed]))}
    if keep_inputs:
        out["router_in"] = jnp.stack([a["router_in"] for a in routed])
        out["attn_in"] = jnp.stack([a["attn_in"] for a in seen])
        out["attn_out"] = jnp.stack([a["attn_out"] for a in seen])
    return logits, out


def _ce(logits, targets):
    logits = logits.reshape(-1, logits.shape[-1])
    lse = jax.nn.logsumexp(logits, -1)
    hit = jnp.take_along_axis(logits, targets.reshape(-1, 1), -1)[:, 0]
    return jnp.mean(lse - hit)


def loss(variables, tokens, conf, **kw):
    """-> (next-token cross-entropy, (logits, None, aux)): the mean over
    every position of every sequence; the second place is the prediction
    module's logits in the other token references, and this model has none.
    `kw`: `forward`'s."""
    logits, aux = forward(variables, tokens, conf, **kw)
    s = tokens.shape[1] - 2
    return _ce(logits, tokens[:, 1:s + 1]), (logits, None, aux)


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a window layer allows: min(t + 1, window) a query."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def forward_flops(conf, seq: int) -> dict:
    """Forward FLOPs of one token at sequence length `seq`, by the model's
    own count, whatever form computes it: a global layer's scores and values
    over the causal pairs ((seq + 1) / 2 keys a query), a window layer's over
    the pairs its window allows (`window_pairs / seq` keys a query), the held
    experts by their expected share of the selections. -> by part, and
    `total`."""
    z = _sizes(conf)
    d, h, dq, dv = z["d"], z["h"], z["dq"], z["dv"]
    proj = [2 * (d * (h * dq + g * (dq + dv)) + h * dv * d) for g in z["kv"]]
    keys = [(seq + 1) / 2, window_pairs(seq, z["window"]) / seq]
    attend = [2 * h * (dq + dv) * n for n in keys]
    held = z["k"] * z["held"] / z["width"] * 2 * 3 * d * z["fe"]
    router = 2 * z["width"] * d
    parts = {
        "global_projections": proj[GLOBAL], "window_projections": proj[WINDOW],
        "global_attend": attend[GLOBAL], "window_attend": attend[WINDOW],
        "dense_mlp": 2 * 3 * d * z["ff"], "held_experts": held,
        "router": router, "head": 2 * d * z["vocab"]}
    total = parts["head"]
    for kind, routed in zip(z["pattern"], z["moe"]):
        total += proj[kind] + attend[kind] + (
            held + router if routed else parts["dense_mlp"])
    parts["total"] = total
    return parts
