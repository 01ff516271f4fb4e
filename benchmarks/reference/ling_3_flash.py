"""Plain reference of Ling-3.0-flash (config.json of huggingface.co/inclusionAI/
Ling-3.0-flash, `model_type` `bailing_hybrid`; the linear layer is Kimi Delta
Attention, arXiv:2510.26692): `init`, `forward`, `loss`, `forward_flops` and
the two counts of the recurrence's roofline in straightforward `jax.numpy`,
float32, no kernel, no chunks. Imports nothing of the program. The caller
sets `jax.default_matmul_precision("highest")`.

`conf` is the configuration file's object: the published keys under their
published names, with the four that are cut giving what is held here
(`num_hidden_layers`, `num_experts`, `vocab_size`,
`num_nextn_predict_layers`) and `held` giving the rest (`router_width`,
`first_expert`, `first_layer`: the published index of the first layer held,
`init_std`). Published layer i (from 0) is a latent layer where (i + 1) %
`layer_group_size` == 0 and a linear layer elsewhere, and has a dense MLP
where i < `first_k_dense_replace`.

The layer (pre-norm, float32 residual stream; x = RMSNorm(h), eps
`rms_norm_eps`):

- **linear layer** (H = `num_attention_heads` heads, keys and values
  `head_dim` wide; its five wide projections are kept side by side in one
  matrix `in`, as the program keeps them): z in (x W_q, x W_k, x W_v) goes through a depthwise
  causal convolution over the `short_conv_kernel_size` last positions,
  y[t] = sum_j c[:, j] z[t - 3 + j] (four shifted adds), then SiLU; q and k
  are L2-normalised a head (x / sqrt(sum x^2 + eps)), q scaled by
  head_dim^-1/2. The decay a channel g_t = `kda_lower_bound` *
  sigmoid(exp(A_log_j) * (x_t W_f + dt_bias)) in (-5, 0), beta_t =
  sigmoid(x_t W_beta) a head. **Position by position** (a `lax.scan` over t),
  from S = 0 at position 0, a head's state S in R^{128 x 128}:
      S' = Diag(exp g_t) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t
  y = (RMSNorm_head(o_t) * sigmoid(x_t W_g)) W_o, the norm over one head's
  values with a gain [head_dim] (`group_norm_size` 1).
- **latent layer**: DeepSeek-V3's without a query low-rank: q = x W_q to H x
  (128 + 64), [c_kv, k_r] = x W_kva, [k_nope, v] = RMSNorm(c_kv) W_kvb,
  interleaved RoPE (pairs (2i, 2i + 1)) on q's last 64 dims and on the one
  shared k_r at theta `rope_theta`, softmax(q k^T * 192^-1/2) causal, then a
  head's output times sigmoid(x W_gate)_j, one scalar a head
  (`gated_attention_proj_granularity_type` `head_wise`), then W_o.
- the leading dense layer: down(silu(gate x) * up x). The others: s =
  sigmoid(W_r x), s' = s + b, `n_group` groups, a group's score the sum of
  its two largest s', the `topk_group` best groups stay, the
  `num_experts_per_tok` largest s' among their experts, weights
  `routed_scaling_factor` * s_e / sum of the selected s; y = sum over the
  held selected experts of w_e * E_e(x), plus one shared expert.
- final RMSNorm, an untied head over the held slice of the vocabulary, loss =
  next-token cross-entropy alone (`mtp_loss_scaling_factor` 0: no prediction
  module is built).

What is assumed is under `assumed` in the configuration's file. Parameters
are two pytrees, `{"base": ..., "params": ...}`, as the other token
references': the base holds every matrix with bfloat16 *values*, stacked by
kind of layer as the program keeps them (`layer_weights` gives a layer its
own; 8.6 GB are not laid out twice), `params` the trained subset (routers,
RMSNorm gains, `A_log`, `dt_bias`), float32, a list a layer. The
expert layer is given the same share as the system (`held`). For memory
only: the scan over positions is cut into segments of 64 whose inside is made
again for the gradient (the recurrence itself is stepped a position at a
time, whatever the segment), attention runs 512 queries at a time, the
dense MLP a quarter of the tokens at a time, a held expert's rows one expert
at a time; a base matrix is widened where it is used, a leaf (an expert) at
a time.

Departures for the check's controls only (all off by default): `quant`
(both operands of every base product through it: float8), `router_dtype`,
`drop_expert`; of the linear layer `state_bf16` (the carried state rounded
to bfloat16 after every position), `decay_bf16` (g rounded), `drop_state`
(n: the state set to 0 before every n-th position, what a chunked form
computes that loses it at a chunk's edge), `gate_form="softplus"` (g =
-exp(A_log) * softplus(x W_f + dt_bias): the gate without its bound),
`beta=False` (beta = 1), `conv_taps` (3: the oldest tap left out); of the
latent layer `gate` ("channel": the H gates laid over the H x 128 output
channels in turn, None: left out); `exchange` (the latent layer runs before
the linear layer in front of it: the pattern read one layer early). `cap`
bounds the rows gathered for one expert. `keep_inputs=True` adds to `aux`
what each router and each attention layer saw, and what each attention layer
gave.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LATENT, LINEAR = 0, 1
SEGMENT = 64   # positions a segment of the scan (memory only)


def layer_kinds(conf) -> list:
    """[(kind, dense)] of the layers held, by their published index."""
    first = conf["held"]["first_layer"]
    return [(LATENT if (i + 1) % conf["layer_group_size"] == 0 else LINEAR,
             i < conf["first_k_dense_replace"])
            for i in range(first, first + conf["num_hidden_layers"])]


def _sizes(conf):
    held = conf["held"]
    return dict(
        d=conf["hidden_size"], h=conf["num_attention_heads"],
        dk=conf["head_dim"], taps=conf["short_conv_kernel_size"],
        lower=float(conf["kda_lower_bound"]),
        rkv=conf["kv_lora_rank"], dn=conf["qk_nope_head_dim"],
        dr=conf["qk_rope_head_dim"], dv=conf["v_head_dim"],
        theta=float(conf["rope_theta"]),
        ff=conf["intermediate_size"], fe=conf["moe_intermediate_size"],
        fs=conf["moe_shared_expert_intermediate_size"],
        held=conf["num_experts"], width=held["router_width"],
        first=held["first_expert"], k=conf["num_experts_per_tok"],
        n_group=conf["n_group"], topk_group=conf["topk_group"],
        scaling=conf["routed_scaling_factor"], vocab=conf["vocab_size"],
        eps=conf["rms_norm_eps"], std=held["init_std"],
        layers=layer_kinds(conf))


@functools.partial(jax.jit, static_argnames=("shape",))
def _normal_stack(key, std, shape):
    """bfloat16[shape], normal(std), a matrix (the last two axes) at a time:
    no float32 form of the whole stack exists, and a matrix's generator
    compiles in a second where a stack's takes ten."""
    return jax.lax.map(
        lambda k: (std * jax.random.normal(k, shape[-2:], F32)).astype(
            jnp.bfloat16),
        jax.random.split(key, math.prod(shape[:-2]))).reshape(shape)


def _stacks(z) -> dict:
    """The shapes of the base's stacks of matrices (matrices along the
    leading axes), in the order `init` draws their keys."""
    d, h, dk = z["d"], z["h"], z["dk"]
    dn, dr, dv = z["dn"], z["dr"], z["dv"]
    n_lin = sum(kind == LINEAR for kind, _ in z["layers"])
    n_lat = len(z["layers"]) - n_lin
    n_dense = sum(dense for _, dense in z["layers"])
    n_exp = len(z["layers"]) - n_dense
    return {
        "linear": {"in": (n_lin, d, 5 * h * dk), "beta": (n_lin, d, h),
                   "conv": (n_lin, 3 * h * dk, z["taps"]),
                   "o": (n_lin, h * dk, d)},
        "latent": {"q": (n_lat, d, h * (dn + dr)),
                   "kv_a": (n_lat, d, z["rkv"] + dr),
                   "kv_b": (n_lat, z["rkv"], h * (dn + dv)),
                   "o": (n_lat, h * dv, d), "gate": (n_lat, d, h)},
        "embed": (1, z["vocab"], d), "head": (1, d, z["vocab"]),
        "mlp": {"gate_up": (n_dense, d, 2 * z["ff"]),
                "down": (n_dense, z["ff"], d)},
        "experts": {"gate_up": (n_exp * z["held"], d, 2 * z["fe"]),
                    "down": (n_exp * z["held"], z["fe"], d)},
        "shared": {"gate_up": (n_exp, d, 2 * z["fs"]),
                   "down": (n_exp, z["fs"], d)}}


def generators(conf) -> dict:
    """{shape: a function () -> `_normal_stack` lowered for that shape} of
    `init`'s distinct generators, for a caller that compiles them side by
    side ahead of `init` and hands it the compiled programs (`made`): one
    after another they hold `init` for a minute of the chip's compiler."""
    z = _sizes(conf)
    flat = lambda t: [s for v in t.values()  # noqa: E731
                      for s in (flat(v) if isinstance(v, dict) else [v])]
    key = jax.eval_shape(jax.random.key, 0)
    return {s: functools.partial(_normal_stack.lower, key, z["std"], s)
            for s in dict.fromkeys(flat(_stacks(z)))}


def init(seed: int, conf, made=None) -> dict:
    """Seeded weights: matrices normal(std) rounded to bfloat16, gains 1,
    `A_log` and `dt_bias` 0, the router's bias buffer normal(std) in
    float32. `made`: {shape: `_normal_stack` compiled for it}, called in
    place of it (`generators`)."""
    z = _sizes(conf)
    key = jax.random.key(seed)
    count = [0]

    def normal(shape):
        count[0] += 1
        return z["std"] * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, F32)

    def mat(shape):   # matrices along the leading axes
        count[0] += 1
        args = (jax.random.fold_in(key, count[0]), z["std"])
        return (made[shape](*args) if made is not None
                else _normal_stack(*args, shape))

    fill = lambda t: {k: fill(v) if isinstance(v, dict) else mat(v)  # noqa: E731
                      for k, v in t.items()}
    d, h, dk = z["d"], z["h"], z["dk"]
    base = fill(_stacks(z))
    base.update(embed=base["embed"][0], head=base["head"][0],
                bias=normal((sum(not dense for _, dense in z["layers"]),
                             z["width"])))
    params = {"blocks": [], "final_norm": jnp.ones(d, F32)}
    for kind, dense in z["layers"]:
        p = {"ln_attn": jnp.ones(d, F32), "ln_mlp": jnp.ones(d, F32)}
        if kind == LINEAR:
            p.update(A_log=jnp.zeros(h, F32), dt_bias=jnp.zeros(h * dk, F32),
                     o_norm=jnp.ones(dk, F32))
        else:
            p["kv_norm"] = jnp.ones(z["rkv"], F32)
        if not dense:
            p["router"] = normal((z["width"], d))
        params["blocks"].append(p)
    return {"base": base, "params": params}


def layer_weights(z, base, layer: int) -> dict:
    """Layer `layer`'s frozen matrices out of the base's stacks: `attn` its
    kind's leaves at its place among its kind, then `mlp`, or `shared`,
    `bias` and `experts` (every expert layer's, whole, with `first`: the
    place of this layer's first held expert among them)."""
    kinds = z["layers"]
    kind, dense = kinds[layer]
    ia = sum(k == kind for k, _ in kinds[:layer])
    im = sum(dn == dense for _, dn in kinds[:layer])
    w = {"attn": {n: t[ia] for n, t in
                  base["linear" if kind == LINEAR else "latent"].items()}}
    if dense:
        w["mlp"] = {n: t[im] for n, t in base["mlp"].items()}
    else:
        w.update(experts=base["experts"], first=im * z["held"],
                 shared={n: t[im] for n, t in base["shared"].items()},
                 bias=base["bias"][im])
    return w


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def bf16_values(a):
    """float32 values rounded to the nearest bfloat16 (ties to even), by
    integer operations: a compiler that is allowed excess precision drops a
    float32 -> bfloat16 -> float32 round trip, and this it cannot."""
    bits = jax.lax.bitcast_convert_type(a.astype(F32), jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & (
        jnp.uint32(0xFFFF0000))
    return jax.lax.bitcast_convert_type(bits, F32)


def conv(z, c, taps=None):
    """y[t] = sum_j c[:, j] z[t - (K - 1) + j] over the K last positions
    (zeros before the sequence), as K shifted adds; with `taps` < K only the
    `taps` newest count. z [B, S, C], c [C, K]."""
    k = c.shape[1]
    y = jnp.zeros_like(z)
    for j in range(k - (taps or k), k):
        back = k - 1 - j
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :z.shape[1]]
        y = y + shifted * c[:, j]
    return y


def delta_rule(q, k, v, g, beta, state_bf16=False, drop_state=None):
    """The recurrence, a position at a time. q, k, g [B, S, H, dk], v [B, S,
    H, dv], beta [B, S, H] -> o [B, S, H, dv]."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % SEGMENT
    t_first = lambda a: jnp.moveaxis(  # noqa: E731
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0).reshape(
            (s + pad) // SEGMENT, SEGMENT, b, *a.shape[2:])
    at = jnp.arange(s + pad).reshape(-1, SEGMENT)

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t, t = now          # [B, H, .], b_t [B, H]
        if drop_state:
            state = state * (t % drop_state != 0)
        state = jnp.exp(g_t)[..., None] * state   # S' = Diag(alpha) S
        u = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + b_t[..., None, None] * (
            k_t[..., :, None] * u[..., None, :])
        if state_bf16:
            state = bf16_values(state)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    segment = jax.checkpoint(lambda state, part: jax.lax.scan(step, state, part))
    _, o = jax.lax.scan(
        segment, jnp.zeros((b, h, dk, dv), F32),
        (*(t_first(a) for a in (q, k, v, g, beta)), at))
    return jnp.moveaxis(o.reshape(s + pad, b, h, dv), 0, 1)[:, :s]


def linear_attention(z, w, g, x, mm, state_bf16=False, decay_bf16=False,
                     drop_state=None, gate_form="bounded", beta=True,
                     conv_taps=None):
    """One linear layer on its normed input x [B, S, D] -> [B, S, D]. The
    keywords are the controls' departures."""
    b, s, _ = x.shape
    h, dk = z["h"], z["dk"]
    n = h * dk
    heads = lambda t: t.reshape(b, s, h, dk)  # noqa: E731
    # W_q, W_k, W_v, W_f, W_g side by side in `in`; q's, k's and v's
    # convolutions one under the other in `conv`
    made = mm(x, w["in"])
    x_q, x_k, x_v, x_f, x_g = (made[..., i * n:(i + 1) * n] for i in range(5))
    taps = w["conv"].astype(F32)
    q, k, v = (heads(jax.nn.silu(conv(t, taps[i * n:(i + 1) * n], conv_taps)))
               for i, t in enumerate((x_q, x_k, x_v)))
    unit = lambda t: t / jnp.sqrt(  # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + z["eps"])
    q, k = unit(q) * dk ** -0.5, unit(k)
    rate = jnp.exp(g["A_log"])[:, None]
    pre = heads(x_f + g["dt_bias"])
    if gate_form == "bounded":
        decay = z["lower"] * jax.nn.sigmoid(rate * pre)
    else:
        decay = -rate * jax.nn.softplus(pre)
    if decay_bf16:
        decay = bf16_values(decay)
    b_t = jax.nn.sigmoid(mm(x, w["beta"])) if beta else jnp.ones((b, s, h), F32)
    o = delta_rule(q, k, v, decay, b_t, state_bf16, drop_state)
    o = _norm(o, g["o_norm"], z["eps"]) * jax.nn.sigmoid(heads(x_g))
    return mm(o.reshape(b, s, h * dk), w["o"])


def _rope(x, theta):
    """Pairs (x_2i, x_2i+1) as complex numbers, turned by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(x.shape[1])[:, None] * freq[None, :], F32)
    turn = jax.lax.complex(jnp.cos(ang), jnp.sin(ang))[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    out = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * turn
    return jnp.stack([jnp.real(out), jnp.imag(out)], -1).reshape(x.shape)


def latent_attention(z, w, g, x, mm, gate="head"):
    """One latent layer on its normed input x [B, S, D] -> [B, S, D]."""
    b, s, _ = x.shape
    h, dn, dr, dv = z["h"], z["dn"], z["dr"], z["dv"]
    q = mm(x, w["q"]).reshape(b, s, h, dn + dr)
    kv_a = mm(x, w["kv_a"])
    c_kv, k_r = kv_a[..., :z["rkv"]], kv_a[..., z["rkv"]:]
    kv = mm(_norm(c_kv, g["kv_norm"], z["eps"]), w["kv_b"]).reshape(
        b, s, h, dn + dv)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], z["theta"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    k_r = _rope(k_r[:, :, None, :], z["theta"])[:, :, 0]      # [B, S, dr]
    rows = 512 if s % 512 == 0 else s

    @jax.checkpoint   # a block of query rows against every key, masked
    def attend(lo):
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, lo, rows, 1)  # noqa: E731
        sc = (jnp.einsum("bqhd,bkhd->bhqk", at(q_n), k_n)
              + jnp.einsum("bqhd,bkd->bhqk", at(q_r), k_r)) / math.sqrt(dn + dr)
        ok = (lo + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    outs = jax.lax.map(attend, jnp.arange(0, s, rows))     # [blocks, B, rows, H, dv]
    o = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dv)
    if gate is not None:
        open_ = jax.nn.sigmoid(mm(x, w["gate"]))               # [B, S, H]
        if gate == "head":
            o = o * open_[..., None]
        else:   # the H gates laid over the H x dv channels in turn
            o = (o.reshape(b, s, h * dv) * jnp.tile(open_, (1, 1, dv))).reshape(
                b, s, h, dv)
    return mm(o.reshape(b, s, h * dv), w["o"])


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


def route(z, router, bias, x, router_dtype=None):
    if router_dtype is not None:
        logits = jnp.dot(x.astype(router_dtype), router.T.astype(router_dtype),
                         preferred_element_type=F32)
    else:
        logits = x @ router.T
    s = jax.nn.sigmoid(logits)
    choice = s + bias
    if z["n_group"] > 1:
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


def held_experts(z, w, x, idx, weights, mm, drop_expert=None, cap=None,
                 first=0):
    """Sum over the held experts e of weight[t, e] * E_e(x[t]): a plain loop
    over the held experts, each over the rows routed to it. w's matrices may
    hold several layers' experts; this layer's start at `first`."""
    t = x.shape[0]
    dense_w = jnp.zeros((t, z["width"]), F32).at[
        jnp.arange(t)[:, None], idx].add(weights)
    dense_w = dense_w[:, z["first"]:z["first"] + z["held"]]
    if drop_expert is not None:
        dense_w = dense_w.at[:, drop_expert].set(0.0)
    cap = t if cap is None else min(cap, t)
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), F32)])

    @jax.checkpoint   # an expert's rows are made again for the gradient
    def rows_of(e):
        col = dense_w[:, e]
        rows = jnp.nonzero(col > 0, size=cap, fill_value=t)[0]
        we = {"gate_up": w["gate_up"][first + e], "down": w["down"][first + e]}
        ye = _glu(we, x_pad[rows], mm) * jnp.concatenate(
            [col, jnp.zeros((1,), F32)])[rows][:, None]
        return rows, ye, jnp.sum(col > 0)

    def one(y, e):   # y + expert e's part, where its rows are
        rows, ye, load = rows_of(e)
        return y.at[rows].add(ye, mode="drop"), load

    return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(z["held"]))


def _block(z, kind, w, g, h, mm, router_dtype, drop_expert, cap, linear_kw,
           gate):
    x = _norm(h, g["ln_attn"], z["eps"])
    if kind == LINEAR:
        a = linear_attention(z, w["attn"], g, x, mm, **linear_kw)
    else:
        a = latent_attention(z, w["attn"], g, x, mm, gate)
    h = h + a
    seen = {"attn_in": x, "attn_out": a}
    x = _norm(h, g["ln_mlp"], z["eps"])
    if "experts" not in w:
        return h + _glu_by_parts(w["mlp"], x, mm), seen
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = route(z, g["router"], w["bias"], flat, router_dtype)
    y, loads = held_experts(z, w["experts"], flat, idx, weights, mm,
                            drop_expert, cap, w["first"])
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


LINEAR_KW = ("state_bf16", "decay_bf16", "drop_state", "gate_form", "beta",
             "conv_taps")   # the departures a linear layer can show


def embed(base, tokens):
    """tokens int[B, S + 2] -> the residual stream's start, f32[B, S, D]."""
    return base["embed"][tokens[:, :tokens.shape[1] - 2]].astype(F32)


def block(conf, kind: int, w, g, h, first=0, quant=None, router_dtype=None,
          drop_expert=None, cap=None, gate="head", **linear_kw):
    """One layer of kind `kind` on the residual stream h -> (h, what it saw:
    `attn_in`, `attn_out` and, of an expert layer, `experts`, `router_in`,
    `loads`). w: `layer_weights`' (without `first`, which may be traced),
    g: the layer's trained leaves. Made again for the gradient. The check
    compiles this once a kind and runs the layers one after another."""
    inner = jax.checkpoint(lambda w, g, h, first: _block(
        _sizes(conf), kind, dict(w, first=first), g, h, _Products(quant),
        router_dtype, drop_expert, cap, linear_kw, gate))
    return inner(w, g, h, first)


def head(conf, matrix, gain, h, quant=None):
    """The final norm and the head -> logits f32[B, S, vocab]."""
    return _Products(quant)(_norm(h, gain, conf["rms_norm_eps"]), matrix)


def layer_order(conf, exchange=False) -> list:
    """The layers in the order they run; with `exchange` the latent layer one
    layer early."""
    kinds = layer_kinds(conf)
    order = list(range(len(kinds)))
    if exchange:
        at = next(i for i, (kind, _) in enumerate(kinds) if kind == LATENT)
        order[at - 1], order[at] = order[at], order[at - 1]
    return order


def collect(seen: dict, keep_inputs=False) -> dict:
    """`forward`'s aux from what the layers saw ({layer: `block`'s)."""
    seen = [seen[i] for i in sorted(seen)]
    routed = [a for a in seen if "experts" in a]
    loads = jnp.stack([a["loads"] for a in routed])
    out = {"experts": jnp.stack([a["experts"] for a in routed]),
           "loads": loads, "max_load": jnp.max(loads)}
    if keep_inputs:
        out["router_in"] = jnp.stack([a["router_in"] for a in routed])
        out["attn_in"] = jnp.stack([a["attn_in"] for a in seen])
        out["attn_out"] = jnp.stack([a["attn_out"] for a in seen])
    return out


def forward(variables, tokens, conf, quant=None, keep_inputs=False,
            exchange=False, **kw):
    """tokens int[B, S + 2] (the program's rows; the last id labels nothing
    here) -> (logits f32[B, S, vocab]: position i predicts token i + 1, aux).
    aux: `experts` int[expert layers, T, k], `loads`, `max_load` (the most
    rows one held expert was given) and, with `keep_inputs`, `router_in`
    [expert layers, T, D], `attn_in` and `attn_out` [layers, B, S, D]. `kw`:
    `block`'s."""
    z = _sizes(conf)
    base, p = variables["base"], variables["params"]
    h, seen = embed(base, tokens), {}
    for i in layer_order(conf, exchange):
        w = layer_weights(z, base, i)
        first = w.pop("first", 0)
        h, seen[i] = block(conf, z["layers"][i][0], w, p["blocks"][i], h, first,
                           quant, **kw)
    logits = head(conf, base["head"], p["final_norm"], h, quant)
    return logits, collect(seen, keep_inputs)


def ce(logits, targets):
    """The mean cross-entropy of logits [..., vocab] against integer targets."""
    logits = logits.reshape(-1, logits.shape[-1])
    lse = jax.nn.logsumexp(logits, -1)
    hit = jnp.take_along_axis(logits, targets.reshape(-1, 1), -1)[:, 0]
    return jnp.mean(lse - hit)


def loss(variables, tokens, conf, **kw):
    """-> (next-token cross-entropy, (logits, None, aux)): the mean over
    every position of every sequence; the second place is the prediction
    module's logits in the other token references, and none is built here.
    `kw`: `forward`'s."""
    logits, aux = forward(variables, tokens, conf, **kw)
    s = tokens.shape[1] - 2
    return ce(logits, tokens[:, 1:s + 1]), (logits, None, aux)


# --------------------------------------------------------------------------
# the model's own counts
# --------------------------------------------------------------------------


def kda_scan_flops(conf) -> int:
    """Floating-point operations of the recurrence alone for one position of
    one linear layer, forward, by the model's own count, whatever form
    computes it: S'^T k, the rank-one update and S^T q are 2 dk dv each a
    head (the decay's dk dv multiplies are not counted): 6 dk dv H."""
    return 6 * conf["head_dim"] * conf["head_dim"] * conf["num_attention_heads"]


def kda_scan_bytes(conf, itemsize: int = 4) -> int:
    """Bytes the recurrence must move for one position of one linear layer,
    forward: q, k, v and g read and o written once ([H, d] each), beta read
    ([H]), in the `itemsize` the program gives them (float32)."""
    h, d = conf["num_attention_heads"], conf["head_dim"]
    return (5 * h * d + h) * itemsize


def kda_front_flops(conf, backward: bool = False) -> int:
    """Floating-point operations of a linear layer's front (everything
    between the projections' one product and the recurrence's operands) for
    one position of one layer in one pass, from the module's equations above,
    a transcendental counted as one operation. Forward, a channel of H d: a
    short convolution of K taps 2 K (q, k and v); SiLU 4 (exp, add,
    reciprocal, multiply); the L2 norm a head 3 (square, sum, scale; q and
    k) and 2 a head (add eps, rsqrt); q's scale 1; the decay 6 (add the
    bias, times exp(A_log), the sigmoid's 3, times the lower bound); the
    move into chunks none: (3 (2 K + 4) + 2 x 3 + 1 + 6) H d + 4 H. Backward
    (the forward's intermediates made again, as a pass over the product
    alone must): a stream's convolution, sigmoid and product 2 K + 4 again,
    the cotangent through SiLU 5 and the convolution's transpose 2 K (q, k
    and v); through the norm 3 + 6 a channel and 4 a head (q and k); the
    decay's 13 (the gate made again 5, its derivative 3, times the
    cotangent and exp(A_log) 2, the two sums down the rows for dt_bias and
    A_log 3); the output gate's cotangent is passed on:
    (3 (4 K + 9) + 2 x 9 + 13) H d + 8 H."""
    n = conf["num_attention_heads"] * conf["head_dim"]
    taps = conf["short_conv_kernel_size"]
    if backward:
        return ((3 * (4 * taps + 9) + 2 * 9 + 13) * n
                + 8 * conf["num_attention_heads"])
    return ((3 * (2 * taps + 4) + 2 * 3 + 1 + 6) * n
            + 4 * conf["num_attention_heads"])


def kda_front_bytes(conf, backward: bool = False, itemsize: int = 4) -> int:
    """Bytes a linear layer's front must move for one position of one layer
    in one pass, each operand read once and each result written once in the
    `itemsize` the program gives them (float32; the taps, exp(A_log) and
    dt_bias are a layer's, not a position's). Forward: the product's q, k, v
    and decay columns read, q, k, v and g written: 8 H d. Backward: those
    four columns read again, the four cotangents and the output gate's read,
    the product's cotangent written, five columns wide: 14 H d."""
    n = conf["num_attention_heads"] * conf["head_dim"]
    return (14 if backward else 8) * n * itemsize


def forward_flops(conf, seq: int) -> dict:
    """Forward FLOPs of one token at sequence length `seq`, by the model's
    own count, whatever form computes it: the latent layer's scores and
    values over the causal pairs ((seq + 1) / 2 keys a query), a linear
    layer's recurrence as `kda_scan_flops`, the held experts by their
    expected share of the selections. -> by part, and `total`."""
    z = _sizes(conf)
    d, h, dk = z["d"], z["h"], z["dk"]
    dn, dr, dv = z["dn"], z["dr"], z["dv"]
    parts = {
        "linear_projections": 2 * d * (6 * h * dk + h)
        + 2 * z["taps"] * 3 * h * dk,
        "linear_recurrence": kda_scan_flops(conf),
        "latent_projections": 2 * (d * h * (dn + dr) + d * (z["rkv"] + dr)
                                   + z["rkv"] * h * (dn + dv) + h * dv * d
                                   + d * h),
        "latent_attend": 2 * h * (dn + dr + dv) * (seq + 1) / 2,
        "dense_mlp": 2 * 3 * d * z["ff"],
        "held_experts": z["k"] * z["held"] / z["width"] * 2 * 3 * d * z["fe"],
        "shared_expert": 2 * 3 * d * z["fs"],
        "router": 2 * z["width"] * d, "head": 2 * d * z["vocab"]}
    total = parts["head"]
    for kind, dense in z["layers"]:
        total += (parts["linear_projections"] + parts["linear_recurrence"]
                  if kind == LINEAR
                  else parts["latent_projections"] + parts["latent_attend"])
        total += parts["dense_mlp"] if dense else (
            parts["held_experts"] + parts["shared_expert"] + parts["router"])
    parts["total"] = total
    return parts
