"""The check of a cell whose model is a frozen-base language model with
delta-rule linear-attention layers and a gated latent layer
(`hefl_tpu/models/lm.py` with `kda_head_dim`; reference `<path>/reference/
ling_3_flash.py`): `checks/lm_subset.py`'s check, from which it takes what
the token checks share (`cell["module"]("checks", "lm_subset")`: the norms,
the leaf sums, the float8 stand-in, the ladder of row caps, what is printed
and not judged), with the recurrence, the decay's gradient and the gate held
to the reference as well, and **written around the run's 360 s**: a cold run
of this cell compiles every program it uses, so the check compiles few.

What `lm_subset`'s docstring says holds here: the weights are the
reference's, its base planted as the system's; one timed batch; one round of
the cell's own geometry through `secure_fedavg_round` and `decrypt_average`.
What differs:

- **The reference runs a layer at a time**: `ref.block` and its gradient
  compiled once a kind of layer (linear + dense, linear + experts, latent +
  experts) and the layers called one after another from the host, the
  model's gradient by the chain rule over them: six layers cost three small
  compiles in each direction, and what each layer saw and gave comes with
  the forward pass (kept on the host: beside an 8.6 GB base the chip has no
  room for it). A client's first reference gradient serves the timed
  batch's numbers and the check round's. **What the check will call is
  compiled ahead** (`Ahead`: the system's one program, `_sys_fns`: the timed
  `loss` with its gradient and `apply`'s logits and selections; the
  generators of the reference's weights, which `init` is handed compiled;
  the reference's layers and their gradients; the system's layers alone and
  the larger comparisons), eight at a time on the host's other cores, while
  the reference's weights are made and the check round runs. What the host
  can add and divide it does itself: a one-operation program on the device
  is a compile each.
- **The decays are planted away from their start** (`plant_decays`: `A_log`
  normal(0.3), `dt_bias` -5 + normal(1) from the seed: a channel's decay a
  position exp(-0.03) on average, a tenth of that or ten times it by
  channel, so that the state a chunk starts from weighs): at their start (0,
  0) a state is forgotten within two positions and nothing behind a chunk's
  edge could be seen.
- `kda_layer_gap`: the system's linear layer (`lm.kda_layer`, the chunked
  form) on the reference's own layer input against the reference's layer
  output (position by position), the norm of the difference over the
  reference's norm, worst of the linear layers. `gated_layer_gap`: the same
  for the latent layer with its gate a head.
- `decay_grad_gap`: the gradient of the system's loss with respect to every
  linear layer's `A_log` and `dt_bias` against the reference's, the norm of
  the difference over the reference's norm.
- `future_leak`: the largest change of a linear layer's output at positions
  <= t when every position > t of its input holds noise, and of the short
  convolution's output at positions >= t when every position <= t - K holds
  noise. Exactly 0.
- **The check round is the timed program** (the same module, train
  configuration and mesh: no second compile of the round), decrypted by the
  owner's `decrypt_average`; its step against plain float32 Adam over the
  reference on the same batches (`step_norm_gap`, `leaf_step_gap`,
  `val_loss_gap`). The timed program gives no plain mean, so `he_avg_err` is
  read beside it: the reference's two trained clients encrypted
  (`fl.secure.encrypt_params`), summed (`aggregate_encrypted`) and decrypted
  by the same `decrypt_average`, against their float32 mean: the same HE
  kernels, parameters and row count, on the values a round carries.
- The logits are compared over the tokens whose *held* experts are chosen as
  the reference chooses them in every expert layer (`lm_sparse_subset`'s
  rule), in units of what the float8 reference errs over its own such
  tokens. That unit is the configuration's `held.fp8_logit_err`, read on the
  chip by the controls (`fp8_logit_err` of a run with its controls, which
  reads it anew; PERF.md has the seeds' range): a run without them does not
  pay for a float8 pass of the reference and its compile.

Controls (`control_numbers`), each the reference in the system's place with
one departure: `lm_subset`'s float8 base products, bfloat16 router and a held
expert's output dropped; the carried state in bfloat16; the decay in
bfloat16; the state dropped at a chunk's edge; the gate without its bound
(softplus form); beta left out; a convolution of 3; the latent layer's gate a
channel; left out; the latent layer one layer early. PERF.md gives the
readings and the limit each fails.

This check follows a synchronous, unpacked, IID round on a 1-D mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types


def _shared(cell):
    return cell["module"]("checks", "lm_subset")


# The reference in the system's place, one departure each (`chunk` is filled
# in from the configuration's `held`).
LINEAR_VARIANTS = {
    "control_state_bf16": {"state_bf16": True},
    "control_decay_bf16": {"decay_bf16": True},
    "control_state_dropped": {"drop_state": "chunk"},
    "control_softplus_gate": {"gate_form": "softplus"},
    "control_no_beta": {"beta": False},
    "control_conv_3": {"conv_taps": 3},
    "control_gate_a_channel": {"gate": "channel"},
    "control_no_gate": {"gate": None},
    "control_kinds_exchanged": {"exchange": True},
}
SHARED_VARIANTS = ("control_fp8", "control_router_bf16",
                   "control_dropped_expert")
RECORD_ONLY = ("route_agree_forward", "tokens_compared_share", "logit_err_max",
               "skipped_step_reads", "router_left_out_reads", "decay_mean")
# (`fp8_logit_err`, which only a run with its controls reads, beside them)


def _variants(shared, conf) -> dict:
    out = {name: dict(shared.VARIANTS[name]) for name in SHARED_VARIANTS}
    for name, kw in LINEAR_VARIANTS.items():
        out[name] = {k: conf["held"]["chunk"] if v == "chunk" else v
                     for k, v in kw.items()}
    return out


class Reference:
    """The reference a layer at a time, with `kw` (a variant's departures)
    in every layer: `ref.block` and its gradient jitted once a kind of
    layer, the layers called one after another from the host. A layer's
    frozen matrices are picked out of the base's stacks inside its program
    (by its place among its kind, a traced index): nothing is sliced by the
    host."""

    def __init__(self, ref, conf, **kw):
        import jax

        self.ref, self.conf = ref, conf
        self.z = ref._sizes(conf)
        self.order = ref.layer_order(conf, kw.pop("exchange", False))
        self.quant = kw.get("quant")
        self.kw = kw
        highest = lambda: jax.default_matmul_precision("highest")  # noqa: E731
        at = lambda tree, i: jax.tree_util.tree_map(  # noqa: E731
            lambda t: t[i], tree)
        held = self.z["held"]

        @functools.lru_cache(maxsize=None)
        def block(kind: int, dense: bool, cap: int):
            """-> (the layer, its gradient by its trained leaves and its
            input given the gradient by its output), both compiled once."""
            def run(stacks, ia, im, g, h):
                w = {"attn": at(stacks["attn"], ia)}
                if dense:
                    w["mlp"] = at(stacks["mlp"], im)
                else:
                    w.update(experts=stacks["experts"], bias=stacks["bias"][im],
                             shared=at(stacks["shared"], im))
                with highest():
                    return ref.block(conf, kind, w, g, h, im * held, cap=cap,
                                     **self.kw)

            def pull(stacks, ia, im, g, h, dh):
                return jax.vjp(lambda g, h: run(stacks, ia, im, g, h)[0],
                               g, h)[1](dh)

            return jax.jit(run), jax.jit(pull)

        def head(matrix, gain, h, tokens):
            with highest():
                logits = ref.head(conf, matrix, gain, h, self.quant)
                s = tokens.shape[1] - 2
                return ref.ce(logits, tokens[:, 1:s + 1]), logits

        def attention(kind: int):
            """A layer's attention alone, on its normed input."""
            z, mm = self.z, ref._Products(self.quant)
            linear = {k: v for k, v in kw.items() if k in ref.LINEAR_KW}

            def run(stack, ia, g, x):
                with highest():
                    if kind == ref.LINEAR:
                        return ref.linear_attention(z, at(stack, ia), g, x, mm,
                                                    **linear)
                    return ref.latent_attention(z, at(stack, ia), g, x, mm,
                                                kw.get("gate", "head"))
            return jax.jit(run)

        self.block, self.head = block, jax.jit(head)
        self.head_pull = jax.jit(jax.grad(
            lambda gain, h, matrix, tokens: head(matrix, gain, h, tokens)[0],
            argnums=(0, 1)))
        self.embed = jax.jit(ref.embed)
        self.attention = functools.lru_cache(maxsize=None)(attention)
        # does a layer of this reference differ from the plain one's?
        self.layer_departs = bool(
            self.quant or "gate" in kw or set(kw) & set(ref.LINEAR_KW))

    def place(self, i: int):
        """Layer i -> (its kind, whether its MLP is dense, its place among
        the layers of its kind, its place among those with its MLP)."""
        kinds = self.z["layers"]
        kind, dense = kinds[i]
        return (kind, dense, sum(k == kind for k, _ in kinds[:i]),
                sum(d == dense for _, d in kinds[:i]))

    def stacks(self, base, kind: int, dense: bool) -> dict:
        """The base's stacks a layer of this kind picks its matrices from."""
        out = {"attn": base["linear" if kind == self.ref.LINEAR else "latent"]}
        if dense:
            return dict(out, mlp=base["mlp"])
        return dict(out, experts=base["experts"], shared=base["shared"],
                    bias=base["bias"])

    def cap(self, shared, positions: int, rung: int = 0) -> int:
        mean = positions * self.conf["num_experts_per_tok"] / self.z["width"]
        return int(min(positions, max(8, shared.CAP_LADDER[rung] * mean)))

    def forward(self, shared, p, base, tokens, keep=()):
        """One sequence [1, S + 2] -> (loss, logits, aux, what the gradient
        needs), at the first rung of the ladder of caps that holds the
        busiest expert's rows. Of what each layer saw, aux keeps `experts`
        and `loads` and the names in `keep`, on the host (2 GB of layer
        inputs beside an 8.6 GB base leave the blocks no room)."""
        import numpy as np

        t = int(tokens.shape[1]) - 2
        for rung in range(len(shared.CAP_LADDER)):
            cap = self.cap(shared, t, rung)
            h, seen, tape = self.embed(base, tokens), {}, []
            for i in self.order:
                kind, dense, ia, im = self.place(i)
                tape.append((i, cap, h))
                h, saw = self.block(kind, dense, cap)[0](
                    self.stacks(base, kind, dense), np.int32(ia), np.int32(im),
                    p["blocks"][i], h)
                seen[i] = {k: np.asarray(v) for k, v in saw.items()
                           if k in ("experts", "loads", *keep)}
            load = max(int(a["loads"].max()) for a in seen.values()
                       if "loads" in a)
            if load <= cap:
                break
        else:
            raise RuntimeError(
                f"one expert was routed {load} of {t} tokens, more than "
                f"{shared.CAP_LADDER[-1]} times the mean")
        loss, logits = self.head(base["head"], p["final_norm"], h, tokens)
        routed = [seen[i] for i in sorted(seen) if "experts" in seen[i]]
        aux = {"experts": np.stack([a["experts"] for a in routed]),
               "layers": [seen[i] for i in sorted(seen)], "routed": routed}
        return loss, logits, aux, (tape, h)

    def value_and_grad(self, shared, p, base, tokens, keep=()):
        """-> ((loss, logits, aux), the gradient by every trained leaf): the
        chain rule over the layers, each layer's own gradient compiled once
        a kind."""
        import numpy as np

        loss, logits, aux, (tape, h) = self.forward(shared, p, base, tokens,
                                                    keep)
        d_gain, dh = self.head_pull(p["final_norm"], h, base["head"], tokens)
        grads = {}
        for i, cap, h_in in reversed(tape):
            kind, dense, ia, im = self.place(i)
            grads[i], dh = self.block(kind, dense, cap)[1](
                self.stacks(base, kind, dense), np.int32(ia), np.int32(im),
                p["blocks"][i], h_in, dh)
        return (loss, logits, aux), {
            "blocks": [grads[i] for i in sorted(grads)], "final_norm": d_gain}

    def programs(self, shared, p, base, tokens, gradient=True) -> list:
        """[(jitted function, its arguments' shapes)] of what `forward` (and
        `value_and_grad`) will call at the first rung, for `Ahead`."""
        import jax
        import jax.numpy as jnp

        cap = self.cap(shared, int(tokens.shape[1]) - 2)
        d = self.z["d"]
        h = jax.ShapeDtypeStruct((1, tokens.shape[1] - 2, d), jnp.float32)
        at = jax.ShapeDtypeStruct((), jnp.int32)
        jobs = [(self.embed, (base, tokens)),
                (self.head, (base["head"], p["final_norm"], h, tokens))]
        if gradient:
            jobs.append((self.head_pull,
                         (p["final_norm"], h, base["head"], tokens)))
        for kind, dense in dict.fromkeys(self.z["layers"]):
            i = self.z["layers"].index((kind, dense))
            run, pull = self.block(kind, dense, cap)
            args = (self.stacks(base, kind, dense), at, at, p["blocks"][i], h)
            jobs.append((run, args))
            if gradient:
                jobs.append((pull, (*args, h)))
        return jobs


class _Results(dict):
    """{key: a future}, read as {key: its result}."""

    def __getitem__(self, key):
        return super().__getitem__(key).result()


class Ahead:
    """Programs compiled ahead of their first call, eight at a time on the
    host's other cores, into JAX's persistent compile cache: the call then
    loads what was compiled while the chip and the host did something else.
    Without a cache directory (the CPU's tests) nothing is compiled ahead."""

    def __init__(self, workers: int = 8):
        import concurrent.futures

        import jax

        self.on = bool(jax.config.jax_compilation_cache_dir)
        self.pool = concurrent.futures.ThreadPoolExecutor(workers)
        self.jobs = []

    def add(self, jobs, always=False):
        """-> the futures; with `always` also without a cache (the caller
        calls the compiled programs themselves)."""
        import jax

        shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        new = [self.pool.submit(lambda f=f, a=a: f.lower(*shapes(a)).compile())
               for f, a in jobs if self.on or always]
        self.jobs += new
        return new

    @staticmethod
    def wait(futures):
        """Until these are compiled; one that failed is printed and goes on
        (its call will compile, or raise)."""
        import concurrent.futures

        concurrent.futures.wait(futures)
        for job in futures:
            if job.exception() is not None:
                print(f'{{"compiled_ahead_failed": {str(job.exception())[:300]!r}}}',
                      flush=True)


def plant_decays(variables, seed: int):
    """`variables` with every linear layer's `A_log` at normal(0.3) and
    `dt_bias` at -5 + normal(1), from the seed."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), 78)
    blocks = [
        dict(g, A_log=0.3 * jax.random.normal(
            jax.random.fold_in(key, 2 * i), g["A_log"].shape, jnp.float32),
            dt_bias=-5.0 + jax.random.normal(
                jax.random.fold_in(key, 2 * i + 1), g["dt_bias"].shape,
                jnp.float32))
        if "A_log" in g else g
        for i, g in enumerate(variables["params"]["blocks"])]
    return {"base": variables["base"],
            "params": dict(variables["params"], blocks=blocks)}


@functools.lru_cache(maxsize=None)
def _sys_fns(module):
    """The system on one batch in one program: its loss and gradient (the
    timed `loss`) and its logits and selections (`apply`)."""
    import jax

    def both(p, base, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: module.loss({"params": p, "base": base}, tokens)[0])(p)
        z, _, seen = module.apply({"params": p, "base": base}, tokens,
                                  routed=True)
        return loss, grads, z, seen

    return jax.jit(both)


@functools.lru_cache(maxsize=None)
def _compare_fns(module, first: int, n_held: int):
    """On the device: agreement of two sets of selections (`lm_sparse_subset`'s
    rule: a token counts where its held experts agree in every layer), the
    logits' widest error over a token mask, the cross-entropy of given
    logits, the system's router on the reference's router inputs, a layer's
    gap to the reference's output, and what leaks from later positions."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.models import lm

    arch = module.arch
    held = lambda e: (e >= first) & (e < first + n_held)  # noqa: E731

    def agree(sel, want):
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

    def routes(routers, bias, router_in):   # the system's router, a layer each
        return jnp.stack([lm.route(arch, r, b, x)[0]
                          for r, b, x in zip(routers, bias, router_in)])

    def held_pairs(sel):
        return jnp.sum(held(sel), axis=(1, 2))

    def gap(got, want):
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

    at = lambda tree, i: jax.tree_util.tree_map(lambda t: t[i], tree)  # noqa: E731
    linear = jax.jit(lambda stack, i, g, x: lm.kda_layer(arch, at(stack, i), g, x))
    latent = jax.jit(lambda stack, i, g, x: lm.latent_attention(
        arch, at(stack, i), g, x))

    def leak(stack, i, g, x, noise):
        """The largest change at positions <= t when positions > t hold
        noise."""
        t = x.shape[1] // 2 + 5
        later = (jnp.arange(x.shape[1]) > t)[None, :, None]
        moved = linear(stack, i, g, jnp.where(later, noise, x))
        return jnp.max(jnp.abs(moved[:, :t + 1] - linear(stack, i, g, x)[:, :t + 1]))

    def conv_leak(taps, i, key):
        """`lm.short_conv`: the largest change at positions >= t when
        positions <= t - K hold noise."""
        c = taps[i]
        x, noise = jax.random.normal(key, (2, 1, 96, c.shape[0]), jnp.float32)
        t, k = 53, c.shape[1]
        behind = (jnp.arange(96) <= t - k)[None, :, None]
        moved = lm.short_conv(jnp.where(behind, noise, x), c)
        return jnp.max(jnp.abs(moved[:, t:] - lm.short_conv(x, c)[:, t:]))

    fns = {name: jax.jit(f) for name, f in dict(
        agree=agree, err=err, ce=ce, routes=routes, held_pairs=held_pairs,
        gap=gap, conv_leak=conv_leak, leak=leak).items()}
    fns.update(linear=linear, latent=latent)
    return fns


def _layer_numbers(fns, ref_run, variables, layers, seed: int, stand_in=None):
    """`kda_layer_gap`, `gated_layer_gap` and `future_leak` of the system's
    layers on the reference's layer inputs (`layers`: what each layer saw and
    gave, on the host) or, with `stand_in` (a `Reference` with a departure),
    of its layers."""
    import jax
    import jax.numpy as jnp

    ref = ref_run.ref
    p, base = variables["params"], variables["base"]
    gaps = {ref.LINEAR: 0.0, ref.LATENT: 0.0}
    leaked = 0.0
    if stand_in is not None and not stand_in.layer_departs:
        return {"kda_layer_gap": 0.0, "gated_layer_gap": 0.0,
                "future_leak": 0.0}   # the reference's own layers
    for i in range(len(layers)):
        kind, _, ia, _ = ref_run.place(i)
        x = jnp.asarray(layers[i]["attn_in"])
        want = jnp.asarray(layers[i]["attn_out"])
        stack = base["linear" if kind == ref.LINEAR else "latent"]
        g, ia = p["blocks"][i], jnp.int32(ia)
        if stand_in is not None:
            layer = stand_in.attention(kind)
        else:
            layer = fns["linear" if kind == ref.LINEAR else "latent"]
        gaps[kind] = max(gaps[kind],
                         float(fns["gap"](layer(stack, ia, g, x), want)))
        if kind == ref.LINEAR and stand_in is None:
            key = jax.random.key(seed + i)
            noise = jax.random.normal(key, x.shape, jnp.float32)
            leaked = max(leaked, float(fns["leak"](stack, ia, g, x, noise)),
                         float(fns["conv_leak"](stack["conv"], ia, key)))
    return {"kda_layer_gap": gaps[ref.LINEAR],
            "gated_layer_gap": gaps[ref.LATENT], "future_leak": leaked}


def _decay_leaves(tree) -> list:
    return [g[name] for g in tree["blocks"] if "A_log" in g
            for name in ("A_log", "dt_bias")]


def _tokens_compared(fns, unit, got, want_logits, want_experts, sel, tokens,
                     loss) -> dict:
    """The numbers of one kind's logits, selections and loss against the
    reference's, the logits' error in `unit`s."""
    share, mask = fns["agree"](sel, want_experts)
    worst = float(fns["err"](got, want_logits, mask))
    want = float(fns["ce"](got, tokens))
    return {"logit_err_vs_fp8": worst / unit,
            "loss_gap": abs(float(loss) - want) / want,
            "route_agree_forward": float(share),
            "tokens_compared_share": float(mask.mean()),
            "logit_err_max": worst}


def _rounds(cfg, module, p0, x, y):
    """The cell's own round from the planted weights through the timed
    program and the owner's decrypt -> the decrypted average, the clients'
    validation losses, overflow, the clients' shards and batch order."""
    import jax
    import numpy as np

    from hefl_tpu.ckks import packing
    from hefl_tpu.ckks.keys import keygen
    from hefl_tpu.data import iid_contiguous, stack_federated
    from hefl_tpu.fl import decrypt_average, secure_fedavg_round
    from hefl_tpu.fl.client import epoch_index_streams, train_batch_geometry
    from hefl_tpu.fl.fedavg import pad_federated
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
    mesh = make_mesh(n_cl)
    xs_p, ys_p, num_real = pad_federated(xs, ys, client_mesh_size(mesh))
    place = client_sharding(mesh)
    ctx = cfg.he.build()
    _, k_he = jax.random.split(jax.random.key(cfg.seed))
    sk, pk = keygen(ctx, k_he)
    key = jax.random.fold_in(jax.random.key(cfg.seed), 1000)
    train_keys = jax.random.split(jax.random.split(key)[0], n_cl)
    perms = np.asarray(epoch_index_streams(tc, train_keys, m)[0])
    for c in range(n_cl):
        if len(set(perms[c].ravel().tolist())) != steps * grp:
            raise RuntimeError("the check's batches repeat a row")
    spec = packing.spec_for(p0, ctx.n)   # of the trained subset alone
    owner = functools.partial(decrypt_average, ctx, sk, num_clients=n_cl,
                              spec=spec, base_params=p0)

    def timed():
        ct, mets, overflow = secure_fedavg_round(
            module, tc, mesh, ctx, pk, p0, jax.device_put(xs_p, place),
            jax.device_put(ys_p, place), key, num_real_clients=num_real)[:3]
        if int(ct.c0.shape[0]) != -(-spec.total // ctx.n):
            raise RuntimeError(f"{ct.c0.shape[0]} ciphertext rows for "
                               f"{spec.total} trained parameters at N {ctx.n}")
        return {"avg": jax.tree_util.tree_map(np.asarray, owner(ct)),
                "val": np.asarray(mets, np.float64)[:n_cl, 0, 0],
                "overflow": int(np.sum(np.asarray(overflow))),
                "rows": int(ct.c0.shape[0]),
                "placed": jax.tree_util.tree_map(lambda a: a.sharding, ct)}

    return {"timed": timed, "owner": owner, "ctx": ctx, "pk": pk, "xs": xs,
            "m": m, "n_tr": n_tr, "steps": steps, "grp": grp, "perms": perms,
            "tc": tc}


def _numbers(cell, cfg, data, controls: bool) -> dict:
    """Every number of a sound run and, with `controls`, each control's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hefl_tpu.fl import secure
    from hefl_tpu.models import lm, set_frozen_base

    shared = _shared(cell)
    conf = _conf(cell)
    ref = cell["module"]("reference", cell["config"]["reference"])
    adam = cell["module"]("reference", "adam")
    # the run's own module, so that the check round is the timed program
    module = lm.FrozenBaseLM(num_classes=cfg.train.num_classes,
                             arch=lm.PRESETS[cfg.model], seed=int(cfg.seed))
    set_frozen_base(module, None)   # the run's base goes before the check's comes
    (x, y) = data[0]
    plain = Reference(ref, conf)
    fns = _compare_fns(module, conf["held"]["first_expert"],
                       conf["num_experts"])

    # ---- what the check will call compiles ahead, beside the reference's
    # weights being made and the check round: the system's one program (the
    # timed `loss` with its gradient, `apply`'s logits and selections), the
    # reference's layers and their gradients, the system's layers alone
    ahead = Ahead()
    p_s, base_s = (jax.eval_shape(module.init_trained),
                   jax.eval_shape(module.init_base))
    seq_s = jax.ShapeDtypeStruct((1, int(x.shape[1])), jnp.int32)
    h_s = jax.ShapeDtypeStruct((1, int(x.shape[1]) - 2, conf["hidden_size"]),
                               jnp.float32)
    at_s = jax.ShapeDtypeStruct((), jnp.int32)
    system = ahead.add([(_sys_fns(module), (p_s, base_s, seq_s))], always=True)
    made = ref.generators(conf)
    made = _Results(zip(made, ahead.add(
        [(types.SimpleNamespace(lower=lower), ()) for lower in made.values()],
        always=True)))
    reference = ahead.add(plain.programs(shared, p_s, base_s, seq_s))
    g_of = lambda kind: next(  # noqa: E731
        g for g, (k, _) in zip(p_s["blocks"], plain.z["layers"]) if k == kind)
    routed = [g["router"] for g in p_s["blocks"] if "router" in g]
    row = lambda t: t.update(shape=t.shape[1:])  # noqa: E731
    picked_s = jax.ShapeDtypeStruct(
        (len(routed), h_s.shape[1], conf["num_experts_per_tok"]), jnp.int32)
    layers_alone = ahead.add(
        [(fns["linear"], (base_s["linear"], at_s, g_of(ref.LINEAR), h_s)),
         (fns["latent"], (base_s["latent"], at_s, g_of(ref.LATENT), h_s)),
         (fns["leak"], (base_s["linear"], at_s, g_of(ref.LINEAR), h_s, h_s)),
         (fns["routes"], (routed, [row(base_s["bias"])] * len(routed),
                          [row(h_s)] * len(routed))),
         (fns["agree"], (picked_s, picked_s)),
         (fns["conv_leak"], (base_s["linear"]["conv"], at_s,
                             jax.eval_shape(jax.random.key, 0)))])
    ahead.pool.shutdown(wait=False)   # nothing more is asked for

    variables = plant_decays(ref.init(cfg.seed, conf, made), cfg.seed)
    p0, base = variables["params"], variables["base"]
    set_frozen_base(module, base)
    before = shared._leaf_sums(base)
    rnd = _rounds(cfg, module, p0, x, y)
    n_cl, m, n_tr, tc = cfg.num_clients, rnd["m"], rnd["n_tr"], rnd["tc"]
    rows_of = lambda c: rnd["xs"][c, m - n_tr:][rnd["perms"][c]]  # noqa: E731
    seq = jnp.asarray(rows_of(0)[0][:1])   # the first client's first sequence

    # ---- the check round: the timed program, the owner's decrypt
    timed = rnd["timed"]()
    moved = sum(a != b for a, b in zip(before, shared._leaf_sums(base)))
    ahead.wait(reference)

    # ---- the reference's clients: the same batches, one after another
    first_seen = {}   # the first client's first gradient, with what it saw
    keep = ("attn_in", "attn_out", "router_in")

    def batch_vg(c):
        def vg(p, rows, _):
            got = []
            for i in range(len(rows)):
                one = jnp.asarray(rows[i:i + 1])
                first = c == 0 and i == 0 and not first_seen
                (loss, logits, aux), grads = plain.value_and_grad(
                    shared, p, base, one, keep if first else ())
                if first:   # its logits wait on the host for the system's
                    first_seen.update(logits=np.asarray(logits), aux=aux,
                                      grads=grads)
                del logits, aux
                got.append((float(loss), jax.tree_util.tree_map(
                    np.asarray, grads)))   # (the host's arithmetic from here)
            grads = jax.tree_util.tree_map(lambda *a: sum(a) / len(a),
                                           *[g for _, g in got])
            return (sum(v for v, _ in got) / len(got), None), grads
        return vg

    as_f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: np.asarray(a, np.float32), t)
    add = lambda acc, t: t if acc is None else jax.tree_util.tree_map(  # noqa: E731
        np.add, acc, t)
    total, short, val_gaps, trained = None, None, [], []
    for c in range(n_cl):
        trail, _ = adam.steps(batch_vg(c), p0,
                              [(rows, None) for rows in rows_of(c)],
                              tc.lr, tc.lr_decay, tc.warmup_steps)
        total = add(total, trail[-1])
        short = add(short, trail[-2] if rnd["steps"] > 1 else
                    jax.tree_util.tree_map(np.asarray, p0))
        trained.append(as_f32(trail[-1]))
        val = jnp.asarray(rnd["xs"][c, :m - n_tr])
        want = float(np.mean([float(plain.forward(
            shared, trained[-1], base, val[i:i + 1])[0])
            for i in range(len(val))]))
        val_gaps.append(abs(timed["val"][c] - want) / want)
    mean = lambda t: jax.tree_util.tree_map(lambda a: a / n_cl, t)  # noqa: E731
    delta = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        t, p0)
    d_sys, d_ref = delta(timed["avg"]), delta(mean(total))
    lost = jax.tree_util.tree_map(lambda a: a, d_sys)
    lost["blocks"][-1]["router"] = np.zeros_like(lost["blocks"][-1]["router"])

    # ---- the HE error beside it: the reference's clients through the same
    # encrypt, sum and decrypt
    keys = jax.random.split(jax.random.fold_in(jax.random.key(cfg.seed), 1001),
                            n_cl)
    cts = [secure.encrypt_params(rnd["ctx"], rnd["pk"], t, k)
           for t, k in zip(trained, keys)]
    # (summed in one program, and placed as the round's sum was: the owner's
    # two programs are the ones the round compiled)
    again = rnd["owner"](jax.device_put(
        jax.jit(lambda *cts: secure.aggregate_encrypted(
            rnd["ctx"], jax.tree_util.tree_map(lambda *a: jnp.stack(a), *cts)))(
                *cts), timed["placed"]))
    leaves = lambda t: [np.asarray(v, np.float64)  # noqa: E731
                        for v in jax.tree_util.tree_leaves(t)]
    he_err = max(float(np.max(np.abs(a - b))) for a, b in zip(
        leaves(again), leaves(mean(jax.tree_util.tree_map(
            lambda *a: sum(np.asarray(v, np.float64) for v in a), *trained)))))
    shared.say(check_round={
        "clients": n_cl, "sequences_a_client": m, "steps": rnd["steps"],
        "batch": rnd["grp"], "validation_rows": m - n_tr,
        "ciphertext_rows_a_client": timed["rows"]})
    sound = {
        "step_norm_gap": shared.whole_norm_gap(d_sys, d_ref),
        "leaf_step_gap": shared.norm_gap(d_sys, d_ref),
        "val_loss_gap": float(max(val_gaps)),
        "he_avg_err": he_err if math.isfinite(he_err) else float("inf"),
        "base_moved": int(moved),
        "check_round_overflow": timed["overflow"],
        "skipped_step_reads": shared.whole_norm_gap(delta(mean(short)), d_ref),
        "router_left_out_reads": shared.norm_gap(lost, d_ref),
    }
    del rnd, cts, again, trained, timed

    # ---- the timed batch: the system on the first client's trained sequence
    loss, g_sys, logits, (loads, sel) = system[0].result()(p0, base, seq)
    aux, g_ref = first_seen["aux"], first_seen["grads"]
    want = jnp.asarray(first_seen.pop("logits"))
    experts = jnp.asarray(aux["experts"])
    sound["dropped_pairs"] = int(np.sum(np.abs(
        np.asarray(fns["held_pairs"](sel)) - np.asarray(loads).sum(-1))))
    # what the float8 reference errs by over its own compared tokens: the
    # unit of the logits' number, read by the controls and kept in the file
    unit = conf["held"]["fp8_logit_err"]
    sound.update(_tokens_compared(fns, unit, logits, want, experts, sel, seq,
                                  loss))
    routers = [g["router"] for g in p0["blocks"] if "router" in g]
    sound["route_agree_share"] = float(fns["agree"](
        fns["routes"](routers, list(base["bias"]),
                      [jnp.asarray(a["router_in"]) for a in aux["routed"]]),
        experts)[0])
    sound["grad_norm_gap"] = shared.norm_gap(g_sys, g_ref)
    flat = lambda t: np.concatenate([  # noqa: E731
        np.asarray(a, np.float64).ravel() for a in _decay_leaves(t)])
    sound["decay_grad_gap"] = float(
        np.linalg.norm(flat(g_sys) - flat(g_ref)) / np.linalg.norm(flat(g_ref)))
    del logits, sel, g_sys
    ahead.wait(layers_alone)
    sound.update(_layer_numbers(fns, plain, variables, aux["layers"],
                                int(cfg.seed) % 2 ** 30))
    # for the record: the planted decays' mean a position, exp(g), at x W_f = 0
    sound["decay_mean"] = float(np.mean([np.mean(np.exp(
        conf["kda_lower_bound"] / (1.0 + np.exp(-np.exp(np.asarray(
            g["A_log"]))[:, None] * np.asarray(g["dt_bias"]).reshape(
                g["A_log"].shape[0], -1))))) for g in p0["blocks"]
        if "A_log" in g]))
    got = {"sound": sound}
    for name, kw in (_variants(shared, conf).items() if controls else ()):
        stand_in = Reference(ref, conf, **kw)
        v_loss, v_logits, v_aux, _ = stand_in.forward(shared, p0, base, seq)
        if name == "control_fp8":   # the unit, read anew
            _, f_mask = fns["agree"](jnp.asarray(v_aux["experts"]), experts)
            sound["fp8_logit_err"] = float(fns["err"](v_logits, want, f_mask))
        row = _tokens_compared(fns, unit, v_logits, want, experts,
                               jnp.asarray(v_aux["experts"]), seq, v_loss)
        row["route_agree_share"] = row["route_agree_forward"]
        row["dropped_pairs"] = 0
        row.update(_layer_numbers(fns, plain, variables, aux["layers"], 0,
                                  stand_in))
        got[name] = row
        del v_logits, v_aux, stand_in
    return got


# --------------------------------------------------------------------------
# what the harness and controls.py call
# --------------------------------------------------------------------------


def _conf(cell) -> dict:
    """The configuration file's own keys, as the reference takes them: the
    published numbers at the top level and the group `held`."""
    return {k: v for k, v in cell["config"].items()
            if not isinstance(v, (dict, list, str)) or k == "held"}


def round_work(cell, cfg, data) -> dict:
    """`lm_subset.round_work` over this reference's `forward_flops` (the
    model's own count: a linear layer's recurrence as 6 dk dv a head a
    token, the latent layer's attention over the causal pairs)."""
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


def numbers(cell, cfg, data) -> dict:
    shared = _shared(cell)
    got = _numbers(cell, cfg, data, controls=False)["sound"]
    shared.say(**{k: got.pop(k) for k in RECORD_ONLY})
    got["encode_overflow"] = got.pop("check_round_overflow")
    return got


def control_data(cfg):
    from hefl_tpu.data import make_dataset

    return make_dataset(cfg.dataset, seed=cfg.seed, n_train=cfg.n_train,
                        n_test=1)


def control_numbers(cell, cfg, data) -> dict:
    """Every reading of a sound run, and each control's."""
    return _numbers(cell, cfg, data, controls=True)
