"""Delta-rule linear attention: Kimi Delta Attention (arXiv:2510.26692), the
five layers in six of Ling-3.0-flash (`kda_layer`). q, k, v = SiLU(conv4(x
W)), q and k L2-normalised a head, a decay a channel g_t = -5
sigmoid(exp(A_log) (x_t W_f + dt_bias)) and beta_t = sigmoid(x_t W_beta) a
head, a head's 128 x 128 float32 state under the gated delta rule in chunks
of 64 positions (`kda_recurrence`: XLA operations, no kernel for it here), y =
(RMSNorm_head(o_t) * sigmoid(x_t W_g)) W_o. **The layer's front is one pass
over the projections' output each way** (PR 43): a Pallas kernel pair under a
`custom_vjp` (`_kda_front`) wherever a head is whole lanes wide
(`kda_front_kernel`: a rule of shape, not a switch), XLA's operations
elsewhere (`_kda_front_xla`: the tests' preset, and the kernels' reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hefl_tpu.models.lm import common
from hefl_tpu.models.lm.common import BF16, F32, HIGHEST, LMArch, _mm, rms_norm
from hefl_tpu.obs import scopes as obs_scopes

def short_conv(z, w):
    """Depthwise causal convolution over the last K positions: y[t, c] =
    sum_j w[c, j] z[t - (K - 1) + j, c] (w[:, K - 1] weighs position t
    itself; positions before the sequence read 0). z: f32[B, S, C], w:
    [C, K] -> f32[B, S, C], as K shifted multiply-adds."""
    k = w.shape[1]
    w = w.astype(F32)
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + z.shape[1]] * w[:, j] for j in range(k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(n, block: int):
    """(I + n)^-1 for n f32[..., C, C] strictly lower triangular, solved in
    sub-blocks of `block`, every step a [C, C] product in float32 at the
    highest precision: with n = d + l, d its diagonal sub-blocks and l what
    is below them, (I + d)^-1 is the product (I - d)(I + d^2)(I + d^4)... of
    the nilpotent d (d^block = 0: exact, and block-diagonal like d); then
    I + n = (I + d)(I + m), m = (I + d)^-1 l, and m is nilpotent by blocks
    (m^(C / block) = 0), so (I + m)^-1 is the same product over m: the
    substitution down the block rows, as products. Its gradient is the
    inverse's own: -T^T dT T^T."""
    c = n.shape[-1]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    eye = jnp.eye(c, dtype=F32)

    def nilpotent_inverse(x, order: int):     # (I + x)^-1, x^order = 0
        inv, power = eye - x, x
        for _ in range(max((order - 1).bit_length() - 1, 0)):
            power = mm(power, power)
            inv = mm(inv, eye + power)
        return inv

    at = jnp.arange(c) // block
    d = jnp.where(at[:, None] == at[None, :], n, 0.0)
    d_inv = nilpotent_inverse(d, block)
    return mm(nilpotent_inverse(mm(d_inv, n - d), c // block), d_inv)


def _unit_lower_inverse_fwd(n, block):
    inv = _unit_lower_inverse(n, block)
    return inv, inv


def _unit_lower_inverse_bwd(block, inv, d_inv):
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    t = jnp.swapaxes(inv, -1, -2)
    return (-mm(t, mm(d_inv, t)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


KDA_GROUP = 16   # chunks whose inside is made together (and again for the gradient)


def kda_recurrence(q, k, v, g, beta, chunk: int = 64, block: int = 16,
                   bound: float = 5.0, carry=True, operands=BF16,
                   chunked: bool = False):
    """The gated delta rule, a head at a time: S' = Diag(exp(g_t)) S_{t-1};
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T; o_t = S_t^T q_t, S_0 = 0.
    q, k, g: f32[B, S, H, dk] (0 >= g >= -`bound`), v: [B, S, H, dv], beta:
    [B, S, H] -> o f32[B, S, H, dv]. With `chunked` the operands are by
    chunk already, q, k, v, g [n, B, H, chunk, d] and beta [n, B, H, chunk]
    (as `_kda_front`'s kernel writes them: rows behind the sequence's end
    hold 0) and o has all their n * chunk positions; the other entry moves
    its operands into that form first (`by_chunk`, transposes) and is the
    tests' and the XLA front's.

    The chunked form. Inside a chunk of `chunk` positions, with G the
    running sum of g from the chunk's first position and S the state the
    chunk starts from, the deltas u_t = v_t - S'^T k_t solve the unit lower
    triangular system (I + A Diag(beta)) U = V - (K exp G) S, A[t, r] = sum_c
    k_t[c] k_r[c] exp(G_t[c] - G_r[c]) for r < t, and O = (Q exp G) S + (B
    Diag(beta)) U with B the same product of q_t and k_r for r <= t. A and B
    are made a sub-block of `block` rows at a time with the exponent split at
    the sub-block's first position, exp(G_t - G_b) exp(G_b - G_r): the first
    factor's exponent lies in [-block * bound, 0] and the second's below 0
    for an earlier sub-block, and in (0, block * bound] inside the row's own
    (16 x 5 = 80 < 88: float32 and bfloat16 hold it; a later sub-block's
    positions, masked for every row, are given no exponent). The system is
    solved in the same sub-blocks (`_unit_lower_inverse`). Chunks follow one
    another in a `lax.scan` that
    carries S f32[B, H, dk, dv]: U = U^ - W S, O, then S <- Diag(exp G_C) S
    + (K exp(G_C - G))^T (beta U). Matrix products take bfloat16 operands
    and accumulate in float32 (the inverse's small ones: float32); g, its
    sums, every decay and S are float32. What does not depend on S is made
    `KDA_GROUP` chunks at a time ahead of the scan; it and the chunk body
    are made again for the gradient (`jax.checkpoint`): the scan keeps the
    state at each chunk's edge and nothing else of a chunk (`_kept`). In a
    layer's gradient the forward of both loops therefore runs three times
    (the layer's own pass, the layer checkpoint's second one, and these
    inner checkpoints' inside the two backward loops). A sequence that is no
    multiple of `chunk` is padded at its end with positions that write
    nothing (k, v, beta, g = 0). Without `carry` every chunk starts from S =
    0 (what a form that drops the state at a chunk's edge computes: the
    tests' and the check's control). With `operands` float32 every product
    takes float32 operands at the highest precision (the tests' witness of
    what the bfloat16 operands cost the decay's gradient; no model runs
    it)."""
    if chunked:
        n, b, h, chunk, dk = q.shape
        s, pad = n * chunk, 0
    else:
        b, s, h, dk = q.shape
        pad = (-s) % chunk
    dv = v.shape[-1]
    n, m = (s + pad) // chunk, chunk // block
    group = next(c for c in range(min(n, KDA_GROUP), 0, -1) if n % c == 0)

    def by_chunk(t):          # [B, S, H, ...] -> [n / group, group, B, H, chunk, ...]
        if not chunked:
            t = jnp.pad(t.astype(F32),
                        ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            t = jnp.moveaxis(
                t.reshape(b, n, chunk, h, *t.shape[3:]), (1, 3), (0, 2))
        return t.reshape(n // group, group, *t.shape[1:])

    bmm = lambda eq, x, y: jnp.einsum(  # noqa: E731
        eq, x.astype(operands), y.astype(operands), preferred_element_type=F32,
        precision=HIGHEST if jnp.dtype(operands) == jnp.dtype(F32) else None)

    @jax.checkpoint
    def within(part):                 # `group` chunks, each by itself
        with jax.named_scope(obs_scopes.KDA_SCAN):
            q, k, v, g, beta = part                        # [group, B, H, chunk, .]
            run = jnp.cumsum(g, axis=-2)                   # G, inclusive
            by_block = lambda t: t.reshape(  # noqa: E731
                *t.shape[:-2], m, block, t.shape[-1])
            # G just before each sub-block's first position
            start = jnp.concatenate(
                [jnp.zeros_like(run[..., :1, :]),
                 run[..., block - 1:chunk - 1:block, :]], -2)   # [..., m, dk]
            near = jnp.exp(by_block(run) - start[..., None, :])
            # (a later sub-block's positions are masked for all of this
            # one's rows, and their exponent, up to chunk * bound, is left out)
            ahead = (jnp.arange(chunk) // block)[None, :] > jnp.arange(m)[:, None]
            far = jnp.exp(jnp.where(
                ahead[:, :, None], 0.0,
                start[..., :, None, :] - run[..., None, :, :]))
            k_far = k[..., None, :, :] * far               # [..., m, chunk, dk]
            pairs = lambda rows: bmm(  # noqa: E731
                "...mic,...mjc->...mij", by_block(rows) * near, k_far).reshape(
                    *rows.shape[:-2], chunk, chunk)
            t_pos = jnp.arange(chunk)
            a = jnp.where(t_pos[:, None] > t_pos[None, :], pairs(k), 0.0)
            b_qk = jnp.where(t_pos[:, None] >= t_pos[None, :], pairs(q), 0.0)
            solve = _unit_lower_inverse(a * beta[..., None, :], block)
            solve = solve * beta[..., :, None]             # beta U, not U
            last = run[..., -1:, :]
            # (what only a product reads is kept as the product takes it)
            return (bmm("...tr,...rd->...td", solve, v),
                    bmm("...tr,...rc->...tc", solve,
                        k * jnp.exp(run)).astype(operands),
                    (q * jnp.exp(run)).astype(operands),
                    (k * jnp.exp(last - run)).astype(operands),
                    b_qk.astype(operands),
                    jnp.exp(last[..., 0, :]))              # [group, B, H, dk]

    @jax.checkpoint
    def one(state, part):             # a chunk, from the state before it
        u_hat, w_in, q_in, k_out, b_qk, decay = part
        with jax.named_scope(obs_scopes.KDA_SCAN):
            u = u_hat - bmm("bhtc,bhcd->bhtd", w_in, state)
            o = bmm("bhtc,bhcd->bhtd", q_in, state) + bmm(
                "bhtr,bhrd->bhtd", b_qk, u)
            new = decay[..., None] * state + bmm("bhtc,bhtd->bhcd", k_out, u)
        return (new if carry else state), o

    parts = jax.lax.map(within, tuple(by_chunk(t) for t in (q, k, v, g, beta)))
    _, o = jax.lax.scan(one, jnp.zeros((b, h, dk, dv), F32), tuple(
        t.reshape(n, *t.shape[2:]) for t in parts))
    # [n, B, H, chunk, dv] -> [B, S, H, dv]
    return o.transpose(1, 0, 3, 2, 4).reshape(b, n * chunk, h, dv)[:, :s]


KDA_FRONT_HEADS = 4    # heads, and
KDA_FRONT_CHUNKS = 2   # chunks, a grid step of the front's kernels takes
KDA_HALO = 8           # rows of `made` a step reads before (and after) its own


def kda_front_kernel(arch: LMArch) -> bool:
    """Whether a linear layer's front is the kernel pair (`_kda_front`): a
    head fills whole lanes, a chunk whole sublanes and the convolution's
    reach lies inside the halo. The tests' preset (heads of 16) keeps the
    XLA form, which is the kernels' reference."""
    return (arch.kda_head_dim > 0 and arch.kda_head_dim % 128 == 0
            and arch.kda_chunk % 8 == 0 and arch.kda_conv - 1 <= KDA_HALO)


def _conv_rows(z, w_ref, i, lanes, back: bool = False):
    """The short convolution of stream i down the rows of z [R, d], y[p] =
    sum_t w[t] z[p - (K - 1) + t] in `short_conv`'s order, or with `back`
    its transpose, sum_t w[t] z[p + (K - 1) - t]. Rows wrap: the first (or
    with `back` the last) K - 1 rows of the result are no one's."""
    from jax.experimental.pallas import tpu as pltpu

    taps, y = w_ref.shape[1], None
    for t in range(taps):
        shift = (taps - 1 - t) if not back else (z.shape[0] - (taps - 1 - t))
        term = (pltpu.roll(z, shift, 0) if shift % z.shape[0] else z) * w_ref[
            i, t:t + 1, lanes]
        y = term if y is None else y + term
    return y


def _rows_inside(rows: int, d: int, seq: int, before: int, after: int):
    """Whether each row a step holds is a position of the sequence, bool
    [before + rows + after, d]: the rows before position 0 read zeros and
    the rows behind the end write nothing, whatever the blocks there hold."""
    from jax.experimental import pallas as pl

    row = pl.program_id(1) * rows - before + jax.lax.broadcasted_iota(
        jnp.int32, (before + rows + after, d), 0)
    return (row >= 0) & (row < seq)


def _kda_front_fwd_kernel(q_ref, k_ref, v_ref, f_ref, qh_ref, kh_ref, vh_ref,
                          w_ref, ab_ref, oq_ref, ok_ref, ov_ref, og_ref, *,
                          seq, eps, lower):
    """`KDA_FRONT_CHUNKS` chunks of `made`'s rows and a few heads' lanes of
    its q, k, v and decay columns (`*_ref` [rows, L]; `*h_ref`: the
    `KDA_HALO` rows before them) -> those chunks of q, k, v, g, a head at a
    time (`o*_ref` [chunks, heads, chunk, d]). w_ref: the taps [3, K, L];
    ab_ref [2, L]: exp(A_log) a lane, and dt_bias."""
    chunks, heads, chunk, d = oq_ref.shape
    inside = _rows_inside(chunks * chunk, d, seq, KDA_HALO, 0)
    here = inside[KDA_HALO:]

    def write(out, j, rows):
        rows = jnp.where(here, rows, 0.0)
        for c in range(chunks):
            out[c, j] = rows[c * chunk:(c + 1) * chunk]

    for j in range(heads):
        lanes = slice(j * d, (j + 1) * d)
        for i, (cur, halo, out) in enumerate((
                (q_ref, qh_ref, oq_ref), (k_ref, kh_ref, ok_ref),
                (v_ref, vh_ref, ov_ref))):
            z = jnp.where(inside, jnp.concatenate(
                [halo[:, lanes], cur[:, lanes]], 0), 0.0)
            y = _conv_rows(z, w_ref, i, lanes)[KDA_HALO:]
            a = y * jax.nn.sigmoid(y)
            if i < 2:
                a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + eps)
            write(out, j, a * d ** -0.5 if i == 0 else a)
        u = ab_ref[0:1, lanes] * (f_ref[:, lanes] + ab_ref[1:2, lanes])
        write(og_ref, j, lower * jax.nn.sigmoid(u))


def _kda_front_bwd_kernel(q_ref, k_ref, v_ref, f_ref, qb_ref, kb_ref, vb_ref,
                          qa_ref, ka_ref, va_ref, w_ref, ab_ref, cq_ref,
                          ck_ref, cv_ref, cg_ref, cqa_ref, cka_ref, cva_ref,
                          gate_ref, d_ref, part_ref, *, seq, eps, lower):
    """The gradient's side of `_kda_front_fwd_kernel`, one column block of
    d_made a step (the grid's last axis: q, k, v, the decay, the output
    gate's, which is its cotangent passed on): the forward's intermediates
    made again from the same rows of `made` with a halo on both sides
    (`*b_ref` before, `*a_ref` after), the cotangents `c*_ref` [chunks,
    heads, chunk, d] with the `KDA_HALO` rows after them (`c*a_ref` [heads,
    8, d]: the convolution's transpose reads K - 1 later rows). part_ref
    [2, L]: the step's sums down the rows for dt_bias's gradient and, a
    lane, for exp(A_log)'s."""
    from jax.experimental import pallas as pl

    chunks, heads, chunk, d = cq_ref.shape
    stream = pl.program_id(3)
    inside = _rows_inside(chunks * chunk, d, seq, KDA_HALO, KDA_HALO)
    later = inside[KDA_HALO:]                       # rows + the halo after
    here = inside[KDA_HALO:KDA_HALO + chunks * chunk]
    rows = lambda ref, j: jnp.concatenate(  # noqa: E731
        [ref[c, j] for c in range(chunks)], 0)

    def conv_stream(i, cur, before, after, cot, cot_after):
        for j in range(heads):
            lanes = slice(j * d, (j + 1) * d)
            z = jnp.where(inside, jnp.concatenate(
                [before[:, lanes], cur[:, lanes], after[:, lanes]], 0), 0.0)
            y = _conv_rows(z, w_ref, i, lanes)[KDA_HALO:]
            c = jnp.concatenate([rows(cot, j), cot_after[j]], 0)
            gate = jax.nn.sigmoid(y)
            a = y * gate
            if i < 2:       # through a = a r (Sum a^2 + eps)^-1/2, q's scaled
                r = jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + eps)
                c = (r * d ** -0.5 if i == 0 else r) * (
                    c - a * (r * r) * jnp.sum(c * a, -1, keepdims=True))
            c = jnp.where(later, c * gate * (1.0 + y * (1.0 - gate)), 0.0)
            d_ref[:, lanes] = _conv_rows(c, w_ref, i, lanes, back=True)[
                :chunks * chunk]

    for i, refs in enumerate(((q_ref, qb_ref, qa_ref, cq_ref, cqa_ref),
                              (k_ref, kb_ref, ka_ref, ck_ref, cka_ref),
                              (v_ref, vb_ref, va_ref, cv_ref, cva_ref))):
        pl.when(stream == i)(functools.partial(conv_stream, i, *refs))

    @pl.when(stream == 3)
    def _():
        for j in range(heads):
            lanes = slice(j * d, (j + 1) * d)
            a_lane = ab_ref[0:1, lanes]
            biased = jnp.where(here, f_ref[:, lanes] + ab_ref[1:2, lanes], 0.0)
            gate = jax.nn.sigmoid(a_lane * biased)
            d_u = jnp.where(here, rows(cg_ref, j), 0.0) * (
                lower * gate * (1.0 - gate))
            d_ref[:, lanes] = d_u * a_lane
            part_ref[0:1, lanes] = jnp.sum(d_u * a_lane, 0, keepdims=True)
            part_ref[1:2, lanes] = jnp.sum(d_u * biased, 0, keepdims=True)

    @pl.when(stream == 4)
    def _():
        d_ref[...] = gate_ref[...]


def _kda_front_call(arch: LMArch, made, conv, a_log, dt_bias, cots=None):
    """The front's kernel over `made` f32[B, S, 5 H d]: forward -> q, k, v,
    g [n, B, H, chunk, d]; with `cots` (their cotangents and the output
    gate's [B, S, H d]) the gradient's -> (d_made, d_A_log, d_dt_bias)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, _ = made.shape
    h, d, chunk, taps = arch.heads, arch.kda_head_dim, arch.kda_chunk, arch.kda_conv
    n = -(-s // chunk)
    heads = next(c for c in range(min(h, KDA_FRONT_HEADS), 0, -1) if h % c == 0)
    chunks = next(c for c in range(min(n, KDA_FRONT_CHUNKS), 0, -1) if n % c == 0)
    rows, lanes, across = chunks * chunk, heads * d, h // heads
    halos = rows // KDA_HALO                       # halo blocks a step's rows
    last = -(-s // KDA_HALO) - 1                   # the last halo block there is
    w = conv.astype(F32).reshape(3, h * d, taps).swapaxes(1, 2)
    ab = jnp.stack([jnp.repeat(jnp.exp(a_log), d), dt_bias]).astype(F32)
    # (the grid: batch, step of `chunks` chunks, step of `heads` heads[, stream])
    cur = lambda i: pl.BlockSpec(  # noqa: E731
        (None, rows, lanes), lambda b, c, j, *_: (b, c, i * across + j))
    before = lambda i: pl.BlockSpec(  # noqa: E731
        (None, KDA_HALO, lanes),
        lambda b, c, j, *_: (b, jnp.maximum(c * halos - 1, 0), i * across + j))
    after = lambda i: pl.BlockSpec(  # noqa: E731
        (None, KDA_HALO, lanes),
        lambda b, c, j, *_: (b, jnp.minimum((c + 1) * halos, last),
                             i * across + j))
    taps_spec = pl.BlockSpec((3, taps, lanes), lambda b, c, j, *_: (0, 0, j))
    ab_spec = pl.BlockSpec((2, lanes), lambda b, c, j, *_: (0, j))
    by_chunk = pl.BlockSpec((chunks, None, heads, chunk, d),
                            lambda b, c, j, *_: (c, b, j, 0, 0))
    kw = dict(seq=s, eps=arch.eps, lower=arch.kda_lower_bound)
    if cots is None:
        out = jax.ShapeDtypeStruct((n, b, h, chunk, d), F32)
        return pl.pallas_call(
            functools.partial(_kda_front_fwd_kernel, **kw),
            grid=(b, n // chunks, across),
            in_specs=[cur(0), cur(1), cur(2), cur(3), before(0), before(1),
                      before(2), taps_spec, ab_spec],
            out_specs=[by_chunk] * 4, out_shape=[out] * 4,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",) * 3),
            interpret=common._interpret(), name="kda_front_fwd",
        )(*(made,) * 7, w, ab)
    *by, gate = cots
    chunk_after = pl.BlockSpec(
        (None, None, heads, KDA_HALO, d),
        lambda b, c, j, *_: (jnp.minimum((c + 1) * chunks, n - 1), b, j, 0, 0))
    d_made, parts = pl.pallas_call(
        functools.partial(_kda_front_bwd_kernel, **kw),
        grid=(b, n // chunks, across, 5),
        in_specs=[cur(0), cur(1), cur(2), cur(3), before(0), before(1),
                  before(2), after(0), after(1), after(2), taps_spec, ab_spec,
                  by_chunk, by_chunk, by_chunk, by_chunk, chunk_after,
                  chunk_after, chunk_after,
                  pl.BlockSpec((None, rows, lanes), lambda b, c, j, i: (b, c, j))],
        out_specs=[
            pl.BlockSpec((None, rows, lanes),
                         lambda b, c, j, i: (b, c, i * across + j)),
            pl.BlockSpec((None, None, 2, lanes), lambda b, c, j, i: (b, c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(made.shape, F32),
                   jax.ShapeDtypeStruct((b, n // chunks, 2, h * d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=common._interpret(), name="kda_front_bwd",
    )(*(made,) * 10, w, ab, *by, *by[:3], gate)
    d_bias, d_a = jnp.sum(parts, (0, 1))
    return (d_made,
            (jnp.exp(a_log) * jnp.sum(d_a.reshape(h, d), -1)).astype(a_log.dtype),
            d_bias.astype(dt_bias.dtype))


def _kda_front_xla(arch: LMArch, made, conv, a_log, dt_bias):
    """The front in XLA's operations, a pass each: `made` f32[B, S, 5 H d]
    -> q, k, v, g f32[B, S, H, d]. What a model whose heads are no whole
    lanes runs (the tests' preset), and what the kernel pair `_kda_front`
    is held to."""
    b, s, _ = made.shape
    h, d = arch.heads, arch.kda_head_dim
    n = h * d
    heads = lambda t: t.reshape(b, s, h, d)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(short_conv(
        made[..., i * n:(i + 1) * n], conv[i * n:(i + 1) * n])))
        for i in range(3))
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + arch.eps)
    q, k = unit(q) * d ** -0.5, unit(k)
    return q, k, v, arch.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * heads(made[..., 3 * n:4 * n] + dt_bias))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda_front(arch: LMArch, made, conv, a_log, dt_bias):
    """Everything of a linear layer between its projections' one product
    `made` f32[B, S, 5 H d] and the recurrence's operands, in one pass over
    `made` each way: q, k, v = silu(short_conv(.)) of their columns, q and k
    L2-normalised a head (q times d^-1/2), the decay g = lower_bound *
    sigmoid(exp(A_log) (f + dt_bias)), all float32 and all written by chunk
    ([n, B, H, chunk, d]: the move into chunks is the output's index map,
    and rows behind the sequence's end hold 0), and the output gate's
    columns as they are ([B, S, H d]: a slice, its cotangent goes through
    the gradient's kernel into d_made's fifth block). What `_kda_front_xla`
    computes, operation for operation (the tests hold the pair to it);
    `conv` is frozen and gets no gradient."""
    n = arch.heads * arch.kda_head_dim
    return (*_kda_front_call(arch, made, conv, a_log, dt_bias),
            made[..., 4 * n:])


def _kda_front_fwd(arch, made, conv, a_log, dt_bias):
    return (_kda_front(arch, made, conv, a_log, dt_bias),
            (made, conv, a_log, dt_bias))


def _kda_front_bwd(arch, kept, cots):
    d_made, d_a_log, d_bias = _kda_front_call(arch, *kept, cots=cots)
    return d_made, jnp.zeros_like(kept[1]), d_a_log, d_bias


_kda_front.defvjp(_kda_front_fwd, _kda_front_bwd)


def kda_layer(arch: LMArch, w, g, x):
    """One linear-attention layer (the module's equations). w: the block's
    frozen matrices: `in` [D, 5 H d], the projections W_q, W_k, W_v, W_f (the
    decay's) and W_g (the output gate's) side by side as `gate_up` keeps two
    (one product makes all five: one to compile, in each direction), `beta`
    [D, H], `conv` [3 H d, K] the short convolutions of q, k and v, `o` [H d,
    D]; g: its trained leaves (`A_log` [H], `dt_bias` [H d], `o_norm` [d]);
    x: [B, S, D] (already normed). The front, from the product `made` to the
    recurrence's operands, has two lowerings of the one algorithm, chosen by
    shape (`kda_front_kernel`): the kernel pair (`_kda_front`), whose output
    the recurrence takes by chunk as it is, or XLA's operations
    (`_kda_front_xla`), which are what the kernels are held to."""
    with jax.named_scope(obs_scopes.KDA):
        b, s, _ = x.shape
        h, d = arch.heads, arch.kda_head_dim
        n = h * d
        heads = lambda t: t.reshape(b, s, h, d)  # noqa: E731
        made = _mm(x, w["in"])
        leaves = (w["conv"], g["A_log"], g["dt_bias"])
        if kda_front_kernel(arch):
            chunk = arch.kda_chunk
            *front, gate = _kda_front(arch, made, *leaves)
            beta = jnp.pad(jax.nn.sigmoid(_mm(x, w["beta"])),
                           ((0, 0), (0, (-s) % chunk), (0, 0))).reshape(
                b, -1, chunk, h).transpose(1, 0, 3, 2)     # [n, B, H, chunk]
            o = kda_recurrence(*front, beta, chunk, arch.kda_block,
                               -arch.kda_lower_bound, chunked=True)[:, :s]
        else:
            front = _kda_front_xla(arch, made, *leaves)
            beta = jax.nn.sigmoid(_mm(x, w["beta"]))
            o = kda_recurrence(*front, beta, arch.kda_chunk, arch.kda_block,
                               -arch.kda_lower_bound)
            gate = None     # sliced below, where this form's program has it
        o = rms_norm(o, g["o_norm"], arch.eps) * jax.nn.sigmoid(heads(
            made[..., 4 * n:5 * n] if gate is None else gate))
        return _mm(o.reshape(b, s, n), w["o"])
