"""The expert layer of the token models: the published sigmoid router over
all `n_experts` (`route`; group-limited where the experts are in groups) and
the experts this chip holds (`held_start`, `held_experts`). Selection and
normalisation run over every expert; the held experts' terms are computed by
a grouped matrix product over the (token, held expert) pairs sorted by expert
(`held_experts`: every pair whatever the imbalance, no capacity, nothing
dropped) and summed with the shared expert; what absent experts would add is
left out. How many sorted rows go through one product and how many through
blocks is a plan (`expert_plan`): the cells differ by its values.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from hefl_tpu.models.lm import common
from hefl_tpu.models.lm.common import BF16, F32, HIGHEST, LMArch, _mm
from hefl_tpu.obs import scopes as obs_scopes

def glu(w, x):
    """W_down (silu(W_gate x) * W_up x); gate and up side by side in one
    matrix (`gate_up`: the first half of its columns is the gate)."""
    gu = _mm(x, w["gate_up"])
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], w["down"])


GLU_BYTES = 2 ** 29   # the most a float32 [tokens, gate and up] array may hold


def glu_by_parts(w, x):
    """`glu` over x [B, S, D] with its tokens in as few equal parts (a power
    of two) as keep the float32 gate-and-up array under `GLU_BYTES`, each
    part made again for the gradient; one part is `glu` itself."""
    b, s, d = x.shape
    parts = 1
    while (b * s * w["gate_up"].shape[-1] * 4 > parts * GLU_BYTES
           and (b * s) % (2 * parts) == 0):
        parts *= 2
    if parts == 1:
        return glu(w, x)
    return jax.lax.map(jax.checkpoint(lambda part: glu(w, part)),
                       x.reshape(parts, -1, d)).reshape(b, s, d)


def route(arch: LMArch, router, bias, x):
    """The published router, float32: s = sigmoid(W_r x) over all experts,
    the `experts_per_tok` largest of s + bias, weights routed_scaling * s_k
    / sum of the selected s. With `n_group` groups of experts the choice is
    group-limited: a group scores the sum of its two largest s + bias, and
    only the experts of the `topk_group` best groups can be chosen. x: [T,
    D] -> (experts int32[T, k], weights f32[T, k])."""
    with jax.named_scope(obs_scopes.MOE_ROUTE):
        s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router.T, precision=HIGHEST,
                                   preferred_element_type=F32))
        choice = s + bias
        if arch.n_group > 1:
            t, n = choice.shape
            groups = choice.reshape(t, arch.n_group, n // arch.n_group)
            best = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
            _, keep = jax.lax.top_k(best, arch.topk_group)
            kept = jnp.zeros((t, arch.n_group), bool).at[
                jnp.arange(t)[:, None], keep].set(True)
            choice = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(t, n)
        _, idx = jax.lax.top_k(choice, arch.experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = arch.routed_scaling * w / jnp.sum(w, -1, keepdims=True)
        return idx.astype(jnp.int32), w


GMM_ROWS = 256   # rows a tile: a held expert's mean load at the benchmark's batch


def _gmm_tiles(k: int, n: int) -> tuple[int, int]:
    """(tk, tn): whole dimensions up to 1024, so an expert's matrix is read
    in one or two strips a row tile."""
    fit = lambda d: d if d <= 1024 else next(  # noqa: E731
        t for t in (1024, 768, 512, 384, 256, 128) if d % t == 0)
    return fit(k), fit(n)


def _gmm_call(x, w, sizes, transpose: bool, rows: int = GMM_ROWS):
    """out[rows of group g] = x[rows of group g] @ w[g] (w[g].T if
    `transpose`), rows sorted by group, `sizes` rows a group, `rows` rows a
    tile. The Pallas
    grouped product of `jax.experimental.pallas.ops.tpu.megablox` (the
    expert's matrix is found through scalar prefetch and, transposed, read as
    it lies: no copy of a frozen matrix in any layout). Interpreted off the
    TPU. Rows behind the last group are not written: the caller masks them."""
    import importlib

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    m = x.shape[0]
    n = w.shape[1] if transpose else w.shape[2]
    tm = min(rows, -(-m // 8) * 8)
    pad = (-m) % tm
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
    out = gmm(x, w, sizes, preferred_element_type=F32,
              tiling=(tm, *_gmm_tiles(x.shape[1], n)), transpose_rhs=transpose,
              interpret=common._interpret())
    return out[:m] if pad else out


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """x bf16[m, k] (rows sorted by group) times the group's matrix of w
    bf16[g, k, n] -> f32[m, n]; rows behind the last group read 0. Its
    gradient is taken with respect to x alone: w is frozen, and no
    [g, k, n] cotangent is ever formed."""
    return _masked_rows(_gmm_call(x, w, sizes, False), sizes)


def _masked_rows(out, sizes):
    return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, 0.0)


def _grouped_fwd(x, w, sizes):
    return grouped_matmul(x, w, sizes), (w, sizes)


def _grouped_bwd(res, dy):
    w, sizes = res
    dx = _masked_rows(_gmm_call(dy.astype(BF16), w, sizes, True), sizes)
    return dx.astype(BF16), None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


FRONT_ROWS = 512   # rows a tile of a filled front's grouped products
SUM_LEAD = 4       # pairs a token `_sum_by_token` gathers for every token
SUM_BLOCK = 128    # pairs a block of the rest


def pair_blocks(arch: LMArch, pairs: int) -> tuple[int, int]:
    """(blocks, rows a block) the held experts' sorted pairs are computed
    in. A chip that holds half of the layer's experts or more takes every
    pair in one grouped product; one that holds fewer takes the held pairs,
    which sort first, in blocks of `pair_block` rows, as many as hold them."""
    if 2 * arch.held_experts >= arch.n_experts or pairs <= arch.pair_block:
        return 1, pairs
    return -(-pairs // arch.pair_block), arch.pair_block


def front_pairs(arch: LMArch, pairs: int) -> int:
    """The sorted held pairs a filled front takes ahead of the blocks: whole
    blocks, `pair_front` at most; 0 where the pairs are one block."""
    blocks, rows = pair_blocks(arch, pairs)
    return min(arch.pair_front, pairs) // rows * rows if blocks > 1 else 0


@dataclasses.dataclass(frozen=True)
class ExpertPlan:
    """How an expert layer computes a batch's (token, expert) pairs, as Python
    values (`expert_plan` makes it; `held_experts` and `_with_counts` read
    it): the first `front` sorted pairs in one grouped product a matrix
    (`_held_rows`), those behind them in blocks of `rows` (`_held_blocks`)."""

    front: int       # sorted pairs the rows product takes: 0, whole blocks, all
    rows: int        # rows a block
    blocks: int      # blocks the pairs make, those of the front among them
    tile: int        # rows a tile of the rows product
    filled: bool = False   # every row of the front computed, whatever is held
    spare: int = 0         # rows of one tile behind the front that no group has
    counted: bool = False  # the order by counting (`_counted_order`), not sorts


def expert_plan(arch: LMArch, pairs: int, counted: bool) -> ExpertPlan:
    """The plan of a batch of `pairs` (token, expert) pairs; `counted` in a
    step of `hybrid_layers`' scan. Three forms, a cell on each:
      * **every pair, a group its load** (a chip that holds half of a
        layer's experts or more, `pair_blocks`; joyai's cell, PR 40):
        nothing behind the last group is computed, written, masked or read.
      * **a filled front and blocks behind it, sorted** (mimo's cell, PR
        34): the first `pair_front` sorted pairs in one product of that many
        rows, those behind the last held pair given to the last group: a
        fixed capacity, the same work whatever the routers have learnt,
        until the held pairs outgrow it (then the blocks take over: still
        every pair). A spare tile reads 0 for the un-sort's gathers
        (`_sum_by_token`). `pair_front` 0: blocks alone (deepseek's, PR 31).
      * **the same, counted** (ling's cell, PR 41: the chip's compiler takes
        15 s over each sort of 65,536 keys, the sorted form has four, and a
        scan's step is compiled in both directions): no sort, no spare tile,
        the un-sort one gather a (token, slot)."""
    if counted:
        rows = min(arch.pair_block, pairs)
        front = min(arch.pair_front or pairs, pairs)
        front = front if front == pairs else front // rows * rows
        return ExpertPlan(front, rows, -(-pairs // rows), FRONT_ROWS,
                          filled=True, counted=True)
    blocks, rows = pair_blocks(arch, pairs)
    if blocks == 1:
        return ExpertPlan(pairs, rows, 1, GMM_ROWS)
    front = front_pairs(arch, pairs)
    return ExpertPlan(front, rows, blocks, FRONT_ROWS, filled=front > 0,
                      spare=FRONT_ROWS if front else 0)


def held_experts(arch: LMArch, w, x, idx, weights, at=None):
    """The held experts' part of the layer: sum over the selected experts
    that live here of weight * E(x). Every (token, held expert) pair is
    computed, sorted by expert into a grouped matrix product; pairs of
    absent experts sort behind the last group and add nothing. The plan
    (`expert_plan`) says how many rows go through one product and how many
    through blocks. With `at` (a step of `hybrid_layers`' scan) w's matrices
    hold several layers' experts, layer-major ([layers * held, ...]), and
    this layer's are those from `at * held` on (`_held_counted`).
    -> (y f32[T, D], load int32[held]: pairs a held expert computed)."""
    with jax.named_scope(obs_scopes.MOE_EXPERTS):
        t, k = idx.shape
        held = arch.held_experts
        local = idx - arch.held_start
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held).reshape(t * k)
        if at is not None:
            return _held_counted(arch, w, x, key, jnp.where(here, weights, 0.0),
                                 at)
        plan = expert_plan(arch, t * k, False)
        order = jnp.argsort(key, stable=True)
        load = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        if plan.blocks > 1:
            # (padded to whole blocks: a pair behind the held ones, of token 0)
            xf, padded, pair_w = (
                x.astype(F32),
                jnp.pad(order, (0, plan.blocks * plan.rows - t * k)),
                jnp.where(here, weights, 0.0).reshape(t * k))
            y = _held_blocks(w, xf, padded, load, pair_w, k, plan.rows,
                             plan.front // plan.rows)
            if plan.front:
                y = y + _held_rows(plan, k, w, xf, order, None, load, pair_w)
            return y, load
        return _held_rows(plan, k, w, x.astype(F32), order, None, load,
                          jnp.where(here, weights, 0.0)), load


def _held_counted(arch: LMArch, w, x, key, pair_w, at):
    """`held_experts` in a step of `hybrid_layers`' scan: the pairs' order by
    counting (`_counted_order`), no sort anywhere. key int32[T * k]: a pair's
    held expert, or `held` for an absent one; pair_w [T, k]: 0 for a pair of
    an absent expert. This layer's experts are w's groups from `at * held` on:
    the other layers' groups are given no row and no matrix is sliced out."""
    t, k = pair_w.shape
    held, pairs = arch.held_experts, t * k
    plan = expert_plan(arch, pairs, True)
    pos, order, counts = _counted_order(key, held + 1)
    load = counts[:held]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros(w["down"].shape[0], jnp.int32), load, (at * held,))
    xf, pair_w = x.astype(F32), pair_w.reshape(pairs)
    y = _held_rows(plan, k, w, xf, order, pos, groups, pair_w)
    if plan.front < pairs:
        y = y + _held_blocks(
            w, xf, jnp.pad(order, (0, plan.blocks * plan.rows - pairs)),
            groups, pair_w, k, plan.rows, plan.front // plan.rows)
    return y, load


COUNT_BLOCK = 512   # pairs a block of `_counted_order`'s running counts


def _counted_order(key, buckets: int):
    """The stable order of `key` int32[P] (values in [0, buckets)) without a
    sort -> (pos int32[P]: the place pair p sorts to, order int32[P]: its
    inverse, what `argsort(key, stable=True)` gives, counts int32[buckets]).
    A pair's place is the pairs of smaller keys plus the pairs of its own
    key before it: the running count a key, inside a block of `COUNT_BLOCK`
    pairs as one product with a triangle of ones (0 / 1 operands, float32
    sums: exact), across blocks as a running sum of the blocks' counts."""
    pairs = key.shape[0]
    blk = next(c for c in range(min(pairs, COUNT_BLOCK), 0, -1) if pairs % c == 0)
    hot = (key[:, None] == jnp.arange(buckets)).astype(BF16).reshape(
        pairs // blk, blk, buckets)
    inside = jnp.einsum("ij,bjc->bic", jnp.tril(jnp.ones((blk, blk), BF16)), hot,
                        preferred_element_type=F32)            # inclusive
    per_block = inside[:, -1]
    ran = (inside + (jnp.cumsum(per_block, 0) - per_block)[:, None]).reshape(
        pairs, buckets).astype(jnp.int32)
    counts = ran[-1]
    pos = ((jnp.cumsum(counts) - counts)[key]
           + jnp.take_along_axis(ran, key[:, None], 1)[:, 0] - 1)
    order = jnp.zeros(pairs, jnp.int32).at[pos].set(
        jnp.arange(pairs, dtype=jnp.int32), unique_indices=True)
    return pos, order, counts


def _range_sizes(load, lo, rows: int):
    """Rows a group among the sorted rows [lo, lo + rows): the parts of the
    experts' runs (`load` rows each, one behind the other) that fall inside."""
    ends = jnp.cumsum(load)
    return (jnp.clip(ends, lo, lo + rows)
            - jnp.clip(ends - load, lo, lo + rows)).astype(jnp.int32)


def _expert_rows(w, x, tokens, product):
    """The experts' `glu` over sorted rows: x's rows `tokens` (x itself with
    None), narrowed, through `product(rows, matrices)` twice -> (the gate
    and up product f32[m, 2 f], the outputs f32[m, D])."""
    with jax.named_scope(obs_scopes.MOE_GMM):
        xs = x.astype(BF16)
        gu = product(xs if tokens is None else xs[tokens], w["gate_up"])
    f = gu.shape[-1] // 2
    hid = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        return gu, product(hid, w["down"])


def _expert_rows_back(w, gu, dy, tokens, ws, product, live=None):
    """`_expert_rows`'s gradient from its gate and up product `gu`: dy's rows
    `tokens` are the outputs' cotangents before the pairs' weights ws [m] ->
    (the weights' gradient f32[m], 0 outside `live` if given; the rows')."""
    with jax.named_scope(obs_scopes.MOE_GMM):
        dys = dy.astype(BF16)
        # dy @ down^T a row; the pair's weight comes in behind it
        dh = product(dys if tokens is None else dys[tokens], w["down"],
                     transpose=True)
    f = gu.shape[-1] // 2
    g, u = gu[:, :f], gu[:, f:]
    s = jax.nn.sigmoid(g)
    act = g * s
    dws = jnp.sum((act * u).astype(BF16).astype(F32) * dh, -1)
    if live is not None:
        dws = jnp.where(live, dws, 0.0)
    dh = dh * ws[:, None]
    dgu = jnp.concatenate([dh * u * (s + act * (1.0 - s)), dh * act],
                          -1).astype(BF16)
    with jax.named_scope(obs_scopes.MOE_GMM):
        return dws, product(dgu, w["gate_up"], transpose=True)


def _pair_block(w, x, order, load, pair_w, k: int, rows: int, i):
    """Block i of the sorted pairs: (their tokens, their places in `pair_w`,
    and the function (the tokens' rows of x, the pairs' weights) -> the
    pairs' weighted outputs f32[rows, D]). Its groups are the parts of the
    experts' runs that fall inside it; rows behind the last held pair read
    0."""
    lo = i * rows
    mine = jax.lax.dynamic_slice(order, (lo,), (rows,))
    sizes = _range_sizes(load, lo, rows)

    def outputs(xs, ws):
        ys = _expert_rows(w, xs, None,
                          functools.partial(grouped_matmul, sizes=sizes))[1]
        with jax.named_scope(obs_scopes.MOE_GMM):
            return ys * ws[:, None]

    return mine // k, mine, outputs


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_blocks(w, x, order, load, pair_w, k: int, rows: int, first: int):
    """`held_experts` a block of `rows` sorted pairs at a time (`order`
    padded to whole blocks), from block `first` on, for as many blocks as
    hold a held pair (a loop whose trip count is data: the blocks behind the
    last held pair are never entered). Its gradient, with respect to x and
    the pairs' weights, walks the same blocks and makes each one's rows
    again. -> y f32[T, D]."""

    def add(i, y):
        tokens, mine, outputs = _pair_block(w, x, order, load, pair_w, k, rows, i)
        return y.at[tokens].add(outputs(x[tokens], pair_w[mine]))

    y0 = jnp.zeros((x.shape[0], w["down"].shape[-1]), F32)
    return jax.lax.fori_loop(first, -(-jnp.sum(load) // rows), add, y0)


def _held_blocks_fwd(w, x, order, load, pair_w, k, rows, first):
    return (_held_blocks(w, x, order, load, pair_w, k, rows, first),
            (w, x, order, load, pair_w))


def _held_blocks_bwd(k, rows, first, res, dy):
    w, x, order, load, pair_w = res

    def back(i, carry):
        dx, dw = carry
        tokens, mine, outputs = _pair_block(w, x, order, load, pair_w, k, rows, i)
        _, vjp = jax.vjp(outputs, x[tokens], pair_w[mine])
        dxs, dws = vjp(dy[tokens])
        return dx.at[tokens].add(dxs), dw.at[mine].add(dws)

    dx, dw = jax.lax.fori_loop(
        first, -(-jnp.sum(load) // rows), back,
        (jnp.zeros_like(x), jnp.zeros_like(pair_w)))
    return None, dx, None, None, dw


_held_blocks.defvjp(_held_blocks_fwd, _held_blocks_bwd)


def _sum_by_token(rows, pos, k: int):
    """out[t] = sum over token t's k pairs of rows[pos[t * k + j]], the last
    row of `rows` reading 0: the un-sort as gathers (a scatter-added row
    costs the chip six times a gathered one). A token's `SUM_LEAD` first
    rows that are not the last are gathered for every token; the few pairs
    of tokens with more, sorted ahead of the rest, are added a block at a
    time, for as many blocks as hold one."""
    dummy = rows.shape[0] - 1
    cols = jnp.sort(pos.reshape(-1, k), axis=1)    # the last row sorts behind
    lead = min(k, SUM_LEAD)
    y = rows[cols[:, :lead]].sum(1)
    if lead == k:
        return y
    late = cols[:, lead:].reshape(-1)
    late = jnp.pad(late, (0, (-late.shape[0]) % SUM_BLOCK),
                   constant_values=dummy)
    order = jnp.argsort(late)
    last = y.shape[0] - 1

    def add(i, y):
        at = jax.lax.dynamic_slice(order, (i * SUM_BLOCK,), (SUM_BLOCK,))
        return y.at[jnp.minimum(at // (k - lead), last)].add(rows[late[at]])

    return jax.lax.fori_loop(
        0, -(-jnp.sum(late < dummy) // SUM_BLOCK), add, y)


def _sum_held(rows, pos, ws=None):
    """out[t] = sum over token t's held pairs of (their weight ws [T, k]
    times, if given) the row of `rows` they sorted to (pos [T, k]; the row
    count, behind every row, for a pair that is not among them): the un-sort
    as one gather a (token, slot). A slot with no row is masked here, behind
    the gather: no row behind the last group, which the grouped product
    never wrote, is read into the sum."""
    live = pos < rows.shape[0]
    got = rows[jnp.where(live, pos, 0)]                      # [T, k, D]
    if ws is not None:
        got = got * ws[..., None]
    return jnp.sum(jnp.where(live[..., None], got, 0.0), 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows(plan: ExpertPlan, k: int, w, x, order, pos, load, pair_w):
    """`held_experts` over the first `plan.front` sorted pairs (`order` as
    it is sorted, unpadded; `pos` its inverse where the plan counted it, else
    None) in one grouped product a matrix. Rows move between token order and
    expert order once each way, as gathers: the tokens' rows by `order`, the
    pairs' outputs back by the place each pair sorted to (`_unsort`). A
    filled front (pair_w [T * k]) gives the rows behind the last held pair,
    pairs of absent experts with weight 0, to the last group, and its
    gradient makes the rows again; one that is not (pair_w [T, k], the
    weights enter behind the un-sort's gather) never writes, masks or reads
    them, and its gradient keeps the gate and up products. The gradient is
    with respect to x and the pairs' weights. -> y f32[T, D]."""
    return _held_rows_fwd(plan, k, w, x, order, pos, load, pair_w)[0]


def _held_rows_fwd(plan, k, w, x, order, pos, load, pair_w):
    front = plan.front
    product = lambda sizes: functools.partial(  # noqa: E731
        _gmm_call, sizes=sizes, transpose=False, rows=plan.tile)
    if not plan.filled:
        tokens = order // k
        gu, ys = _expert_rows(w, x.astype(BF16)[tokens], None, product(load))
        place = jnp.argsort(order).astype(jnp.int32)             # the inverse
        pos = jnp.where(place < jnp.sum(load), place,            # held sort first
                        front).reshape(pair_w.shape)
        return _sum_held(ys, pos, pair_w), (
            w, gu, tokens, pair_w.reshape(-1)[order], load, None, pos)
    mine = order[:front]
    if plan.spare:
        mine = jnp.pad(mine, (0, plan.spare))
    sizes = _range_sizes(load, 0, front)
    n = jnp.sum(sizes)
    if pos is None:
        pos = jnp.argsort(order).astype(jnp.int32)               # the inverse
    tokens, ws = mine // k, pair_w[mine]
    # (the fill before or behind `live`: each form's order as its cell's
    # program has it, so that a cell's program stays the one it measured)
    if plan.spare:
        sizes = sizes.at[-1].add(front - n)
    live = jnp.arange(mine.shape[0]) < n
    if not plan.spare:
        sizes = sizes.at[-1].add(front - n)
    # held sort first; a pair behind them reads the spare tile's last row, or none
    pos = jnp.where(pos < n, pos, mine.shape[0] - bool(plan.spare))
    if not plan.spare:
        pos = pos.reshape(-1, k)
    ys = _expert_rows(w, x, tokens, product(sizes))[1]
    # (rows behind the last held pair read 0; a spare tile is not written)
    ys = jnp.where(live[:, None], ys * ws[:, None], 0.0)
    return _unsort(plan, k, ys, pos), (w, x, tokens, ws, sizes, live, pos)


def _unsort(plan, k, rows, pos):
    return _sum_by_token(rows, pos, k) if plan.spare else _sum_held(rows, pos)


def _held_rows_bwd(plan, k, res, dy):
    w, kept, tokens, ws, sizes, live, pos = res
    product = functools.partial(_gmm_call, sizes=sizes, transpose=False,
                                rows=plan.tile)
    if not plan.filled:
        dws, dxs = _expert_rows_back(w, kept, dy.astype(BF16)[tokens], None,
                                     ws, product)
        live = pos < dws.shape[0]
        return (None, _sum_held(dxs, pos), None, None, None,
                jnp.where(live, dws[jnp.where(live, pos, 0)], 0.0))
    with jax.named_scope(obs_scopes.MOE_GMM):
        gu = product(kept.astype(BF16)[tokens], w["gate_up"])
    dws, dxs = _expert_rows_back(w, gu, dy, tokens, ws, product,
                                 None if plan.spare else live)
    dx = _unsort(plan, k, jnp.where(live[:, None], dxs, 0.0), pos)
    if plan.spare:   # a pair behind the front reads the last row: 0
        return None, dx, None, None, None, jnp.where(live, dws, 0.0)[pos]
    among = pos < plan.front
    return (None, dx, None, None, None,
            jnp.where(among, dws[jnp.where(among, pos, 0)], 0.0).reshape(-1))


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def expert_layer(arch: LMArch, w, router, x, at=None):
    """x: [B, S, D] (already normed) -> (y, load, the selections [T, k]).
    `at`: `held_experts`'s (the other leaves of w are this layer's own)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    idx, weights = route(arch, router, w["bias"], flat)
    y, load = held_experts(arch, w["experts"], flat, idx, weights, at)
    if arch.shared_experts:
        y = y + glu(w["shared"], flat)
    return y.reshape(b, s, d), load, idx
