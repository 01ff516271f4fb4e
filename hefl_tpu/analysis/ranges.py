"""Integer-range abstract interpretation over jaxprs.

The packed-quantized CKKS pipeline rests on arithmetic invariants — the
carry-free headroom `field_bits = b + ceil(log2 C)`, the guard band that
absorbs decrypt noise, the 2**62 exact-integer ceiling of the hi/lo split
encode, the q/2 wall of the centered decode, the uint32 lazy-sum bound of
`psum_mod` — that PR 6/7 enforce with *sampled* runtime tests. A config
outside the tested grid, or a refactor that widens a shift, ships silently.

This module proves those invariants statically, for ALL inputs, by interval
abstract interpretation of the real jaxprs:

  * :class:`Interval` — the abstract domain: one [lo, hi] pair per value,
    exact Python ints for integer dtypes (no 64-bit ceiling in the
    *analysis*, which is how an op that would overflow int64 gets caught
    rather than wrapped), floats with ±inf for float dtypes.
  * :func:`eval_jaxpr_ranges` — the interpreter: propagates intervals
    through add/mul/shift/and/or/select/reduce/convert/psum/... including
    sub-jaxprs (jit, shard_map, custom_{j,v}jp, cond branches), recording
    a :class:`RangeFinding` at the exact eqn whose INTEGER output interval
    escapes the declared ceiling or its dtype — the "offending op".
  * **loop fixpoints** (ISSUE 12) — `lax.scan` / `lax.while_loop` carries
    are no longer conservatively unbounded: the body jaxpr is evaluated
    iteratively over the carried intervals until a post-fixpoint. A scan
    with a small static trip count is iterated exactly (with early exit on
    a stable carry); anything else — long scans, every while — joins
    iterates and, after :data:`WIDEN_DELAY` unstable rounds, WIDENS the
    unstable carries up a threshold ladder (declared ceiling → dtype
    bounds → ±inf), then applies one narrowing pass re-anchored at the
    initial carry. A final AUDITED body pass at the proven invariant
    emits the per-eqn findings, so a carry that can grow past a ceiling
    still cites the offending op inside the loop body. `while` conditions
    of the shape `carry OP bound` additionally refine the carry on entry
    (and, negated, on exit), which is what bounds count-up/count-down
    loop counters. Every loop contributes a :class:`LoopReport` to the
    result — the proof that the analysis reached a sound post-fixpoint
    rather than giving up.
  * :func:`certify_packing` — the headroom proof: traces
    `ckks.quantize.packing_sum_probe` (the shaped jaxpr of the plaintext
    integer math that encode_packed → encrypt → psum_mod /
    OnlineAccumulator fold → decode_int_center implements homomorphically)
    and checks, for one (modulus, bits, k, clients, guard) point:
      - every field's C-client sum stays below 2**field_bits (carry-free),
      - the accumulated decrypt noise stays inside the guard band,
      - the packed client-sum stays below min(q/2, 2**62) at EVERY op.
    The certificate either proves the config safe for all inputs or names
    the overflowing op. `ckks.quantize.max_interleave` cross-checks its
    closed-form k against this proof on every call (loud error on
    divergence), and `PackedSpec.for_params` rejects uncertified configs
    at build time.

The interpreter is deliberately conservative: an unsupported primitive
yields an unbounded interval (sound — it can only cause false alarms
downstream, never a false proof), and the wrapping Montgomery cores
(`ckks.modular`) are NOT range-probed — their uint32 wraparound is
intentional and bitwise-tested; the lint layer (analysis.lint) covers them
with the no-divide/no-float rules instead.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any

import numpy as np

_POS_INF = float("inf")
_NEG_INF = float("-inf")

# Loop-fixpoint knobs (ISSUE 12). A scan with static length <= the unroll
# limit is iterated exactly (tight bounds like C * field_max fall out);
# longer scans and every while_loop go through join-then-widen. WIDEN_DELAY
# is the classic K: how many unstable joined iterations to observe before
# widening a moving bound up the threshold ladder.
SCAN_EXACT_LIMIT = 4096
WIDEN_DELAY = 3
# The declared iteration-count ceiling the while-loop probes certify
# against ("any arrival count / ladder depth up to 2**48"): large enough
# for any real deployment, small enough that a counter increment provably
# stays inside its int64 carrier.
LOOP_COUNT_CEILING = 1 << 48


@dataclasses.dataclass(frozen=True)
class Interval:
    """Closed interval [lo, hi]; ints stay exact Python ints (unbounded)."""

    lo: Any
    hi: Any

    def __post_init__(self):
        if self.lo > self.hi:  # pragma: no cover - internal invariant
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    def union(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def __repr__(self):
        return f"[{_fmt(self.lo)}, {_fmt(self.hi)}]"


TOP = Interval(_NEG_INF, _POS_INF)
BOOL = Interval(0, 1)
_TRUE = Interval(1, 1)
_FALSE = Interval(0, 0)


def _compare(name: str, a: "Interval", b: "Interval"):
    """[1,1] / [0,0] when the comparison is decided by the intervals,
    None when it is not."""
    if name == "lt":
        if a.hi < b.lo:
            return _TRUE
        if a.lo >= b.hi:
            return _FALSE
    elif name == "le":
        if a.hi <= b.lo:
            return _TRUE
        if a.lo > b.hi:
            return _FALSE
    elif name == "gt":
        if a.lo > b.hi:
            return _TRUE
        if a.hi <= b.lo:
            return _FALSE
    elif name == "ge":
        if a.lo >= b.hi:
            return _TRUE
        if a.hi < b.lo:
            return _FALSE
    elif name == "eq":
        if a.hi < b.lo or a.lo > b.hi:
            return _FALSE
        if a.lo == a.hi == b.lo == b.hi:
            return _TRUE
    elif name == "ne":
        if a.hi < b.lo or a.lo > b.hi:
            return _TRUE
        if a.lo == a.hi == b.lo == b.hi:
            return _FALSE
    return None


def _fmt(v) -> str:
    """Log-friendly bound: huge exact ints print as 2**k, not 40 digits."""
    if isinstance(v, int) and abs(v) >= 1 << 40:
        sign = "-" if v < 0 else ""
        a = abs(v)
        if a & (a - 1) == 0:
            return f"{sign}2**{a.bit_length() - 1}"
        if (a + 1) & a == 0:
            return f"{sign}(2**{a.bit_length()}-1)"
        return f"{sign}~2**{a.bit_length() - 1}"
    return str(v)


@dataclasses.dataclass(frozen=True)
class RangeFinding:
    """One op whose statically-derived range violates a declared bound."""

    kind: str        # "ceiling" | "dtype-overflow" | "output-bound"
    op: str          # primitive name — the offending op
    eqn_index: int   # position in the (flattened) eqn walk
    interval: Interval
    bound: Interval
    message: str

    def __str__(self):
        return self.message


@dataclasses.dataclass(frozen=True)
class LoopReport:
    """How one scan/while reached its post-fixpoint (always sound: TOP is
    a post-fixpoint, so the analysis never gives up unsoundly — `widened`
    records that precision, not soundness, was traded)."""

    op: str            # "scan" | "while"
    eqn_index: int     # position in the flattened eqn walk
    mode: str          # "exact" (unrolled static trip count) | "fixpoint"
    length: int | None # static trip count for scans, None for while
    rounds: int        # abstract body iterations evaluated
    widened: bool      # the threshold-ladder widening fired
    narrowed: bool     # the narrowing pass tightened the invariant


@dataclasses.dataclass
class RangeResult:
    out_intervals: list
    findings: list
    notes: list      # non-fatal analysis caveats (unknown primitives, ...)
    loops: list = dataclasses.field(default_factory=list)  # LoopReports


def _contains(outer: Interval, inner: Interval) -> bool:
    return outer.lo <= inner.lo and outer.hi >= inner.hi


def _is_int_dtype(dtype) -> bool:
    try:
        return np.issubdtype(np.dtype(dtype), np.integer)
    except TypeError:   # extended dtypes (PRNG keys) have no numpy analog
        return False


def _dtype_interval(dtype) -> Interval:
    info = np.iinfo(np.dtype(dtype))
    return Interval(int(info.min), int(info.max))


def _mul_bound(a, b):
    if a == 0 or b == 0:
        return 0
    return a * b


def _imul(a: Interval, b: Interval) -> Interval:
    cands = [
        _mul_bound(a.lo, b.lo), _mul_bound(a.lo, b.hi),
        _mul_bound(a.hi, b.lo), _mul_bound(a.hi, b.hi),
    ]
    return Interval(min(cands), max(cands))


def _pow2_shift(x: Interval, s: Interval) -> Interval:
    """x << s as x * 2**s (mathematical, never wrapping)."""
    s_lo = max(int(s.lo), 0) if s.lo != _NEG_INF else 0
    if s.hi == _POS_INF:
        return TOP
    return _imul(x, Interval(1 << s_lo, 1 << int(s.hi)))


def _floordiv_pow2(x: Interval, s: Interval) -> Interval:
    s_lo = max(int(s.lo), 0) if s.lo != _NEG_INF else 0
    s_hi = int(s.hi) if s.hi != _POS_INF else s_lo
    cands = []
    for v in (x.lo, x.hi):
        for sh in (s_lo, s_hi):
            if v in (_NEG_INF, _POS_INF):
                cands.append(v)
            else:
                cands.append(math.floor(v / (1 << sh)))
    return Interval(min(cands), max(cands))


def _bitwise(a: Interval, b: Interval, dtype) -> Interval:
    """and/or/xor bound for non-negative operands; dtype range otherwise."""
    if a.lo >= 0 and b.lo >= 0 and a.hi != _POS_INF and b.hi != _POS_INF:
        bits = max(int(a.hi).bit_length(), int(b.hi).bit_length())
        return Interval(0, (1 << bits) - 1)
    return _dtype_interval(dtype) if _is_int_dtype(dtype) else TOP


def _reduced_size(in_aval, out_aval) -> int:
    n_in = int(np.prod(in_aval.shape)) if in_aval.shape else 1
    n_out = int(np.prod(out_aval.shape)) if out_aval.shape else 1
    return max(n_in // max(n_out, 1), 1)


def _array_interval(x) -> Interval:
    arr = np.asarray(x)
    if arr.size == 0:
        return Interval(0, 0)
    if _is_int_dtype(arr.dtype):
        return Interval(int(arr.min()), int(arr.max()))
    if arr.dtype == np.bool_:
        return Interval(int(arr.min()), int(arr.max()))
    return Interval(float(arr.min()), float(arr.max()))


def _sub_jaxpr(params: dict):
    """The (closed_jaxpr, consts_known) of a call-like eqn, if any."""
    from jax.extend import core as jex_core

    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if sub is None:
            continue
        if isinstance(sub, jex_core.ClosedJaxpr):
            return sub
        if isinstance(sub, jex_core.Jaxpr):  # shard_map carries a bare Jaxpr
            return jex_core.ClosedJaxpr(sub, ())
    return None


class _RangeInterpreter:
    def __init__(self, ceiling: Interval | None, check_dtype: bool,
                 axis_sizes: dict | None):
        self.ceiling = ceiling
        self.check_dtype = check_dtype
        self.axis_sizes = dict(axis_sizes or {})
        self.findings: list[RangeFinding] = []
        self.notes: list[str] = []
        self.loops: list[LoopReport] = []
        self.counter = 0
        self._quiet = 0
        self._note_seen: set[str] = set()

    # -- environment ------------------------------------------------------
    def _read(self, env, v) -> Interval:
        from jax.extend import core as jex_core

        if isinstance(v, jex_core.Literal):
            return _array_interval(v.val)
        return env[v]

    @contextlib.contextmanager
    def _quieted(self):
        """Suppress findings/notes/loop-reports during the exploratory
        fixpoint iterations; the AUDITED pass at the proven invariant is
        the one that reports, so each in-loop violation fires once."""
        self._quiet += 1
        try:
            yield
        finally:
            self._quiet -= 1

    def _note(self, msg: str) -> None:
        if self._quiet or msg in self._note_seen:
            return
        self._note_seen.add(msg)
        self.notes.append(msg)

    def _report_loop(self, rep: "LoopReport") -> None:
        # Quiet-gated like findings/notes: a loop nested inside another
        # loop's exploratory iterations reports once, at the audited pass.
        if not self._quiet:
            self.loops.append(rep)

    # -- one eqn ----------------------------------------------------------
    def _check(self, eqn, out: Interval, aval) -> None:
        if self._quiet:
            return
        if not _is_int_dtype(getattr(aval, "dtype", np.float32)):
            return
        name = eqn.primitive.name
        finding = None
        if self.ceiling is not None and (
            out.lo < self.ceiling.lo or out.hi > self.ceiling.hi
        ):
            finding = RangeFinding(
                kind="ceiling", op=name, eqn_index=self.counter,
                interval=out, bound=self.ceiling,
                message=(
                    f"`{name}` (eqn {self.counter}) produces values in "
                    f"{out}, outside the declared exact-integer ceiling "
                    f"{self.ceiling}"
                ),
            )
        elif self.check_dtype:
            drange = _dtype_interval(aval.dtype)
            if out.lo < drange.lo or out.hi > drange.hi:
                finding = RangeFinding(
                    kind="dtype-overflow", op=name, eqn_index=self.counter,
                    interval=out, bound=drange,
                    message=(
                        f"`{name}` (eqn {self.counter}) produces values in "
                        f"{out}, wrapping its {np.dtype(aval.dtype).name} "
                        f"carrier {drange}"
                    ),
                )
        # Multi-output eqns (scan carries + ys) can derive the identical
        # finding per outvar; report it once.
        if finding is not None and finding not in self.findings[-4:]:
            self.findings.append(finding)

    def _eval_eqn(self, eqn, ins: list[Interval]) -> list[Interval]:
        name = eqn.primitive.name
        out_aval = eqn.outvars[0].aval
        a = ins[0] if ins else TOP
        b = ins[1] if len(ins) > 1 else None

        if name in ("add", "add_any"):
            return [Interval(a.lo + b.lo, a.hi + b.hi)]
        if name == "sub":
            return [Interval(a.lo - b.hi, a.hi - b.lo)]
        if name == "mul":
            return [_imul(a, b)]
        if name == "neg":
            return [Interval(-a.hi, -a.lo)]
        if name == "abs":
            lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
            return [Interval(lo, max(abs(a.lo), abs(a.hi)))]
        if name == "max":
            return [Interval(max(a.lo, b.lo), max(a.hi, b.hi))]
        if name == "min":
            return [Interval(min(a.lo, b.lo), min(a.hi, b.hi))]
        if name == "div":
            if b.lo <= 0 <= b.hi:
                return [TOP]
            cands = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
            return [Interval(min(cands), max(cands))]
        if name == "rem":
            if a.lo >= 0 and b.lo > 0 and b.hi != _POS_INF:
                # Non-negative dividend, positive divisor: the canonical
                # residue case the fold/ladder probes rely on. rem < b and
                # rem <= a, so the invariant [0, p-1] is closed.
                hi = b.hi - 1
                if a.hi != _POS_INF:
                    hi = min(hi, a.hi)
                return [Interval(0, hi)]
            # sign conventions differ across rem flavors; conservative.
            if b.lo in (_NEG_INF,) or b.hi in (_POS_INF,):
                return [TOP]
            m = max(abs(b.lo), abs(b.hi))
            return [Interval(-m, m)]
        if name == "integer_pow":
            p = int(eqn.params.get("y", 2))
            cands = [x**p for x in (a.lo, a.hi) if x not in (_NEG_INF, _POS_INF)]
            if not cands:
                return [TOP]
            if p % 2 == 0 and a.lo <= 0 <= a.hi:
                cands.append(0)
            return [Interval(min(cands), max(cands))]
        if name in ("floor", "ceil", "round", "round_nearest_even",
                    "nextafter"):
            lo = a.lo if a.lo in (_NEG_INF,) else math.floor(a.lo)
            hi = a.hi if a.hi in (_POS_INF,) else math.ceil(a.hi)
            return [Interval(lo, hi)]
        if name == "sign":
            return [Interval(-1, 1)]
        if name == "clamp":
            lo_b, x, hi_b = ins
            return [Interval(
                max(lo_b.lo, min(x.lo, hi_b.lo)),
                max(lo_b.hi, min(x.hi, hi_b.hi)),
            )]
        if name == "shift_left":
            return [_pow2_shift(a, b)]
        if name == "shift_right_arithmetic":
            return [_floordiv_pow2(a, b)]
        if name == "shift_right_logical":
            if a.lo >= 0:
                return [_floordiv_pow2(a, b)]
            return [_dtype_interval(out_aval.dtype)]
        if name == "and":
            # x & y <= min(x, y) for non-negative operands — one bounded
            # non-negative side caps the result even when the other is
            # unbounded (the mod-2**32 counter-wrap mask idiom).
            caps = [x.hi for x in (a, b) if x.lo >= 0 and x.hi != _POS_INF]
            if caps:
                return [Interval(0, min(caps))]
            return [_bitwise(a, b, out_aval.dtype)]
        if name in ("or", "xor"):
            return [_bitwise(a, b, out_aval.dtype)]
        if name == "not":
            return [_dtype_interval(out_aval.dtype)
                    if _is_int_dtype(out_aval.dtype) else BOOL]
        if name == "select_n":
            pred, cases = ins[0], ins[1:]
            # Dead-branch elimination: a predicate the comparison handlers
            # proved constant selects exactly one case — this is what
            # keeps `jnp.remainder`'s sign-correction branch (provably
            # dead for canonical operands) from poisoning the bound.
            if (pred.lo == pred.hi and isinstance(pred.lo, int)
                    and 0 <= pred.lo < len(cases)):
                return [cases[pred.lo]]
            out = cases[0]
            for case in cases[1:]:
                out = out.union(case)
            return [out]
        if name == "convert_element_type":
            if np.dtype(out_aval.dtype) == np.bool_:
                return [BOOL]
            if _is_int_dtype(out_aval.dtype) and not isinstance(a.lo, int):
                lo = a.lo if a.lo == _NEG_INF else math.floor(a.lo)
                hi = a.hi if a.hi == _POS_INF else math.ceil(a.hi)
                return [Interval(lo, hi)]
            return [a]
        if name == "reduce_sum":
            n = _reduced_size(eqn.invars[0].aval, out_aval)
            return [Interval(_mul_bound(n, a.lo), _mul_bound(n, a.hi))]
        if name in ("reduce_max", "reduce_min", "reduce_and", "reduce_or",
                    "argmax", "argmin", "cumsum", "cumlogsumexp"):
            if name == "cumsum":
                n = int(np.prod(eqn.invars[0].aval.shape) or 1)
                return [Interval(_mul_bound(n, min(a.lo, 0)),
                                 _mul_bound(n, max(a.hi, 0)))]
            if name in ("argmax", "argmin"):
                return [Interval(0, max(int(np.prod(eqn.invars[0].aval.shape)) - 1, 0))]
            return [a]
        if name == "psum":
            total = 1
            for ax in eqn.params.get("axes", ()):
                size = self.axis_sizes.get(ax)
                if size is None:
                    # Unknown participant count: a prover must not default
                    # to the identity (a silent under-approximation) —
                    # unbounded is the sound answer, and the note tells
                    # the caller which axis to declare.
                    self._note(
                        f"psum over axis {ax!r} with undeclared size: "
                        "outputs unbounded (pass axis_sizes)"
                    )
                    return [TOP for _ in ins]
                total *= int(size)
            return [Interval(_mul_bound(total, iv.lo), _mul_bound(total, iv.hi))
                    for iv in ins]
        if name in ("pmax", "pmin", "all_gather", "ppermute"):
            return [iv for iv in ins]
        if name in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                    "slice", "rev", "expand_dims", "copy", "stop_gradient",
                    "reduce_precision", "device_put", "sharding_constraint",
                    "dynamic_slice", "gather", "pad", "sort"):
            if name == "pad":
                return [a.union(ins[1])]
            if name == "dynamic_slice":
                return [a]
            return [a]
        if name == "concatenate":
            out = ins[0]
            for iv in ins[1:]:
                out = out.union(iv)
            return [out]
        if name == "iota":
            dim = int(eqn.params["shape"][eqn.params["dimension"]])
            return [Interval(0, max(dim - 1, 0))]
        if name in ("eq", "ne", "lt", "le", "gt", "ge"):
            # Definite results when the intervals prove them: the
            # comparison feeds select_n's dead-branch elimination and the
            # while-loop zero-iteration check.
            verdict = _compare(name, a, b)
            return [verdict if verdict is not None else BOOL]
        if name == "is_finite":
            return [BOOL]
        if name == "scan":
            return self._eval_scan(eqn, ins)
        if name == "while":
            return self._eval_while(eqn, ins)
        if name == "cond":
            branches = eqn.params.get("branches", ())
            outs = None
            for br in branches:
                # Either branch may execute: evaluate both (audited — a
                # violation on one branch is a violation) and union.
                res = self._eval_jaxpr(br, ins[1:])
                outs = res if outs is None else [
                    o.union(r) for o, r in zip(res, outs)
                ]
            if outs is not None and len(outs) == len(eqn.outvars):
                return outs
            return [TOP for _ in eqn.outvars]
        if name in ("jit", "closed_call", "custom_jvp_call",
                    "custom_vjp_call", "remat2", "shard_map", "core_call"):
            sub = _sub_jaxpr(eqn.params)
            if sub is not None:
                if name == "shard_map":
                    mesh = eqn.params.get("mesh")
                    if mesh is not None:
                        try:
                            for ax, size in dict(mesh.shape).items():
                                # setdefault: a caller-declared WORST-CASE
                                # axis size (prove 32 participants on a
                                # 1-device trace mesh) must win over the
                                # traced mesh's.
                                self.axis_sizes.setdefault(ax, int(size))
                        except Exception:  # abstract mesh without .shape
                            pass
                return self._eval_jaxpr(sub, ins)
            self._note(f"opaque call `{name}`: outputs unbounded")
            return [TOP for _ in eqn.outvars]

        self._note(f"unsupported primitive `{name}`: output unbounded")
        return [TOP for _ in eqn.outvars]

    # -- loop fixpoints (ISSUE 12) ----------------------------------------

    def _widen(self, joined: Interval, prev: Interval, aval) -> Interval:
        """Escalate whichever bound is still moving up the threshold
        ladder: declared ceiling -> dtype bounds -> ±inf. Each unstable
        round strictly climbs the finite ladder, so the fixpoint loop
        terminates; a carry pushed past its dtype threshold is exactly the
        loop-overflow the audited pass then reports."""
        los: list = []
        his: list = []
        if self.ceiling is not None:
            los.append(self.ceiling.lo)
            his.append(self.ceiling.hi)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            try:
                if _is_int_dtype(dtype):
                    d = _dtype_interval(dtype)
                    los.append(d.lo)
                    his.append(d.hi)
            except TypeError:
                pass
        lo, hi = joined.lo, joined.hi
        if joined.lo < prev.lo:
            cands = [t for t in los if t <= joined.lo]
            lo = max(cands) if cands else _NEG_INF
        if joined.hi > prev.hi:
            cands = [t for t in his if t >= joined.hi]
            hi = min(cands) if cands else _POS_INF
        return Interval(lo, hi)

    def _loop_fixpoint(self, body, init, avals, refine):
        """Join-iterate `body` over the carried intervals to a
        post-fixpoint (body(carry) ⊆ carry), widening after WIDEN_DELAY
        unstable rounds, then apply one narrowing pass re-anchored at the
        initial carry. -> (invariant, rounds, widened, narrowed)."""
        carry = list(init)
        widened = narrowed = False
        rounds = 0
        max_rounds = WIDEN_DELAY + 8

        def step(c):
            entry = refine(c) if refine is not None else c
            if entry is None:       # refinement contradicts: body dead
                return None
            with self._quieted():
                return body(entry)[:len(init)]

        while True:
            out = step(carry)
            rounds += 1
            if out is None or all(
                _contains(c, o) for c, o in zip(carry, out)
            ):
                break               # post-fixpoint reached
            joined = [c.union(o) for c, o in zip(carry, out)]
            if rounds >= WIDEN_DELAY:
                joined = [
                    j if _contains(c, j) else self._widen(j, c, a)
                    for j, c, a in zip(joined, carry, avals)
                ]
                widened = True
            carry = joined
            if rounds >= max_rounds:  # pragma: no cover - ladder backstop
                carry = [TOP for _ in init]
                widened = True
                break
        # One narrowing pass: re-anchor at the initial carry. Accept only
        # if the tightened candidate is itself still a post-fixpoint.
        out = step(carry)
        if out is not None:
            cand = [i.union(o) for i, o in zip(init, out)]
            if any(
                n.lo > c.lo or n.hi < c.hi for n, c in zip(cand, carry)
            ) and all(_contains(c, n) for c, n in zip(carry, cand)):
                out2 = step(cand)
                if out2 is not None and all(
                    _contains(n, i.union(o))
                    for n, i, o in zip(cand, init, out2)
                ):
                    carry = cand
                    narrowed = True
        return carry, rounds, widened, narrowed

    def _eval_scan(self, eqn, ins):
        params = eqn.params
        sub = params["jaxpr"]
        nc = int(params.get("num_consts", 0))
        ncar = int(params.get("num_carry", 0))
        length = params.get("length")
        consts = list(ins[:nc])
        init = list(ins[nc:nc + ncar])
        xs = list(ins[nc + ncar:])   # per-iteration slice == stacked range
        n_ys = len(eqn.outvars) - ncar
        avals = [v.aval for v in eqn.outvars[:ncar]]

        def body(c):
            return self._eval_jaxpr(sub, consts + list(c) + xs)

        if length is not None and int(length) == 0:
            # A zero-trip scan never runs its body: the carry is exactly
            # the init and the stacked outputs are empty (any interval is
            # vacuously sound for zero elements) — no audit, no findings.
            with self._quieted():
                outs = body(list(init))
            self._report_loop(LoopReport(
                op="scan", eqn_index=self.counter, mode="exact", length=0,
                rounds=0, widened=False, narrowed=False,
            ))
            return list(init) + list(outs[ncar:])

        widened = narrowed = False
        rounds = 0
        ys: list = [None] * n_ys
        if length is not None and 0 < int(length) <= SCAN_EXACT_LIMIT:
            mode = "exact"
            carry = list(init)
            # Join of carry ENTRY values only (never the final carry-out):
            # auditing the body at this join covers every iteration that
            # actually runs without charging a phantom extra step — a
            # boundary-exact headroom config must not be rejected for an
            # iteration C+1 that does not exist.
            entry_join: list | None = None
            for _ in range(int(length)):
                entry_join = (list(carry) if entry_join is None else
                              [e.union(c) for e, c in zip(entry_join, carry)])
                with self._quieted():
                    outs = body(carry)
                new = outs[:ncar]
                for i, y in enumerate(outs[ncar:]):
                    ys[i] = y if ys[i] is None else ys[i].union(y)
                rounds += 1
                stable = all(
                    n.lo == c.lo and n.hi == c.hi
                    for n, c in zip(new, carry)
                )
                carry = new
                if stable:
                    break           # deterministic: later iterates equal
            invariant = entry_join if entry_join is not None else list(init)
        else:
            mode = "fixpoint"
            invariant, rounds, widened, narrowed = self._loop_fixpoint(
                body, init, avals, None
            )
            carry = invariant
        # AUDITED pass at the loop invariant: per-eqn checks fire here, so
        # a carry that escapes a ceiling cites the in-body offending op.
        audited = body(invariant)
        if mode == "fixpoint" or any(y is None for y in ys):
            ys = list(audited[ncar:])
        self._report_loop(LoopReport(
            op="scan", eqn_index=self.counter, mode=mode,
            length=int(length) if length is not None else None,
            rounds=rounds, widened=widened, narrowed=narrowed,
        ))
        return list(carry) + list(ys)

    def _cond_refiners(self, cond_closed, cond_const_ivs, carry_avals):
        """Entry/exit carry refiners from a while condition of the shape
        `carry[i] OP bound` (bound = literal, cond const, or jaxpr const).
        Returns (entry, exit) callables (or Nones when the pattern does
        not match — sound, just less precise): entry refines the carry
        seen by the body (cond true), exit the carry the loop returns
        (cond false, negated relation)."""
        from jax.extend import core as jex_core

        jaxpr = cond_closed.jaxpr
        if len(jaxpr.outvars) != 1:
            return None, None
        outv = jaxpr.outvars[0]
        if isinstance(outv, jex_core.Literal):
            return None, None
        def_eqn = None
        for e in jaxpr.eqns:
            if outv in e.outvars:
                def_eqn = e
        flips = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}
        if def_eqn is None or def_eqn.primitive.name not in flips:
            return None, None
        consts_env = {
            v: _array_interval(c)
            for v, c in zip(jaxpr.constvars, cond_closed.consts)
        }
        invars = list(jaxpr.invars)
        ncc = len(cond_const_ivs)

        def classify(v):
            if isinstance(v, jex_core.Literal):
                return "iv", _array_interval(v.val)
            if v in consts_env:
                return "iv", consts_env[v]
            if v in invars:
                idx = invars.index(v)
                if idx < ncc:
                    return "iv", cond_const_ivs[idx]
                return "carry", idx - ncc
            return None, None

        a_kind, a_val = classify(def_eqn.invars[0])
        b_kind, b_val = classify(def_eqn.invars[1])
        rel = def_eqn.primitive.name
        if a_kind == "carry" and b_kind == "iv":
            ci, bound = a_val, b_val
        elif b_kind == "carry" and a_kind == "iv":
            ci, bound = b_val, a_val
            rel = flips[rel]
        else:
            return None, None
        dtype = getattr(getattr(def_eqn.invars[0], "aval", None),
                        "dtype", None)
        step = 1 if (dtype is not None and _is_int_dtype(dtype)) else 0

        def make(r):
            def refine(carry):
                c = carry[ci]
                lo, hi = c.lo, c.hi
                if r == "lt":
                    hi = min(hi, bound.hi - step)
                elif r == "le":
                    hi = min(hi, bound.hi)
                elif r == "gt":
                    lo = max(lo, bound.lo + step)
                elif r == "ge":
                    lo = max(lo, bound.lo)
                if lo > hi:
                    return None      # contradiction: branch unreachable
                new = list(carry)
                new[ci] = Interval(lo, hi)
                return new

            return refine

        negations = {"lt": "ge", "le": "gt", "gt": "le", "ge": "lt"}
        return make(rel), make(negations[rel])

    def _eval_while(self, eqn, ins):
        params = eqn.params
        cond_closed = params["cond_jaxpr"]
        body_closed = params["body_jaxpr"]
        cn = int(params.get("cond_nconsts", 0))
        bn = int(params.get("body_nconsts", 0))
        cond_consts = list(ins[:cn])
        body_consts = list(ins[cn:cn + bn])
        init = list(ins[cn + bn:])
        avals = [v.aval for v in eqn.outvars]

        entry_refine, exit_refine = self._cond_refiners(
            cond_closed, cond_consts, avals
        )

        def body(c):
            return self._eval_jaxpr(body_closed, body_consts + list(c))

        invariant, rounds, widened, narrowed = self._loop_fixpoint(
            body, init, avals, entry_refine
        )
        # AUDITED pass at the invariant (skipped when the entry
        # refinement proves the body unreachable).
        entry = (entry_refine(invariant) if entry_refine is not None
                 else invariant)
        if entry is not None:
            body(entry)
        self._report_loop(LoopReport(
            op="while", eqn_index=self.counter, mode="fixpoint",
            length=None, rounds=rounds, widened=widened, narrowed=narrowed,
        ))
        # Loop output: the invariant under the NEGATED condition — plus
        # the initial carry whenever the condition may be false on entry
        # (the loop can run zero times).
        out = (exit_refine(invariant) if exit_refine is not None
               else list(invariant))
        if out is None:
            out = list(invariant)
        with self._quieted():
            cond0 = self._eval_jaxpr(cond_closed, cond_consts + init)
        may_skip = not cond0 or cond0[0].lo <= 0
        if may_skip:
            out = [o.union(i) for o, i in zip(out, init)]
        return out

    # -- a whole (closed) jaxpr -------------------------------------------
    def _eval_jaxpr(self, closed, in_intervals: list[Interval]):
        jaxpr = closed.jaxpr
        env: dict = {}
        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = _array_interval(c)
        n_in = len(jaxpr.invars)
        ins = list(in_intervals[:n_in])
        # call-like eqns may pass consts as leading args; pad conservatively
        while len(ins) < n_in:
            ins.append(TOP)
        for v, iv in zip(jaxpr.invars, ins):
            env[v] = iv
        for eqn in jaxpr.eqns:
            eins = [self._read(env, v) for v in eqn.invars]
            try:
                outs = self._eval_eqn(eqn, eins)
            except Exception as e:  # a handler hole must not kill analysis
                self._note(
                    f"`{eqn.primitive.name}`: interval evaluation failed "
                    f"({type(e).__name__}: {e}); output unbounded"
                )
                outs = [TOP for _ in eqn.outvars]
            if len(outs) != len(eqn.outvars):
                outs = [TOP for _ in eqn.outvars]
            for v, out in zip(eqn.outvars, outs):
                self._check(eqn, out, v.aval)
                env[v] = out
            self.counter += 1
        return [self._read(env, v) for v in jaxpr.outvars]


def eval_jaxpr_ranges(
    closed_jaxpr,
    in_intervals: list[Interval],
    *,
    ceiling: Interval | None = None,
    check_dtype: bool = True,
    axis_sizes: dict | None = None,
) -> RangeResult:
    """Propagate intervals through `closed_jaxpr` (recursing into jit /
    shard_map / custom-vjp sub-jaxprs).

    `ceiling` declares the exact-integer carrier bound every integer-dtype
    op must respect (e.g. the packed pipeline's min(q/2, 2**62)); without
    it, integer ops are checked against their own dtype range
    (`check_dtype`). Violations are recorded as findings citing the eqn —
    analysis continues with the mathematical interval so the FIRST
    offending op is the root cause, not a cascade.
    """
    interp = _RangeInterpreter(ceiling, check_dtype, axis_sizes)
    outs = interp._eval_jaxpr(closed_jaxpr, in_intervals)
    return RangeResult(outs, interp.findings, interp.notes, interp.loops)


# ---------------------------------------------------------------------------
# Packing-headroom certification (the ISSUE-8 tentpole proof).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackingCertificate:
    """Static proof (or refutation) of one packed-aggregation geometry."""

    ok: bool
    modulus_bits: int   # bit length of q
    bits: int           # quantizer width b
    k: int              # interleave factor
    fbits: int          # field width b + ceil(log2 C)
    guard: int          # effective guard guard_bits + ceil(log2 C)
    clients: int
    ceiling_bits: int   # log2 of the binding wall: min(q/2, 2**62)
    findings: tuple     # RangeFinding tuple, empty when ok
    checks: tuple       # human-readable proven facts

    def summary(self) -> str:
        head = (
            f"packing b={self.bits} k={self.k} C={self.clients} "
            f"(field {self.fbits}b, guard {self.guard}b, "
            f"wall 2**{self.ceiling_bits})"
        )
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(
            str(f) for f in self.findings
        )


@functools.lru_cache(maxsize=256)
def certify_packing(
    modulus: int, bits: int, k: int, clients: int, guard_bits: int
) -> PackingCertificate:
    """Prove (or refute) the carry-free headroom of one packing geometry
    by interval analysis of the real integer-pipeline jaxpr.

    Traces `ckks.quantize.packing_sum_probe` — the plaintext integer math
    the homomorphic path (encode_packed → encrypt → psum_mod /
    OnlineAccumulator fold → decode_int_center) computes under encryption —
    and checks every op's range against the exact-integer ceiling
    min(q/2, 2**62) plus the probe's declared output bounds:

      field_sums ≤ 2**fbits - 1          (the C-client sum never carries)
      |noise_sum| < 2**(guard_eff - 1)   (decrypt noise stays in the guard)
      packed total < min(q/2, 2**62)     (centered decode + int64 exactness)

    A failed check names the offending op. Cached: PackedSpec.for_params
    and max_interleave certify on every build.
    """
    import jax

    from hefl_tpu.ckks import quantize

    fbits = quantize.field_bits(bits, clients)
    guard_eff = guard_bits + max(int(clients) - 1, 0).bit_length()
    ceiling_val = min(modulus // 2, 1 << quantize.MAX_PACKED_BITS)
    ceiling = Interval(-(ceiling_val - 1), ceiling_val - 1)

    probe, args = quantize.packing_sum_probe(bits, k, fbits, guard_eff, clients)
    # x64 only for TRACING: the probe's avals must be able to NAME an
    # int64 carrier; the analysis itself computes in unbounded ints.
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(probe)(*args)

    qm = quantize.qmax(bits)
    noise_per_client = (1 << max(guard_bits - 1, 0)) - 1
    in_ivs = [
        TOP,                                         # raw float updates
        Interval(-noise_per_client, noise_per_client),  # per-client noise
    ]
    res = eval_jaxpr_ranges(closed, in_ivs, ceiling=ceiling)
    findings = list(res.findings)
    checks: list[str] = []

    def out_check(idx: int, bound: Interval, what: str):
        iv = res.out_intervals[idx]
        if iv.lo < bound.lo or iv.hi > bound.hi:
            # Name the op that PRODUCES this output.
            outvar = closed.jaxpr.outvars[idx]
            op = "input"
            for eqn in closed.jaxpr.eqns:
                if outvar in eqn.outvars:
                    op = eqn.primitive.name
            findings.append(RangeFinding(
                kind="output-bound", op=op, eqn_index=-1,
                interval=iv, bound=bound,
                message=f"{what}: `{op}` yields {iv}, outside {bound}",
            ))
        else:
            checks.append(f"{what} in {iv} ⊆ {bound}")

    # probe outputs: (field_sums, noise_sum, packed_total)
    out_check(0, Interval(0, (1 << fbits) - 1),
              f"per-field {clients}-client sum (carry-free)")
    half_guard = 1 << max(guard_eff - 1, 0)
    out_check(1, Interval(-(half_guard - 1), half_guard - 1),
              "accumulated decrypt noise (guard band)")
    out_check(2, ceiling, "packed client-sum (q/2 & 2**62 wall)")

    return PackingCertificate(
        ok=not findings,
        modulus_bits=modulus.bit_length(),
        bits=bits, k=k, fbits=fbits, guard=guard_eff, clients=int(clients),
        ceiling_bits=ceiling_val.bit_length() - 1,
        findings=tuple(findings),
        checks=tuple(checks),
    )


@dataclasses.dataclass(frozen=True)
class AggregationCertificate:
    """Static no-wrap proof of the aggregation hot path at one prime size."""

    ok: bool
    prime_bits: int
    chunk: int          # lazy-sum participants proven per reduction
    findings: tuple
    checks: tuple

    def summary(self) -> str:
        head = f"aggregation p<2**{self.prime_bits} chunk={self.chunk}"
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(str(f) for f in self.findings)


@functools.lru_cache(maxsize=32)
def certify_aggregation(prime: int) -> AggregationCertificate:
    """Prove the three aggregation folds never wrap their carriers for a
    given RNS prime size, over ALL inputs:

      1. `fl.secure._lazy_sum_mod`'s uint32 chunk accumulation of
         MAX_PSUM_CLIENTS canonical residues (< p each);
      2. `parallel.collectives.psum_mod`'s fused lazy all-reduce at
         MAX_PSUM_CLIENTS participants per mesh axis (analyzed at the
         declared worst-case axis size, whatever mesh traced it) — on the
         1-D client mesh AND on the 2-D ("clients", "ct") mesh
         (ISSUE 15), with worst-case sizes injected on BOTH axes over the
         trace mesh, so the cohort-bucketed 2-D round's psum bound is
         proven rather than sampled (the ct axis partitions rows and is
         never reduced over; analyzing it at the worst case proves the
         bound is shard-count-independent);
      3. `fl.stream.OnlineAccumulator`'s int64 online fold — proven
         INDUCTIVELY for any arrival count (`certify_fold_inductive`),
         not at one traced fold.

    These are the invariants the MAX_PSUM_CLIENTS constant encodes; a
    prime-size bump that silently breaks them fails here, statically.
    """
    import jax

    from hefl_tpu.fl import secure
    from hefl_tpu.parallel import collectives
    from hefl_tpu.parallel.collectives import MAX_PSUM_CLIENTS

    prime = int(prime)
    canonical = Interval(0, prime - 1)
    findings: list[RangeFinding] = []
    checks: list[str] = []

    def run(name, closed, in_ivs, axis_sizes=None):
        res = eval_jaxpr_ranges(closed, in_ivs, axis_sizes=axis_sizes)
        if res.findings:
            for f in res.findings:
                findings.append(dataclasses.replace(
                    f, message=f"{name}: {f.message}"
                ))
        else:
            checks.append(
                f"{name} stays in {res.out_intervals[0]}"
            )

    # 1. lazy chunk sum (uint32, no reduction until the chunk boundary)
    fn, args = secure.lazy_sum_chunk_probe(MAX_PSUM_CLIENTS)
    run("lazy_sum_mod chunk", jax.make_jaxpr(fn)(*args), [canonical])

    # 2. psum_mod's lazy accumulation at the worst-case participant count
    fn, args = collectives.psum_range_probe(prime)
    run(
        f"psum_mod[{MAX_PSUM_CLIENTS} participants]",
        jax.make_jaxpr(fn)(*args),
        [canonical],
        axis_sizes={"clients": MAX_PSUM_CLIENTS},
    )

    # 2b. the same collective on the 2-D ("clients", "ct") mesh
    # (ISSUE 15): worst-case sizes injected on BOTH axes over the trace
    # mesh — proves the cohort-bucketed round's psum bound holds at any
    # ct shard count (the ct axis only partitions rows).
    fn, args = collectives.psum_range_probe_2d(prime)
    run(
        f"psum_mod 2-D[{MAX_PSUM_CLIENTS} clients x "
        f"{MAX_PSUM_CLIENTS} ct]",
        jax.make_jaxpr(fn)(*args),
        [canonical],
        axis_sizes={
            "clients": MAX_PSUM_CLIENTS, "ct": MAX_PSUM_CLIENTS,
        },
    )

    # 3. the streaming engine's int64 online fold: the inductive loop
    # certificate (any arrival count), replacing the old one-fold trace.
    fold = certify_fold_inductive(prime)
    findings.extend(fold.findings)
    checks.extend(fold.checks)

    return AggregationCertificate(
        ok=not findings,
        prime_bits=prime.bit_length(),
        chunk=MAX_PSUM_CLIENTS,
        findings=tuple(findings),
        checks=tuple(checks),
    )


@dataclasses.dataclass(frozen=True)
class FoldCertificate:
    """Inductive proof of the streaming fold invariant (ISSUE 12).

    accumulator-in-[0, p-1] ∧ one fold step ⇒ accumulator-in-[0, p-1],
    established as a while-loop post-fixpoint over an ABSTRACT arrival
    count — valid for any number of arrivals up to 2**48, not the fixed
    C a traced test exercises. With a PackedSpec, the headroom-capped
    packed C-client sum is re-derived through the same loop machinery
    (`certify_packing`'s scan fold)."""

    ok: bool
    prime_bits: int
    count_ceiling_bits: int
    bits: int | None     # packed leg (None when certifying unpacked)
    k: int | None
    clients: int | None
    findings: tuple
    checks: tuple

    def summary(self) -> str:
        head = (
            f"fold-inductive p<2**{self.prime_bits} "
            f"arrivals<=2**{self.count_ceiling_bits}"
        )
        if self.bits is not None:
            head += f" packed(b={self.bits} k={self.k} C={self.clients})"
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(str(f) for f in self.findings)


@functools.lru_cache(maxsize=64)
def certify_fold_inductive(
    prime: int, spec=None, modulus: int | None = None
) -> FoldCertificate:
    """Prove the `OnlineAccumulator` invariant inductively for UNBOUNDED
    arrival counts (ISSUE 12).

    Traces `fl.stream.fold_loop_probe` — the online fold as a
    `lax.while_loop` over an abstract arrival count in [0, 2**48] — and
    establishes, as a loop post-fixpoint:

      * the carried accumulator stays canonical ([0, p-1]) after EVERY
        fold, for any arrival count (the base case is the canonical
        first upload; the step is the body jaxpr, so this is a machine-
        checked induction, replacing the fixed-C fold trace);
      * the fold's int64 carrier never wraps (acc + row < 2p fits).

    With `spec` (a hashable `PackedSpec`) and `modulus`, the packed
    integer half rides along: the headroom-capped C-client packed sum is
    re-derived through `certify_packing`'s scan-fold machinery at the
    spec's exact geometry — so the streaming engine's fold cap
    (`stream.headroom_blocked`) is backed by the same loop proof.
    """
    import jax

    from hefl_tpu.fl import stream

    prime = int(prime)
    canonical = Interval(0, prime - 1)
    probe, args = stream.fold_loop_probe(prime)
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(probe)(*args)

    res = eval_jaxpr_ranges(
        closed,
        [Interval(0, LOOP_COUNT_CEILING), canonical, canonical],
    )
    findings = list(res.findings)
    checks: list[str] = []
    out = res.out_intervals[0]
    loops = [rep for rep in res.loops if rep.op == "while"]
    if not loops:  # pragma: no cover - probe/interpreter drift tripwire
        findings.append(RangeFinding(
            kind="output-bound", op="while", eqn_index=-1,
            interval=out, bound=canonical,
            message="fold probe traced without a while loop — the "
                    "inductive machinery was not exercised",
        ))
    if out.lo < canonical.lo or out.hi > canonical.hi:
        findings.append(RangeFinding(
            kind="output-bound", op="while", eqn_index=-1,
            interval=out, bound=canonical,
            message=(
                f"OnlineAccumulator fold: carried sum reaches {out}, "
                f"escaping the canonical residue range {canonical}"
            ),
        ))
    else:
        checks.append(
            f"OnlineAccumulator fold invariant {out} ⊆ {canonical} closed "
            f"under any arrival count <= 2**{LOOP_COUNT_CEILING.bit_length() - 1}"
            " (inductive)"
        )

    bits = k = clients = None
    if spec is not None:
        if modulus is None:
            raise ValueError(
                "certify_fold_inductive: a PackedSpec needs the ring "
                "modulus to re-derive the packed C-client sum"
            )
        bits, k, clients = int(spec.bits), int(spec.k), int(spec.clients)
        raw_guard = spec.guard - max(clients - 1, 0).bit_length()
        packed = certify_packing(int(modulus), bits, k, clients, raw_guard)
        for f in packed.findings:
            findings.append(dataclasses.replace(
                f, message=f"packed fold: {f.message}"
            ))
        if packed.ok:
            checks.append(
                f"headroom-capped packed fold (C={clients} scan): "
                + "; ".join(packed.checks)
            )

    return FoldCertificate(
        ok=not findings,
        prime_bits=prime.bit_length(),
        count_ceiling_bits=LOOP_COUNT_CEILING.bit_length() - 1,
        bits=bits, k=k, clients=clients,
        findings=tuple(findings),
        checks=tuple(checks),
    )


@functools.lru_cache(maxsize=64)
def certify_fold_tree(prime: int) -> FoldCertificate:
    """Certify the TWO-TIER fold tree (ISSUE 16: hierarchical multi-host
    aggregation) on top of the inductive single-loop proof.

    The hierarchical aggregator (fl.hierarchy) runs the SAME certified
    fold loop twice: once per host over its local block (the tier fold),
    then once at the root over the shipped per-host partials. The tree
    introduces no new arithmetic, so the certificate is the inductive one
    plus two derived facts it makes checkable:

      * tier partials are canonical — the loop post-fixpoint proves every
        tier accumulator ends in [0, p-1], which is exactly the canonical-
        residue precondition the root fold's base/step cases assume, so
        the root loop is ANOTHER instance of the certified loop, not a new
        region;
      * tree == flat bitwise — every fold is an exact canonical addition
        mod p (int64 carrier, proven wrap-free), and modular addition is
        associative and commutative, so any bracketing of the same upload
        multiset — flat, per-host-then-root, any arrival order — yields
        the same canonical residues bit for bit. This is the identity the
        BENCH_DCN / chaos flat-vs-hierarchical hash gates then measure;
      * carried partials stay certified (ISSUE 17) — a sealed tier
        partial that misses its round's ship and folds at a LATER round's
        root is still a canonical residue in [0, p-1] (sealing cannot
        change its value), so the stale tier fold is one more instance of
        the same certified loop: folding it at round r+k is bitwise
        folding it at round r, and the released sum it joins remains a
        sum of certified canonical summands.

    Unsafe base certificate => unsafe tree (no tree claim is made on top
    of a broken loop invariant).
    """
    base = certify_fold_inductive(int(prime))
    if not base.ok:
        return base
    checks = base.checks + (
        "tier partials canonical: each host fold ends in the loop "
        "post-fixpoint [0, p-1], satisfying the root fold's canonical-"
        "input precondition — the root is the same certified loop",
        "fold-tree = flat fold bitwise: exact canonical add mod p is "
        "associative+commutative, so any bracketing/arrival order of the "
        "same uploads yields identical residues",
        "carried partials certified: a sealed tier partial is a frozen "
        "canonical residue, so a stale tier fold at a later round's root "
        "is the same certified loop on the same value — late folding "
        "cannot leave the proven region",
    )
    return dataclasses.replace(base, checks=checks)


@dataclasses.dataclass(frozen=True)
class InferenceCertificate:
    """Static proof (or refutation) of the rotate-and-sum serving program
    (ISSUE 12): the encrypted-inference ladder's integer invariants."""

    ok: bool
    prime_bits: int
    digit_bits: int
    num_digits: int
    depth_ceiling_bits: int
    findings: tuple
    checks: tuple

    def summary(self) -> str:
        head = (
            f"inference ladder p<2**{self.prime_bits} "
            f"gadget(w={self.digit_bits} d={self.num_digits}) "
            f"depth<=2**{self.depth_ceiling_bits}"
        )
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(str(f) for f in self.findings)


@functools.lru_cache(maxsize=64)
def certify_inference(
    prime: int, digit_bits: int, num_digits: int
) -> InferenceCertificate:
    """Range-certify the rotate-and-sum Galois serving program
    (`he_inference.rotate_and_sum_scan`) for one ring geometry — the
    named analysis prerequisite of the encrypted-inference direction.

    Traces `he_inference.rotation_ladder_range_probe` — the ladder's
    carrier arithmetic as one `lax.while_loop` over an abstract stage
    depth, with the gadget decomposition and the rotation (gather +
    worst-case sign flip) inlined, and the rotation/gadget KEY tensors
    abstracted as canonical-residue inputs — and proves, as a loop
    post-fixpoint:

      * the carried (c0, c1) residues stay canonical ([0, p-1]) at ANY
        ladder depth (rotate-and-sum needs log2(slots) stages; the
        certificate does not care);
      * every gadget digit stays below 2**digit_bits and every
        digit x key inner-product term inside the declared 2**62
        exact-integer ceiling (the Montgomery REDC carrier contract);
      * the modular tree-sum re-canonicalizes at every step.

    The wrapping uint32 Montgomery cores themselves are NOT range-probed
    (intentional wraparound, covered by the lint rules + bitwise parity
    tests); the probe mirrors their canonical-residue CONTRACT, exactly
    like the packing probes mirror `psum_mod`.

    ISSUE 18 extends the same certificate over the other two serving
    programs: `ckks.ops.hoisted_gadget_probe` (the shared UNCENTERED
    decomposition — its digits must be canonical as extracted, i.e.
    2**digit_bits must sit inside the prime — plus the per-step digit x
    key products and eval permutation, at any abstract step count) and
    `he_inference.mlp_bsgs_range_probe` (the composed two-layer BSGS
    circuit: hoisted sweep → square → relinearize → rescale → hoisted
    sweep). A geometry is CERTIFIED only when all three programs hold;
    rejections cite the producing op.
    """
    import jax

    from hefl_tpu import he_inference
    from hefl_tpu.ckks import quantize

    prime = int(prime)
    canonical = Interval(0, prime - 1)
    wall = (1 << quantize.MAX_PACKED_BITS) - 1
    probe, args = he_inference.rotation_ladder_range_probe(
        prime, digit_bits, num_digits
    )
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(probe)(*args)

    in_ivs = [
        Interval(0, LOOP_COUNT_CEILING),   # abstract ladder depth
        canonical, canonical,              # carried ciphertext residues
        canonical, canonical,              # gadget/rotation key tensors
        # automorphism table indices: gather is range-preserving, so the
        # index bound is immaterial to the carried invariant.
        Interval(0, LOOP_COUNT_CEILING),
    ]
    res = eval_jaxpr_ranges(
        closed, in_ivs, ceiling=Interval(-wall, wall)
    )
    findings = list(res.findings)
    checks: list[str] = []
    if not any(rep.op == "while" for rep in res.loops):
        findings.append(RangeFinding(  # pragma: no cover - drift tripwire
            kind="output-bound", op="while", eqn_index=-1,
            interval=res.out_intervals[0], bound=canonical,
            message="ladder probe traced without a while loop — the "
                    "inductive machinery was not exercised",
        ))

    def out_check(idx: int, what: str):
        iv = res.out_intervals[idx]
        if iv.lo < canonical.lo or iv.hi > canonical.hi:
            outvar = closed.jaxpr.outvars[idx]
            op = "input"
            for eqn in closed.jaxpr.eqns:
                if outvar in eqn.outvars:
                    op = eqn.primitive.name
            findings.append(RangeFinding(
                kind="output-bound", op=op, eqn_index=-1,
                interval=iv, bound=canonical,
                message=f"{what}: `{op}` yields {iv}, outside {canonical}",
            ))
        else:
            checks.append(f"{what} in {iv} ⊆ {canonical}")

    out_check(0, "carried c0 residues (any ladder depth)")
    out_check(1, "carried c1 residues (any ladder depth)")
    if not findings:
        checks.append(
            f"gadget digit x key products inside the 2**62 wall "
            f"(w={digit_bits}, d={num_digits})"
        )

    # ISSUE 18: the hoisted-rotation sweep and the composed two-layer MLP
    # program ride the SAME certificate — serving dispatches through them,
    # so an uncertified geometry must refuse all three programs at once.
    def probe_checks(name: str, closed2, in_ivs2, out_specs) -> None:
        res2 = eval_jaxpr_ranges(
            closed2, in_ivs2, ceiling=Interval(-wall, wall)
        )
        findings.extend(res2.findings)
        if not any(rep.op == "while" for rep in res2.loops):
            findings.append(RangeFinding(  # pragma: no cover - tripwire
                kind="output-bound", op="while", eqn_index=-1,
                interval=res2.out_intervals[0], bound=canonical,
                message=f"{name} probe traced without a while loop — the "
                        "inductive machinery was not exercised",
            ))
        for idx, what, bound in out_specs:
            iv = res2.out_intervals[idx]
            if iv.lo < bound.lo or iv.hi > bound.hi:
                outvar = closed2.jaxpr.outvars[idx]
                op = "input"
                for eqn in closed2.jaxpr.eqns:
                    if outvar in eqn.outvars:
                        op = eqn.primitive.name
                findings.append(RangeFinding(
                    kind="output-bound", op=op, eqn_index=-1,
                    interval=iv, bound=bound,
                    message=f"{name}: {what}: `{op}` yields {iv}, "
                            f"outside {bound}",
                ))
            else:
                checks.append(f"{name}: {what} in {iv} ⊆ {bound}")

    from hefl_tpu.ckks import ops as ckks_ops

    hprobe, hargs = ckks_ops.hoisted_gadget_probe(
        prime, digit_bits, num_digits
    )
    with jax.enable_x64(True):
        hclosed = jax.make_jaxpr(hprobe)(*hargs)
    # The hoisted path skips centering, so its digits must be canonical AS
    # EXTRACTED: the 2**w gadget bound has to sit inside [0, p-1].
    digit_bound = Interval(0, min((1 << int(digit_bits)) - 1, prime - 1))
    probe_checks(
        "hoisted sweep", hclosed,
        [
            Interval(0, LOOP_COUNT_CEILING),   # abstract step count
            canonical, canonical,              # shared (c0, c1) residues
            canonical, canonical,              # pre-permuted key tensors
            Interval(0, LOOP_COUNT_CEILING),   # eval permutation indices
        ],
        [
            (0, "uncentered gadget digits (shared across every step)",
             digit_bound),
            (1, "hoisted c0 outputs (any step count)", canonical),
            (2, "hoisted c1 outputs (any step count)", canonical),
        ],
    )

    mprobe, margs = he_inference.mlp_bsgs_range_probe(
        prime, digit_bits, num_digits
    )
    with jax.enable_x64(True):
        mclosed = jax.make_jaxpr(mprobe)(*margs)
    probe_checks(
        "mlp compose", mclosed,
        [
            Interval(0, LOOP_COUNT_CEILING),   # layer-1 step count
            Interval(0, LOOP_COUNT_CEILING),   # layer-2 step count
            canonical, canonical,              # input ciphertext residues
            canonical, canonical,              # key tensors
            Interval(0, LOOP_COUNT_CEILING),   # permutation indices
            canonical,                         # rescale p_last^{-1} mod p
        ],
        [
            (0, "composed c0 residues (sweep→square→relin→rescale→sweep)",
             canonical),
            (1, "composed c1 residues (full two-layer circuit)", canonical),
        ],
    )

    return InferenceCertificate(
        ok=not findings,
        prime_bits=prime.bit_length(),
        digit_bits=int(digit_bits),
        num_digits=int(num_digits),
        depth_ceiling_bits=LOOP_COUNT_CEILING.bit_length() - 1,
        findings=tuple(findings),
        checks=tuple(checks),
    )


@dataclasses.dataclass(frozen=True)
class KeyswitchCertificate:
    """Static proof (or refutation) of one key-switch gadget geometry
    (ISSUE 13): the fused kernel's gadget-tensor contract."""

    ok: bool
    prime_bits: int
    digit_bits: int
    num_digits: int
    findings: tuple
    checks: tuple

    def summary(self) -> str:
        head = (
            f"keyswitch gadget p<2**{self.prime_bits} "
            f"(w={self.digit_bits} d={self.num_digits})"
        )
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(
            str(f) for f in self.findings
        )


@functools.lru_cache(maxsize=64)
def certify_keyswitch(
    prime: int, digit_bits: int, num_digits: int
) -> KeyswitchCertificate:
    """Range-certify the gadget key-switch itself for one geometry — the
    contract the fused `pallas_ntt.keyswitch_fused_pallas` kernel and the
    XLA reference both implement (ISSUE 13, the PR-8 follow-on the
    ROADMAP carried with the fusion item).

    Traces `ckks.ops.keyswitch_gadget_probe` — digit extraction,
    centering, the digit x key inner product over all L*d+1 gadget
    components, and the modular tree-sum on the int64 carrier — and
    proves, for ALL canonical inputs:

      * every gadget digit stays below 2**digit_bits AND below the prime
        (the kernel's `sub_mod` centering assumes canonical digits — a
        digit width that can overflow the prime is refuted here);
      * every digit x key product and Montgomery accumulation term stays
        inside the declared 2**62 exact-integer ceiling (the REDC
        carrier contract);
      * the accumulated (c0, c1) correction pair re-canonicalizes at
        every step and leaves the gadget in [0, p-1].

    `certify_inference` proves the same arithmetic embedded in the
    serving ladder's loop; this certificate is the standalone per-switch
    proof relinearization and single rotations rest on.
    """
    import jax

    from hefl_tpu.ckks import ops, quantize

    prime = int(prime)
    canonical = Interval(0, prime - 1)
    wall = (1 << quantize.MAX_PACKED_BITS) - 1
    probe, args = ops.keyswitch_gadget_probe(prime, digit_bits, num_digits)
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(probe)(*args)

    res = eval_jaxpr_ranges(
        closed,
        [canonical, canonical, canonical],
        ceiling=Interval(-wall, wall),
    )
    findings = list(res.findings)
    checks: list[str] = []

    def out_check(idx: int, bound: Interval, what: str):
        iv = res.out_intervals[idx]
        if iv.lo < bound.lo or iv.hi > bound.hi:
            outvar = closed.jaxpr.outvars[idx]
            op = "input"
            for eqn in closed.jaxpr.eqns:
                if outvar in eqn.outvars:
                    op = eqn.primitive.name
            findings.append(RangeFinding(
                kind="output-bound", op=op, eqn_index=-1,
                interval=iv, bound=bound,
                message=f"{what}: `{op}` yields {iv}, outside {bound}",
            ))
        else:
            checks.append(f"{what} in {iv} ⊆ {bound}")

    # probe outputs: (stacked digits, c0, c1)
    out_check(0, Interval(0, (1 << int(digit_bits)) - 1),
              "gadget digits (base-2**w bound)")
    out_check(0, canonical,
              "gadget digits canonical (the kernel's sub_mod precondition)")
    out_check(1, canonical, "accumulated c0 correction")
    out_check(2, canonical, "accumulated c1 correction")
    if not findings:
        checks.append(
            f"digit x key products inside the 2**62 wall "
            f"(w={digit_bits}, d={num_digits})"
        )

    return KeyswitchCertificate(
        ok=not findings,
        prime_bits=prime.bit_length(),
        digit_bits=int(digit_bits),
        num_digits=int(num_digits),
        findings=tuple(findings),
        checks=tuple(checks),
    )


@dataclasses.dataclass(frozen=True)
class TranscipherCertificate:
    """Static proof (or refutation) of one HHE transciphering geometry."""

    ok: bool
    modulus_bits: int
    bits: int
    k: int
    fbits: int
    guard: int          # effective guard guard_bits + ceil(log2 C)
    clients: int
    findings: tuple     # RangeFinding tuple, empty when ok
    checks: tuple       # human-readable proven facts

    def summary(self) -> str:
        head = (
            f"transciphering b={self.bits} k={self.k} C={self.clients} "
            f"(field {self.fbits}b, guard {self.guard}b, "
            f"q/2 wall 2**{self.modulus_bits - 1})"
        )
        if self.ok:
            return f"{head}: CERTIFIED — " + "; ".join(self.checks)
        return f"{head}: UNSAFE — " + "; ".join(
            str(f) for f in self.findings
        )


@functools.lru_cache(maxsize=256)
def certify_transciphering(
    modulus: int, bits: int, k: int, clients: int, guard_bits: int
) -> TranscipherCertificate:
    """Prove (or refute) the hybrid-HE transciphering invariants (ISSUE 11)
    for one (q, bits, k, clients, guard) point, over ALL inputs.

    Traces `hhe.cipher.transcipher_sum_probe` — the plaintext integer math
    the transciphered aggregation (trivial-embed → pad subtract → fold →
    decode_int_center → hhe_center_mod) computes under encryption, with
    the cipher's per-client wrap carry gamma ∈ {0, 1} abstracted as an
    input (its VALUE depends on the secret keystream; its range does not)
    — and checks:

      field_sums ≤ 2**fbits - 1       (the C-client sum never carries —
                                       keystream-subtract is carry-free
                                       inside the packed guard band)
      |noise_sum| < 2**(guard_eff-1)  (decrypt noise stays in the guard)
      |transciphered total| < q/2     (the centered CRT decode represents
                                       sum(v) - 2**62·Γ + E exactly)
      recovered+2**(g-1) ∈ [0, 2**62) (hhe_center_mod's shifted mod-2**62
                                       window recovers sum(v) + E exactly)

    The analysis runs with `check_dtype=False`: the probe's int64 is a
    TRACING carrier only — the real pipeline's decode reads the centered
    value through uint64 two's-complement, whose mod-2**64 wraparound is
    benign for the mod-2**62 recovery because 2**62 divides 2**64. The
    q/2 wall (the `ceiling`) is the mathematically binding bound, and a
    violated check names the offending op. Cached: the streaming engine
    certifies on every HHE round setup.
    """
    import jax

    from hefl_tpu.ckks import quantize
    from hefl_tpu.hhe import cipher as hhe_cipher

    fbits = quantize.field_bits(bits, clients)
    guard_eff = guard_bits + max(int(clients) - 1, 0).bit_length()
    half_q = modulus // 2
    ceiling = Interval(-(half_q - 1), half_q - 1)
    domain = 1 << hhe_cipher.HHE_DOMAIN_BITS

    probe, args = hhe_cipher.transcipher_sum_probe(
        bits, k, fbits, guard_eff, clients
    )
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(probe)(*args)

    noise_per_client = (1 << max(guard_bits - 1, 0)) - 1
    in_ivs = [
        TOP,                                            # raw float updates
        Interval(0, 1),                                 # wrap carry gamma
        Interval(-noise_per_client, noise_per_client),  # per-client noise
    ]
    res = eval_jaxpr_ranges(
        closed, in_ivs, ceiling=ceiling, check_dtype=False
    )
    findings = list(res.findings)
    checks: list[str] = []

    def out_check(idx: int, bound: Interval, what: str):
        iv = res.out_intervals[idx]
        if iv.lo < bound.lo or iv.hi > bound.hi:
            outvar = closed.jaxpr.outvars[idx]
            op = "input"
            for eqn in closed.jaxpr.eqns:
                if outvar in eqn.outvars:
                    op = eqn.primitive.name
            findings.append(RangeFinding(
                kind="output-bound", op=op, eqn_index=-1,
                interval=iv, bound=bound,
                message=f"{what}: `{op}` yields {iv}, outside {bound}",
            ))
        else:
            checks.append(f"{what} in {iv} ⊆ {bound}")

    # probe outputs:
    # (field_sums, noise_sum, transciphered_total, recovered_shifted)
    out_check(0, Interval(0, (1 << fbits) - 1),
              f"per-field {clients}-client sum (carry-free)")
    half_guard = 1 << max(guard_eff - 1, 0)
    out_check(1, Interval(-(half_guard - 1), half_guard - 1),
              "accumulated decrypt noise (guard band)")
    out_check(2, ceiling, "transciphered total (q/2 wall)")
    out_check(3, Interval(0, domain - 1),
              "shifted recovery (mod-2**62 window)")

    # The counter-mode keystream loop (ISSUE 12): the cipher's word-pair
    # no-wrap invariants proven over ANY round count — the round counter
    # (intentionally mod 2**32) and the carry-propagating add stay inside
    # their uint32 carriers at every iteration of the service's lifetime,
    # established as a while-loop post-fixpoint, not sampled at one round.
    cprobe, cargs = hhe_cipher.keystream_counter_probe()
    with jax.enable_x64(True):
        cclosed = jax.make_jaxpr(cprobe)(*cargs)
    word = Interval(0, (1 << 31) - 1)
    cres = eval_jaxpr_ranges(cclosed, [
        Interval(0, LOOP_COUNT_CEILING),     # abstract round count
        Interval(0, (1 << 32) - 1),          # round counter (mod 2**32)
        Interval((1 << 32) - 1, (1 << 32) - 1),  # the mod-2**32 mask
        word, word,                          # packed (hi, lo) payload
        word, word,                          # keystream (hi, lo) draws
    ])
    for f in cres.findings:
        findings.append(dataclasses.replace(
            f, message=f"keystream counter loop: {f.message}"
        ))
    if not any(rep.op == "while" for rep in cres.loops):
        findings.append(RangeFinding(  # pragma: no cover - drift tripwire
            kind="output-bound", op="while", eqn_index=-1,
            interval=cres.out_intervals[0], bound=word,
            message="keystream counter probe traced without a while loop",
        ))
    ctr_out, whi_out, wlo_out = cres.out_intervals
    for what, iv, bound in (
        ("round counter (mod 2**32)", ctr_out, Interval(0, (1 << 32) - 1)),
        ("cipher word hi", whi_out, word),
        ("cipher word lo", wlo_out, word),
    ):
        if iv.lo < bound.lo or iv.hi > bound.hi:
            findings.append(RangeFinding(
                kind="output-bound", op="while", eqn_index=-1,
                interval=iv, bound=bound,
                message=f"keystream counter loop: {what} reaches {iv}, "
                        f"outside {bound}",
            ))
        else:
            checks.append(f"{what} in {iv} ⊆ {bound} at any round count")

    return TranscipherCertificate(
        ok=not findings,
        modulus_bits=modulus.bit_length(),
        bits=bits, k=k, fbits=fbits, guard=guard_eff, clients=int(clients),
        findings=tuple(findings),
        checks=tuple(checks),
    )


def certified_max_interleave(
    modulus: int, bits: int, clients: int, guard_bits: int
) -> int:
    """The largest k this analyzer can certify (search upward from 1).

    The cross-check target for the closed-form headroom formula: the two
    derivations MUST agree on every supported config (quantize.
    max_interleave raises loudly when they don't)."""
    k = 0
    while certify_packing(modulus, bits, k + 1, clients, guard_bits).ok:
        k += 1
        if k > 64:  # one packed slot cannot hold more than 64 one-bit fields
            break
    return k


__all__ = [
    "Interval",
    "TOP",
    "LOOP_COUNT_CEILING",
    "SCAN_EXACT_LIMIT",
    "WIDEN_DELAY",
    "RangeFinding",
    "RangeResult",
    "LoopReport",
    "eval_jaxpr_ranges",
    "PackingCertificate",
    "AggregationCertificate",
    "FoldCertificate",
    "InferenceCertificate",
    "KeyswitchCertificate",
    "TranscipherCertificate",
    "certify_packing",
    "certify_aggregation",
    "certify_fold_inductive",
    "certify_fold_tree",
    "certify_inference",
    "certify_keyswitch",
    "certify_transciphering",
    "certified_max_interleave",
]
