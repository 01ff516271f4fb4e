"""Forbidden-primitive, dtype-contamination, callback, and donation lint.

The HE pipeline's structural invariants — zero hardware divides in the
modular hot path (PR 4), float-free exact-integer regions (PR 6), no
host-synchronizing callbacks inside jitted round programs, donated buffers
actually donated — are checked here STATICALLY, on the jaxprs and lowered
programs of the real code, instead of by hoping a reviewer notices a
reintroduced `lax.rem`.

Rules (each with a per-rule allowlist, see :data:`ALLOWLIST`):

  * ``forbidden-primitive`` — `rem`/`div` eqns. Inside a *declared
    exact-integer region* (the modules' ``exact_int_probes()`` exports)
    any rem/div is flagged regardless of dtype; in whole-program (hot
    path) mode only INTEGER rem/div are flagged — float division is the
    normal language of training math, an integer divide is a hardware
    divide the modular path must never issue.
  * ``float-contamination`` — any inexact-dtype value inside a declared
    exact-integer region (one f32 round-trip would shear packed bits).
  * ``f64`` — float64 anywhere in an analyzed program (the pipeline is
    f32/bf16/int; an f64 usually means an accidental host upcast leaked
    into a traced program).
  * ``host-callback`` — `pure_callback`/`io_callback`/`debug_callback`
    eqns in a jitted hot path (each one is a device→host sync).
  * ``broken-donation`` — a function declared with `donate_argnums`
    whose lowering carries NO input-output aliasing attribute: the
    donation silently degraded to a copy (dtype/shape mismatch, or a
    refactor dropped the argnum).
  * ``source-forbidden`` — AST-level sweep for `jnp.remainder` /
    `lax.rem` / `jnp.mod` attribute references in the package source
    (catches code paths no probe traces; docstrings don't trip it).

`lint_exact_regions` + `lint_round_programs` + `check_tree_donations` are
the whole-tree gates `hefl-lint` runs; `lint_fn` is the building block the
golden-violation fixtures exercise.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import os
import re
from typing import Any, Callable, Iterable

import numpy as np


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str      # rule id (see module docstring)
    where: str     # region / program / file the violation lives in
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.where}: {self.message}"


@dataclasses.dataclass(frozen=True)
class Allow:
    """One allowlist entry: exempts `primitive` from `rule` in regions
    matching the fnmatch `region` pattern, with a recorded justification.

    `max_size` restricts the exemption to ops whose output has at most
    that many elements (the "constant-table" qualifier). `source`
    restricts it to eqns whose traceback contains a user frame matching
    the `file:function` fnmatch pattern — the precise way to bless ONE
    call site (e.g. jax.random's unbiased modulo) without blessing every
    future rem in the same program."""

    region: str
    rule: str
    primitive: str
    reason: str
    max_size: int | None = None
    source: str | None = None


# The seeded allowlist (ISSUE 8 satellite): every entry is a DELIBERATE,
# justified exception — an unexplained new rem/div/float must fail CI, not
# grow this list silently.
ALLOWLIST: tuple[Allow, ...] = (
    Allow(
        region="*",
        rule="forbidden-primitive",
        primitive="div",
        source="*/ckks/modular.py:barrett_mu",
        reason=(
            "ckks.modular.barrett_mu: floor(2**32/p) on the uint32[L, 1] "
            "prime-constant table — XLA constant-folds it; never a "
            "per-element hot-path divide. Pinned to the ONE call site by "
            "source pattern AND capped by size so any other small integer "
            "divide still fails"
        ),
        max_size=64,
    ),
    Allow(
        region="fl.stream.accumulator_fold",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "OnlineAccumulator._add runs HOST-side (numpy on the driver, "
            "not a jitted hot path); the probe mirrors its (a+b) % p in "
            "jax only so the int64 no-wrap range proof stays honest"
        ),
    ),
    Allow(
        region="fl.stream.fold_loop",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "the arrival-loop form of the same host-side fold mirror "
            "(fold_loop_probe, ISSUE 12): the `%` inside the while body "
            "is OnlineAccumulator._add's numpy modulo, traced so the "
            "INDUCTIVE invariant proof analyzes the real loop shape"
        ),
    ),
    Allow(
        region="he_inference.rotate_ladder",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "rotation_ladder_range_probe (ISSUE 12) mirrors the serving "
            "ladder's canonical-residue arithmetic with `%` standing in "
            "for the Montgomery REDC contract — a probe traced for range "
            "analysis, never executed on a device; the REAL ladder "
            "(rotate_and_sum_scan) stays division-free and is hot-path "
            "linted separately"
        ),
    ),
    Allow(
        region="ckks.ops.keyswitch_gadget",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "keyswitch_gadget_probe (ISSUE 13) mirrors the fused "
            "key-switch kernel's digit x key accumulation with `%` "
            "standing in for the Montgomery REDC canonical-residue "
            "contract — a probe traced for range analysis, never executed "
            "on a device; the REAL key-switch (fused Pallas kernel + XLA "
            "reference) stays division-free and is bitwise parity-tested"
        ),
    ),
    Allow(
        region="ckks.ops.hoisted_gadget",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "hoisted_gadget_probe (ISSUE 18) mirrors the hoisted baby "
            "sweep — uncentered digit extraction, digit x pre-permuted "
            "key accumulation, the eval-domain output gather — with `%` "
            "standing in for the Montgomery REDC canonical-residue "
            "contract; a probe traced for range analysis (certifying the "
            "2**w <= min(p) digit-width geometry), never executed on a "
            "device. The REAL sweep (hoisted_rotations + Pallas kernel) "
            "stays division-free and is bitwise parity-tested against "
            "the per-step reference"
        ),
    ),
    Allow(
        region="he_inference.mlp_compose",
        rule="forbidden-primitive",
        primitive="rem",
        reason=(
            "mlp_bsgs_range_probe (ISSUE 18) mirrors the composed "
            "two-layer serving circuit — hoisted sweep, square, relin "
            "key-switch, rescale, second hoisted sweep — with `%` "
            "standing in for the Montgomery REDC contract; traced for "
            "range analysis only. The REAL composed program "
            "(_mlp_bsgs_program) stays division-free, is hot-path linted "
            "separately, and its hoisted/unhoisted twins are bitwise "
            "parity-tested"
        ),
    ),
    Allow(
        region="*",
        rule="forbidden-primitive",
        primitive="rem",
        source="*/ckks/keys.py:sample_*",
        reason=(
            "jax.random.randint inside the ternary/uniform SAMPLERS: the "
            "modulo is the standard unbiased range reduction of raw "
            "random bits — cryptographic sampling quality over saved "
            "cycles; not part of the deterministic modular-arithmetic "
            "hot path PR 4 made division-free"
        ),
    ),
    Allow(
        region="*",
        rule="forbidden-primitive",
        primitive="rem",
        source="*/fl/client.py:*",
        max_size=1,
        reason=(
            "flat steps-major scan bookkeeping: one SCALAR "
            "`step % steps_per_epoch` per training step to detect epoch "
            "boundaries — a scalar modulo on the host-shaped schedule, "
            "not per-element ciphertext work"
        ),
    ),
)

FORBIDDEN = ("rem", "div")
CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
}


def _iter_eqns(closed) -> Iterable:
    """All eqns of a closed jaxpr, recursing into every sub-jaxpr
    (pjit/scan/while/cond/shard_map/custom-vjp/...)."""
    from jax.extend import core as jex_core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in _as_jaxprs(v, jex_core):
                    yield from walk(sub)

    yield from walk(closed.jaxpr)


def _as_jaxprs(v, jex_core):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _as_jaxprs(item, jex_core)


def _out_size(eqn) -> int:
    aval = eqn.outvars[0].aval
    shape = getattr(aval, "shape", ())
    return int(np.prod(shape)) if shape else 1


def _eqn_sources(eqn) -> list[str]:
    """`file:function` strings of an eqn's traceback frames (empty when the
    eqn carries no traceback — source-scoped allowlist entries then
    conservatively do NOT match). JAX-internal frames are left in: no
    allowlist pattern names one."""
    tb = eqn.source_info.traceback
    if tb is None:
        return []
    return [f"{f.file_name}:{f.function_name}" for f in tb.frames]


def _allowed(
    allow: tuple[Allow, ...],
    region: str,
    rule: str,
    prim: str,
    size: int,
    eqn=None,
) -> Allow | None:
    for a in allow:
        if a.rule != rule or a.primitive not in ("*", prim):
            continue
        if not fnmatch.fnmatch(region, a.region):
            continue
        if a.max_size is not None and size > a.max_size:
            continue
        if a.source is not None:
            if eqn is None or not any(
                fnmatch.fnmatch(src, a.source) for src in _eqn_sources(eqn)
            ):
                continue
        return a
    return None


def _eqn_dtypes(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        # Literals carry an aval too; extended dtypes (PRNG keys) have no
        # numpy analog and are skipped.
        dtype = getattr(getattr(v, "aval", None), "dtype", None)
        if dtype is None:
            continue
        try:
            yield np.dtype(dtype)
        except TypeError:
            continue


def lint_jaxpr(
    closed,
    region: str,
    *,
    exact_int: bool,
    allow: tuple[Allow, ...] = ALLOWLIST,
) -> list[LintFinding]:
    """Run the jaxpr-level rules over one program.

    `exact_int=True` is the declared-exact-integer-region mode (any
    rem/div + any inexact dtype is a violation); False is the hot-path
    mode (integer rem/div, f64, callbacks)."""
    findings: list[LintFinding] = []
    for eqn in _iter_eqns(closed):
        prim = eqn.primitive.name
        dtypes = list(_eqn_dtypes(eqn))
        size = _out_size(eqn)
        if prim in CALLBACK_PRIMS:
            findings.append(LintFinding(
                rule="host-callback", where=region,
                message=(
                    f"`{prim}` inside a jitted program — a device→host "
                    "sync on the hot path"
                ),
            ))
        if any(d == np.float64 for d in dtypes):
            if _allowed(allow, region, "f64", prim, size, eqn) is None:
                findings.append(LintFinding(
                    rule="f64", where=region,
                    message=f"`{prim}` carries float64 "
                            f"({[str(d) for d in dtypes]})",
                ))
        if prim in FORBIDDEN:
            int_involved = any(np.issubdtype(d, np.integer) for d in dtypes)
            if (exact_int or int_involved) and _allowed(
                allow, region, "forbidden-primitive", prim, size, eqn
            ) is None:
                kind = "exact-integer region" if exact_int else "hot path"
                findings.append(LintFinding(
                    rule="forbidden-primitive", where=region,
                    message=(
                        f"`{prim}` in {kind} "
                        f"(dtypes {[str(d) for d in dtypes]}, "
                        f"out size {size}) — a hardware divide the modular "
                        "path must never issue"
                    ),
                ))
        if exact_int and any(
            np.issubdtype(d, np.inexact) for d in dtypes
        ):
            if _allowed(allow, region, "float-contamination", prim, size,
                        eqn) is None:
                findings.append(LintFinding(
                    rule="float-contamination", where=region,
                    message=(
                        f"`{prim}` carries inexact dtypes "
                        f"({[str(d) for d in dtypes]}) inside a declared "
                        "exact-integer region — one float round-trip "
                        "shears packed bits"
                    ),
                ))
    return findings


def lint_fn(
    fn: Callable,
    args: tuple,
    region: str,
    *,
    exact_int: bool,
    allow: tuple[Allow, ...] = ALLOWLIST,
) -> list[LintFinding]:
    """Trace `fn(*args)` and lint the jaxpr (the fixture entry point)."""
    import jax

    return lint_jaxpr(
        jax.make_jaxpr(fn)(*args), region, exact_int=exact_int, allow=allow
    )


# ---------------------------------------------------------------------------
# Whole-tree gates.
# ---------------------------------------------------------------------------


def exact_int_regions() -> dict[str, tuple[Callable, tuple]]:
    """Every declared exact-integer region in the codebase, as the shaped
    jaxpr probes their home modules export."""
    from hefl_tpu import he_inference
    from hefl_tpu.ckks import encoding, ops, packing, quantize
    from hefl_tpu.fl import secure, stream
    from hefl_tpu.hhe import cipher as hhe_cipher
    from hefl_tpu.hhe import transcipher as hhe_transcipher
    from hefl_tpu.parallel import collectives

    regions: dict[str, tuple[Callable, tuple]] = {}
    for mod in (quantize, packing, encoding, ops, secure, stream,
                collectives, hhe_cipher, hhe_transcipher, he_inference):
        regions.update(mod.exact_int_probes())
    return regions


def lint_exact_regions(
    allow: tuple[Allow, ...] = ALLOWLIST,
) -> list[LintFinding]:
    """Lint every declared exact-integer region (no rem/div, no floats)."""
    findings: list[LintFinding] = []
    for region, (fn, args) in exact_int_regions().items():
        findings.extend(
            lint_fn(fn, args, region, exact_int=True, allow=allow)
        )
    return findings


def _tiny_round_inputs():
    """Shared tiny geometry for tracing the REAL round programs."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.data import iid_contiguous, make_dataset, stack_federated
    from hefl_tpu.fl.fedavg import replicate_on
    from hefl_tpu.models import create_model
    from hefl_tpu.parallel import make_mesh

    (x, y), _, _ = make_dataset("mnist", seed=0, n_train=16, n_test=8)
    xs, ys = stack_federated(x, y, iid_contiguous(len(x), 2))
    module, params = create_model("smallcnn", rng=jax.random.key(0))
    mesh = make_mesh(2)
    gp = replicate_on(mesh, params)
    keys = jax.random.split(jax.random.key(1), 2)
    return module, params, mesh, gp, jnp.asarray(xs), jnp.asarray(ys), keys


def lint_round_programs(
    allow: tuple[Allow, ...] = ALLOWLIST,
    *,
    secure: bool = True,
    fusion: str = "vmap",
) -> list[LintFinding]:
    """Trace the real (tiny-geometry) round programs and run the hot-path
    rules: no integer rem/div, no f64, no host callbacks."""
    import jax

    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.fl.fedavg import _build_round_fn

    module, params, mesh, gp, xs, ys, keys = _tiny_round_inputs()
    cfg = TrainConfig(
        epochs=1, batch_size=4, num_classes=10, val_fraction=0.25,
        client_fusion=fusion,
    )
    findings: list[LintFinding] = []
    fn = _build_round_fn(module, cfg, mesh)
    findings.extend(lint_jaxpr(
        jax.make_jaxpr(fn)(gp, xs, ys, keys),
        f"fl.fedavg.round[{fusion}]", exact_int=False, allow=allow,
    ))
    if secure:
        from hefl_tpu.ckks.keys import CkksContext, keygen
        from hefl_tpu.fl.secure import _build_secure_round_fn

        ctx = CkksContext.create(n=256)
        _, pk = keygen(ctx, jax.random.key(2))
        sfn = _build_secure_round_fn(module, cfg, mesh, ctx, False)
        findings.extend(lint_jaxpr(
            jax.make_jaxpr(sfn)(gp, pk, xs, ys, keys, keys),
            f"fl.secure.round[{fusion}]", exact_int=False, allow=allow,
        ))
    return findings


# ---------------------------------------------------------------------------
# Donation rule.
# ---------------------------------------------------------------------------

_ALIAS_RE = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")


def check_donation(
    jitted: Any, args: tuple, where: str, *, min_aliased: int = 1
) -> list[LintFinding]:
    """Verify a `donate_argnums`-declared function actually lowers with
    input-output aliasing. JAX drops unusable donations with only a
    warning; this turns the silent copy back into a CI failure."""
    txt = jitted.lower(*args).as_text()
    aliased = len(_ALIAS_RE.findall(txt))
    if aliased < min_aliased:
        return [LintFinding(
            rule="broken-donation", where=where,
            message=(
                f"declared donation lowered with {aliased} aliased "
                f"buffer(s) (expected >= {min_aliased}) — the donated "
                "input is silently copied, not reused"
            ),
        )]
    return []


def check_tree_donations() -> list[LintFinding]:
    """The repo's declared donation sites, checked against their real
    lowerings at tiny geometry."""
    import jax
    import jax.numpy as jnp

    from hefl_tpu.fl import TrainConfig
    from hefl_tpu.fl.client import init_client_state, local_train_epochs_jit
    from hefl_tpu.models import create_model

    module, params = create_model("smallcnn", rng=jax.random.key(0))
    cfg = TrainConfig(epochs=1, batch_size=4, num_classes=10,
                      val_fraction=0.25)
    x = jnp.zeros((8, 28, 28, 1), jnp.uint8)
    y = jnp.zeros((8,), jnp.int32)
    state = init_client_state(params)
    keys = jax.random.split(jax.random.key(1), 1)
    return check_donation(
        local_train_epochs_jit,
        (module, cfg, params, x, y, state, keys, True),
        "fl.client.local_train_epochs_jit",
    )


# ---------------------------------------------------------------------------
# Source-level sweep (the grep the lint replaces, made docstring-proof).
# ---------------------------------------------------------------------------

_SOURCE_FORBIDDEN = {
    ("jnp", "remainder"): "jnp.remainder",
    ("lax", "rem"): "lax.rem",
    ("jnp", "mod"): "jnp.mod",
}


def source_sweep(root: str | None = None) -> list[LintFinding]:
    """AST-walk the package for forbidden attribute references. Docstrings
    and comments cannot trip it; a real call site always does."""
    import hefl_tpu

    root = root or os.path.dirname(hefl_tpu.__file__)
    findings: list[LintFinding] = []
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, os.path.dirname(root))
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError as e:  # pragma: no cover
                    findings.append(LintFinding(
                        rule="source-forbidden", where=rel,
                        message=f"unparsable: {e}",
                    ))
                    continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Attribute):
                    continue
                base = node.value
                if isinstance(base, ast.Name):
                    key = (base.id, node.attr)
                    if key in _SOURCE_FORBIDDEN:
                        findings.append(LintFinding(
                            rule="source-forbidden",
                            where=f"{rel}:{node.lineno}",
                            message=(
                                f"`{_SOURCE_FORBIDDEN[key]}` — use the "
                                "division-free ckks.modular Barrett "
                                "helpers instead"
                            ),
                        ))
    return findings


__all__ = [
    "LintFinding",
    "Allow",
    "ALLOWLIST",
    "lint_jaxpr",
    "lint_fn",
    "exact_int_regions",
    "lint_exact_regions",
    "lint_round_programs",
    "check_donation",
    "check_tree_donations",
    "source_sweep",
]
