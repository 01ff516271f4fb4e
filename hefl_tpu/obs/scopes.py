"""Canonical phase-scope names for trace-native attribution.

The round program's phases are annotated IN the program with
`jax.named_scope(<one of these>)`. A named scope rides the JAX name stack
into every lowered op's HLO metadata (`op_name="jit(f)/.../hefl.augment/
dot_general"`), which means two independent consumers see the same names:

  * HLO text — the scopes survive jit/compile, so a test can assert the
    annotation didn't get lost in a refactor (tests/test_obs.py);
  * profiler traces — on a TPU every device op's event metadata carries
    that `op_name` as the stat `tf_op`, and `obs.trace` reads it from the
    `.xplane.pb` itself: device seconds by scope and by kernel of the
    programs that ran, with no HLO text and no second compile.

Annotation rule (load-bearing): wrap only LEAF compute regions — never a
region that CALLS `lax.scan` / `lax.while_loop`, because the loop op
itself would then inherit the scope and its one trace event (spanning
every iteration, including other phases' work) would swallow the
attribution. A loop op deliberately left scope-less shows up as a
container whose children are attributed individually (`obs.trace` counts
an op's self time: its duration less the ops nested in it). Wrapping a
`lax.cond` call IS intended (e.g. the per-epoch validation cond): its
per-iteration event is the executed branch only.
"""

from __future__ import annotations

# One component of the op_name path; must not contain "/" (the path
# separator) so a scope is always exactly one component.
PREFIX = "hefl."

AUGMENT = "hefl.augment"              # affine-warp data augmentation
SGD_CORE = "hefl.sgd_core"            # fwd/bwd/Adam + batch gather/shuffles
VAL = "hefl.val"                      # per-epoch validation + callbacks
SANITIZE = "hefl.sanitize"            # poison injection + exclusion predicates
ENCRYPT = "hefl.encrypt"              # pack/encode + CKKS encrypt core
TRANSCIPHER = "hefl.transcipher"      # HHE trivial-embed + keystream subtract
PSUM_AGGREGATE = "hefl.psum_aggregate"  # ciphertext masking + lazy sum + psum
AGGREGATE = "hefl.aggregate"          # plaintext (masked) FedAvg mean + pmean
DECRYPT = "hefl.decrypt"              # c0 + c1*s, iNTT, decode, unpack
EVALUATE = "hefl.evaluate"            # test-set forward + softmax
SERVE_SCORE = "hefl.serve_score"      # inference ct x plain mul + bias
SERVE_ROTATE = "hefl.serve_rotate"    # rotation sweep bodies (ladder/BSGS)
SERVE_KEYSWITCH = "hefl.serve_keyswitch"  # gadget key-switch (fused kernel)
SERVE_HOIST = "hefl.serve_hoist"      # hoisted decompose + per-step products
MLA = "hefl.mla"                      # latent attention: projections, RoPE, softmax
MOE_ROUTE = "hefl.moe.route"          # sigmoid router, top-k, weights
MOE_EXPERTS = "hefl.moe.experts"      # sort + grouped product of the held experts
MOE_GMM = "hefl.moe_gmm"              # the grouped product's Pallas calls alone
DSA_INDEX = "hefl.dsa.index"          # the indexer's scores and its selection
DSA_ATTEND = "hefl.dsa.attend"        # attention over the selected keys alone
GQA = "hefl.gqa"                      # grouped-query attention: projections, RoPE, softmax
SWA_ATTEND = "hefl.swa.attend"        # a window layer's fused attention calls
KDA = "hefl.kda"                      # a linear-attention layer whole: projections to output gate
KDA_SCAN = "hefl.kda.scan"            # its chunked delta-rule recurrence alone
MTP = "hefl.mtp"                      # the multi-token-prediction module
LM_HEAD = "hefl.lm_head"              # head logits + cross-entropy, by slices
CONV = "hefl.conv"                    # a convolution (medcnn: with bias, ReLU and pool)
NORM = "hefl.norm"                    # GroupNorm
DENSE = "hefl.dense"                  # the dense head and its loss
ADAM = "hefl.adam"                    # the optimizer's update of a step
BATCH = "hefl.batch"                  # a step's batch: the gather by index and its rescale

# HOST-side spans (recorded through `obs.spans.span`, which opens a
# jax.profiler.TraceAnnotation; not named_scope): driver work that owns
# wall-clock but runs no device ops. The trace parser reports them as
# `host_rows` so e.g. a straggler wait is a first-class row instead of an
# unexplained wall-vs-device gap.
STRAGGLER_WAIT = "hefl.straggler_wait"  # driver-side straggler sleep
QUORUM_WAIT = "hefl.quorum_wait"        # streaming engine's wait-for-quorum

# Canonical ordering for tables; the trace parser buckets ANY "hefl.*"
# component it finds, so adding a scope never requires touching the parser.
PHASES = (
    AUGMENT,
    SGD_CORE,
    VAL,
    SANITIZE,
    ENCRYPT,
    TRANSCIPHER,
    PSUM_AGGREGATE,
    AGGREGATE,
    DECRYPT,
    EVALUATE,
    SERVE_SCORE,
    SERVE_ROTATE,
    SERVE_KEYSWITCH,
    SERVE_HOIST,
    MLA,
    MOE_ROUTE,
    MOE_EXPERTS,
    MOE_GMM,
    DSA_INDEX,
    DSA_ATTEND,
    GQA,
    SWA_ATTEND,
    KDA,
    KDA_SCAN,
    MTP,
    LM_HEAD,
    CONV,
    NORM,
    DENSE,
    ADAM,
    BATCH,
)


import re

# A scope may appear decorated by transformation context in the op_name
# path ("vmap(hefl.sgd_core)", "transpose(jvp(...))/hefl.val"), so scopes
# are extracted by substring, not by exact path-component match.
_SCOPE_RE = re.compile(r"hefl\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")


def is_phase_scope(component: str) -> bool:
    """Is this op_name path component one of ours?"""
    return component.startswith(PREFIX)


def scopes_in(op_name: str) -> list[str]:
    """Every hefl.* scope of an HLO `op_name` path, outer -> inner (a scope
    a transformation repeats, `hefl.val/cond/.../hefl.val`, appears again)."""
    return _SCOPE_RE.findall(op_name)


def scope_of(op_name: str) -> str | None:
    """Deepest hefl.* scope in an HLO `op_name` path (scopes nest — e.g.
    augment inside sgd_core — and the innermost is the attribution). Path
    components run outer -> inner, so the last match wins."""
    hits = scopes_in(op_name)
    return hits[-1] if hits else None
