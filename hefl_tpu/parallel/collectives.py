"""Cross-client collectives — the wire layer of the federated system.

Reference equivalent: `export_weights` / `import_encrypted_weights`
(/root/reference/FLPyfhelin.py:230-240, :303-328) — pickle files standing in
for a network. Here the "network" is the TPU interconnect and the transfer
IS the aggregation: one XLA collective per round.

`psum_mod` is the homomorphic-aggregation primitive (SURVEY.md §5,
"distributed communication backend"): a psum of uint32 RNS residues
followed by one modular reduction. Residues are < p < 2**27 and the psum
adds at most 32 of them, so the sum stays < 2**32 with no wraparound —
lazy reduction, one reduction per round instead of one per pairwise add,
and that reduction is shift-multiply Barrett (no hardware divide).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# p < 2**27 (keys.DEFAULT_PRIME_BITS) and sums must stay < 2**32.
MAX_PSUM_CLIENTS = 32


def psum_mod(residues: jax.Array, p: jax.Array, axis_name: str) -> jax.Array:
    """Modular all-reduce: (Σ_clients residues) mod p, residues uint32[..., L, N].

    The homomorphic FedAvg sum: psum of ciphertext limbs over ICI = ct+ct
    for every client simultaneously (the reference's loop at
    FLPyfhelin.py:378-381 collapsed into one collective). The post-psum
    canonicalization is division-free Barrett, bitwise-equal to the
    historical `lax.rem`.
    """
    from hefl_tpu.ckks.modular import barrett_mod, barrett_mu

    total = jax.lax.psum(residues, axis_name)
    # Compute the Barrett constant at the [L, 1] table shape BEFORE
    # broadcasting (hefl-lint forbidden-primitive): the divide inside
    # barrett_mu must stay a constant-table op, not balloon to the full
    # ciphertext shape and rely on XLA to fold it away.
    mu = barrett_mu(p)
    return barrett_mod(
        total,
        jnp.broadcast_to(p, total.shape),
        jnp.broadcast_to(mu, total.shape),
    )


def exact_int_probes() -> dict:
    """Shaped jaxpr probes of the modular all-reduce (ISSUE 8,
    analysis.lint): the whole collective — psum plus the Barrett
    canonicalization — must stay rem/div- and float-free, on the 1-D
    client mesh AND on the 2-D ("clients", "ct") mesh (ISSUE 15), where
    the same collective runs on ct-sharded ciphertext rows."""
    import numpy as np

    from hefl_tpu.parallel import make_mesh, make_mesh_2d, shard_map
    from jax.sharding import PartitionSpec as P

    p = jnp.asarray(np.full((1, 1), 2**27 - 39, np.uint32))
    mesh = make_mesh(1)
    fn = shard_map(
        lambda x: psum_mod(x, p, "clients"),
        mesh=mesh,
        in_specs=P("clients"),
        out_specs=P(),
        check_vma=False,
    )
    mesh2d = make_mesh_2d(1, 1)
    fn2d = shard_map(
        lambda x: psum_mod(x, p, "clients"),
        mesh=mesh2d,
        in_specs=P("clients", "ct"),
        out_specs=P(None, "ct"),
        check_vma=False,
    )
    x = jnp.zeros((1, 1, 8), jnp.uint32)
    return {
        "parallel.collectives.psum_mod": (fn, (x,)),
        "parallel.collectives.psum_mod_2d": (fn2d, (x,)),
    }


def psum_range_probe(prime: int):
    """Range probe (analysis.ranges.certify_aggregation): the LAZY psum
    accumulation inside `psum_mod` — the sum of canonical residues across
    the client axis runs unreduced, so the no-wrap invariant is
    participants * (p-1) < 2**32. Analyzed at the declared worst-case
    axis size (MAX_PSUM_CLIENTS), whatever mesh traced the probe. The
    Barrett canonicalization that follows wraps uint32 BY DESIGN
    (mul32_wide's carry arithmetic) and is covered by the lint rules +
    bitwise parity tests instead of interval analysis."""
    from hefl_tpu.parallel import make_mesh, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(1)
    fn = shard_map(
        lambda x: jax.lax.psum(x, "clients"),
        mesh=mesh,
        in_specs=P("clients"),
        out_specs=P(),
        check_vma=False,
    )
    x = jnp.zeros((1, 1, 8), jnp.uint32)
    return fn, (x,)


def psum_range_probe_2d(prime: int):
    """Range probe of the 2-D round's aggregation tail (ISSUE 15): the
    SAME lazy psum accumulation as `psum_range_probe`, traced over a
    ("clients", "ct") mesh with the ciphertext-row axis sharded over
    ``"ct"`` — the shape `analysis.ranges.certify_aggregation` analyzes
    with worst-case sizes injected on BOTH axes, so the cohort-bucketed
    psum bound is proven on the topology the 2-D round actually runs, not
    extrapolated from the 1-D trace. Only the ``"clients"`` axis is
    reduced over; the injected ``"ct"`` worst case proves the bound is
    ct-shard-count-independent (sharding partitions rows, it never adds
    summands)."""
    from hefl_tpu.parallel import make_mesh_2d, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh_2d(1, 1)
    fn = shard_map(
        lambda x: jax.lax.psum(x, "clients"),
        mesh=mesh,
        in_specs=P("clients", "ct"),
        out_specs=P(None, "ct"),
        check_vma=False,
    )
    x = jnp.zeros((1, 1, 8), jnp.uint32)
    return fn, (x,)


def pmean_tree(tree, axis_name: str | tuple[str, ...]):
    """Plaintext FedAvg: pmean of a parameter pytree over the client axis —
    one name on the flat mesh, the ("hosts", "clients") tuple on the 2-D
    multi-host mesh (lax.pmean reduces over all named axes jointly)."""
    return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis_name), tree)


def reduce_mod(residues: jax.Array, p: jax.Array, axis_name: str) -> jax.Array:
    """Modular all-reduce over one axis, picking the sound backend: the
    fused lazy psum up to MAX_PSUM_CLIENTS participants, the canonical
    ppermute ring beyond."""
    n = jax.lax.axis_size(axis_name)
    return (psum_mod if n <= MAX_PSUM_CLIENTS else ring_psum_mod)(
        residues, p, axis_name
    )


def hierarchical_psum_mod(
    residues: jax.Array, p: jax.Array, axis_names: tuple[str, ...]
) -> jax.Array:
    """Modular all-reduce over several mesh axes, innermost LAST — the
    multi-host pattern (SURVEY.md §2.13's distributed-backend story): on a
    ("hosts", "clients") mesh pass `("hosts", "clients")` and each host row
    first reduces its clients over ICI (fast, lazy psum), then the
    already-reduced per-host partials cross DCN once. Each stage re-canonicalizes
    (< p), so the lazy uint32 bound applies PER AXIS — 32 clients per host
    times 32 hosts = 1024 participants without ever leaving the fused-psum
    fast path, and the ring lifts either axis past 32.
    """
    for axis in reversed(axis_names):   # innermost (intra-host) first
        residues = reduce_mod(residues, p, axis)
    return residues


def dcn_traffic_model(
    num_participants: int,
    num_hosts: int,
    ct_nbytes: int,
    participants_per_host: tuple[int, ...] | None = None,
) -> dict:
    """Per-round cross-host (simulated-DCN) byte cost of the two aggregation
    topologies on a ("hosts", "clients") mesh — host-side arithmetic, no jax.

    Flat aggregation ships every participant's ciphertext across the
    cross-host link to one root: `num_participants * ct_nbytes`. The
    hierarchical fold (`hierarchical_psum_mod` on the mesh; fl.hierarchy's
    `HierarchicalAggregator` off it) reduces each host's block over ICI
    first and crosses DCN with exactly ONE partial ciphertext per host that
    holds any participant: at most `num_hosts * ct_nbytes`, i.e. O(hosts)
    instead of O(cohort). `participants_per_host` (when known) tightens the
    hierarchical cost to the NONEMPTY hosts — an outage-darkened host ships
    nothing. This model is what the `dcn.link.*` obs counters measure and
    what the BENCH_DCN gate checks against.
    """
    if num_participants < 0 or num_hosts < 1 or ct_nbytes < 1:
        raise ValueError(
            f"dcn_traffic_model: participants={num_participants} "
            f"hosts={num_hosts} ct_nbytes={ct_nbytes}"
        )
    if participants_per_host is not None:
        if len(participants_per_host) != num_hosts:
            raise ValueError(
                f"participants_per_host has {len(participants_per_host)} "
                f"entries for {num_hosts} hosts"
            )
        if sum(participants_per_host) != num_participants:
            raise ValueError(
                f"participants_per_host sums to {sum(participants_per_host)}"
                f", expected {num_participants}"
            )
        shipping = sum(1 for n in participants_per_host if n > 0)
    else:
        shipping = min(num_hosts, num_participants)
    flat = num_participants * ct_nbytes
    hier = shipping * ct_nbytes
    return {
        "num_participants": int(num_participants),
        "num_hosts": int(num_hosts),
        "shipping_hosts": int(shipping),
        "ct_bytes": int(ct_nbytes),
        "flat_dcn_bytes": int(flat),
        "hier_dcn_bytes": int(hier),
        "bytes_ratio": (flat / hier) if hier else float("inf"),
    }


def ring_psum_mod(residues: jax.Array, p: jax.Array, axis_name: str) -> jax.Array:
    """Modular all-reduce as an explicit ppermute ring — no participant cap.

    `psum_mod` rides XLA's fused all-reduce but leans on lazy reduction, so
    it is only sound for <= MAX_PSUM_CLIENTS participants. Here each of the
    D-1 ring hops shifts the running buffer one neighbor over (XLA lowers
    `ppermute` to ICI neighbor exchanges) and folds it in with a CANONICAL
    modular add, so residues stay < p < 2**31 at every step and any device
    count works. Tradeoff: D-1 full-tensor hops (bandwidth ~2x the optimal
    reduce-scatter ring) and a serial chain — the right tool past the lazy
    bound or when per-hop canonicality is wanted, not a psum replacement.
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    from hefl_tpu.ckks.modular import add_mod

    acc = residues
    buf = residues
    for _ in range(n - 1):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        acc = add_mod(acc, buf, jnp.broadcast_to(p, acc.shape))
    return acc
