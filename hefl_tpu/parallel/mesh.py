"""Mesh construction for the one-client-per-device FL topology.

Three shapes:

  * `make_mesh` — the flat 1-D "clients" mesh (one pod slice, clients over
    ICI). This is the default topology for every single-host experiment.
  * `make_host_mesh` — a 2-D ("hosts", "clients") mesh modeling the
    multi-host deployment: the client collective runs over the fast
    intra-host interconnect (ICI), and the cross-host fold is the one DCN
    hop per round. The reference's analog of "many machines exchanging
    pickle files" (SURVEY.md §2.13) — here the exchange IS the hierarchical
    collective.
  * `make_mesh_2d` — a 2-D ("clients", "ct") mesh (ISSUE 15): the client
    axis shards the cohort's training blocks, and the ``"ct"`` axis shards
    the [n_ct, L, N] ciphertext rows of the in-round encrypt core *within*
    each client block (fl.secure's `_ct_sharded_encrypt_core`). With
    cohort-only training the client axis is small (the cohort bucket, not
    the registry), so the leftover devices go to HE row throughput instead
    of idling. The client axis is laid out outer/slowest so a multi-host
    `pjit` deployment keeps each host's client block local (host-local
    cohort gather) and crosses DCN only for the psum of ciphertext sums.

`HEFL_MESH_CT=K` (K > 1) makes `make_mesh` return the 2-D shape with K
ct-shards per client block — the CI knob that re-runs whole suites on the
(clients, ct) topology without touching each call site.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

CLIENT_AXIS = "clients"
HOST_AXIS = "hosts"
CT_AXIS = "ct"


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map` with the replication check off by default. Every
    round program builds through here."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def client_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the federated client dimension shards over (outer-first:
    hosts, then clients on a 2-D mesh)."""
    if HOST_AXIS in mesh.axis_names:
        return (HOST_AXIS, CLIENT_AXIS)
    return (CLIENT_AXIS,)


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (client) axis split over the mesh's client axes, the rest
    replicated — the layout every round program's `P(axes)` in_spec asks
    for, so federated arrays placed with it are never resharded per round."""
    return NamedSharding(mesh, PartitionSpec(client_axes(mesh)))


def client_mesh_size(mesh: Mesh) -> int:
    """Total devices the client dimension spans."""
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh)]))


def make_mesh(num_clients: int, devices: list | None = None) -> Mesh:
    """1-D mesh over min(num_clients, n_devices) devices, axis "clients".

    When num_clients exceeds the device count (e.g. 16 clients on a v4-8),
    the client axis of the federated arrays is still sharded over this mesh
    and each device sequentially simulates `num_clients / n_devices` clients
    via an inner vmap — see fl.fedavg. A count that does NOT divide the
    mesh is fine: the round engines pad the client axis with masked-out
    dummy clients (fl.fedavg.pad_index), so any client count runs on any
    mesh.

    With `HEFL_MESH_CT=K` (K > 1) the same call returns the 2-D
    ("clients", "ct") mesh instead — every round program built through
    here then shards its in-round HE rows K ways (bitwise-identical
    results; see `make_mesh_2d`). The env knob exists so CI can re-run the
    stream/secure suites on the 2-D topology unmodified.
    """
    devs = list(devices if devices is not None else jax.devices())
    ct = int(os.environ.get("HEFL_MESH_CT", "0") or 0)
    if ct > 1:
        return make_mesh_2d(num_clients, ct, devices=devs)
    n = min(num_clients, len(devs))
    return Mesh(np.array(devs[:n]), (CLIENT_AXIS,))


def make_mesh_2d(
    num_clients: int, ct_shards: int, devices: list | None = None
) -> Mesh:
    """2-D ("clients", "ct") mesh: client blocks x in-round ciphertext
    shards (ISSUE 15).

    Rows (the client axis) take min(num_clients, n_devices // ct_shards)
    devices; each row's `ct_shards` devices split that block's [n_ct, L, N]
    ciphertext rows inside the round program (`fl.secure`). Training is
    sharded over the client axis only — each ct column of a row computes
    the same (deterministic) training block, so the wall-clock cost equals
    the row-count 1-D mesh while the NTT-heavy encrypt core runs
    `ct_shards`-way parallel. A `ct_shards` that exceeds the device count
    is clamped (never fail on a smaller box); at least one client row
    always exists.
    """
    if ct_shards < 1:
        raise ValueError(f"make_mesh_2d: ct_shards={ct_shards} must be >= 1")
    devs = list(devices if devices is not None else jax.devices())
    ct = min(int(ct_shards), len(devs))
    rows = max(1, min(num_clients, len(devs) // ct))
    need = rows * ct
    return Mesh(
        np.array(devs[:need]).reshape(rows, ct), (CLIENT_AXIS, CT_AXIS)
    )


def ct_shard_count(mesh: Mesh) -> int:
    """In-round ciphertext shards this mesh provides (1 on the 1-D and
    ("hosts", "clients") meshes — the historical replicated-HE layout)."""
    if CT_AXIS in mesh.axis_names:
        return int(mesh.shape[CT_AXIS])
    return 1


def make_host_mesh(
    num_hosts: int, clients_per_host: int, devices: list | None = None
) -> Mesh:
    """2-D ("hosts", "clients") mesh: `num_hosts` rows of `clients_per_host`
    devices. Federated arrays shard their client axis over BOTH axes
    (row-major: host 0 takes the first `clients_per_host` clients); the
    secure round reduces within a host first (lazy psum over ICI), then
    across hosts (the DCN hop) — see parallel.collectives and fl.secure."""
    devs = list(devices if devices is not None else jax.devices())
    need = num_hosts * clients_per_host
    if len(devs) < need:
        raise ValueError(f"need {need} devices for a {num_hosts}x{clients_per_host} mesh, have {len(devs)}")
    if devices is None:
        # The hierarchical reduce's performance story (clients over ICI,
        # hosts over DCN) only holds if each mesh row lives on ONE physical
        # process; jax.devices() is process-major but nothing forces the row
        # width to match. Group by process so rows align when possible —
        # the mod-p result is grouping-independent either way, only the
        # interconnect each stage rides changes.
        by_proc: dict[int, list] = {}
        for d in devs:
            by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
        if all(len(g) % clients_per_host == 0 for g in by_proc.values()):
            devs = [d for g in by_proc.values() for d in g]
    return Mesh(
        np.array(devs[:need]).reshape(num_hosts, clients_per_host),
        (HOST_AXIS, CLIENT_AXIS),
    )


def local_client_count(mesh: Mesh, num_clients: int) -> int:
    """Clients simulated per device (>=1)."""
    return num_clients // client_mesh_size(mesh)


def host_count(mesh: Mesh) -> int:
    """Host rows this mesh models (1 on every single-host topology)."""
    if HOST_AXIS in mesh.axis_names:
        return int(mesh.shape[HOST_AXIS])
    return 1


def host_of_clients(num_clients: int, num_hosts: int) -> np.ndarray:
    """int64[num_clients]: which host row owns each client slot.

    The PR-15 layout contract, made queryable: the client axis is laid out
    outer/slowest, so host h owns the CONTIGUOUS block of
    ceil(num_clients / num_hosts) client slots starting at
    h * ceil(num_clients / num_hosts) — exactly the row-major assignment
    `make_host_mesh` gives a ("hosts", "clients") mesh. The hierarchical
    aggregation tier (fl.hierarchy) and the regional-outage fault schedule
    (fl.faults) both key off this map, so "a host's cohort block is
    host-local" means the same clients everywhere.
    """
    if num_hosts < 1:
        raise ValueError(f"host_of_clients: num_hosts={num_hosts} must be >= 1")
    if num_clients < num_hosts:
        raise ValueError(
            f"host_of_clients: {num_hosts} hosts over {num_clients} clients "
            "would leave empty host rows; use num_hosts <= num_clients"
        )
    per_host = -(-num_clients // num_hosts)
    return np.arange(num_clients, dtype=np.int64) // per_host


def dcn_link_names(num_hosts: int) -> tuple[str, ...]:
    """The simulated-DCN uplinks of the two-tier aggregation topology:
    one host->root link per host row (h{h}_root). Per-link byte counters
    ride the obs registry as `dcn.link.<name>.bytes` — see fl.hierarchy."""
    return tuple(f"h{h}_root" for h in range(int(num_hosts)))


def make_ct_mesh(devices: list | None = None, max_devices: int | None = None) -> Mesh:
    """1-D mesh over the ciphertext-batch axis ``"ct"`` (ISSUE 4).

    The [n_ct, L, N] ciphertext residue tensors are embarrassingly parallel
    over `n_ct` (every ciphertext row is independent; RNS limbs too), so
    owner-side encrypt/decrypt shards the ciphertext batch over every
    device of the slice instead of running replicated — HE throughput then
    scales with devices exactly like training does. `fl.secure`'s
    `encrypt_params_sharded` / `decrypt_average(..., mesh=)` consume this.
    """
    devs = list(devices if devices is not None else jax.devices())
    if max_devices is not None:
        devs = devs[:max_devices]
    return Mesh(np.array(devs), (CT_AXIS,))
