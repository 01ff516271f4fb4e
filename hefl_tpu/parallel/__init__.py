"""Device-mesh parallelism for federated learning.

The reference "parallelizes" clients by a sequential Python loop in one
process (SURVEY.md §2.13) and moves bytes between parties as pickle files.
Here federated data parallelism is real hardware parallelism: a 1-D
`jax.sharding.Mesh` over the axis ``"clients"``, one (or more) FL clients
per TPU device under `shard_map`, and the cross-client exchange is an XLA
collective over ICI — `pmean` of weight pytrees for plaintext FedAvg,
`psum` of ciphertext RNS limbs (with lazy modular reduction) for the
encrypted path.
"""

from hefl_tpu.parallel.mesh import (
    CLIENT_AXIS,
    CT_AXIS,
    HOST_AXIS,
    client_axes,
    client_sharding,
    client_mesh_size,
    ct_shard_count,
    dcn_link_names,
    host_count,
    host_of_clients,
    local_client_count,
    make_ct_mesh,
    make_host_mesh,
    make_mesh,
    make_mesh_2d,
    shard_map,
)
from hefl_tpu.parallel.collectives import (
    dcn_traffic_model,
    hierarchical_psum_mod,
    pmean_tree,
    psum_mod,
    ring_psum_mod,
)

__all__ = [
    "CLIENT_AXIS",
    "CT_AXIS",
    "HOST_AXIS",
    "make_ct_mesh",
    "client_axes",
    "client_sharding",
    "client_mesh_size",
    "ct_shard_count",
    "dcn_link_names",
    "dcn_traffic_model",
    "host_count",
    "host_of_clients",
    "make_mesh",
    "make_mesh_2d",
    "make_host_mesh",
    "shard_map",
    "local_client_count",
    "psum_mod",
    "pmean_tree",
    "ring_psum_mod",
    "hierarchical_psum_mod",
]
