"""repro.dist — the single source of truth for device context.

Everything mesh-shaped in the codebase goes through this package (full
reference: ``docs/dist_api.md``):

  - :mod:`repro.dist.api`      — ``use_mesh`` / ``current_ctx`` /
    ``constrain``: the ambient device context every model, trainer,
    pruner and server resolves instead of threading a mesh by hand;
  - :mod:`repro.dist.mesh`     — mesh construction (``make_mesh``, the
    one constructor — all axes ``Auto``; production pods, host test
    mesh, ``--mesh`` CLI specs);
  - :mod:`repro.dist.sharding` — the rules layer: param / batch
    PartitionSpecs and NamedShardings (FSDP over the data axes, tensor
    parallel over ``model``, MoE expert parallel).

Axis-naming convention: ``pod`` (DCN, outer batch axis), ``data``
(batch + FSDP), ``model`` (tensor/expert parallel).
"""

from repro.dist.api import (
    DistContext,
    constrain,
    current_ctx,
    use_mesh,
)
from repro.dist.mesh import (
    add_mesh_argument,
    dp_axes_of,
    make_host_mesh,
    make_mesh,
    make_production_mesh,
    mesh_context,
    mesh_from_spec,
)
from repro.dist.sharding import (
    FSDP_EXCLUDE_EMBED,
    batch_sharding,
    batch_spec,
    decode_cache_block_specs,
    moe_dispatch_specs,
    named_shardings,
    paged_kv_block_specs,
    paged_state_block_specs,
    param_shardings,
    param_specs,
    replicated,
    row_sharding,
    shard_params,
)

__all__ = [
    "DistContext",
    "constrain",
    "current_ctx",
    "use_mesh",
    "add_mesh_argument",
    "dp_axes_of",
    "make_host_mesh",
    "make_mesh",
    "make_production_mesh",
    "mesh_context",
    "mesh_from_spec",
    "FSDP_EXCLUDE_EMBED",
    "batch_sharding",
    "batch_spec",
    "decode_cache_block_specs",
    "moe_dispatch_specs",
    "named_shardings",
    "paged_kv_block_specs",
    "paged_state_block_specs",
    "param_shardings",
    "param_specs",
    "replicated",
    "row_sharding",
    "shard_params",
]
