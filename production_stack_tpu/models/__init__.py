"""JAX model definitions.

Pure-functional models: parameters are pytrees of jnp arrays with the
layer dimension stacked (tensor-parallel sharding annotations apply
uniformly across layers) and the decoder loop STATICALLY UNROLLED so
every paged-KV update is an in-place scatter at a static layer index.
Scanning layers with the cache as scan xs/ys made XLA copy whole layer
caches in and out per step — ~16x the cost of the chained in-place
scatters on a v5e (builder-captured 2026-07-30, not measured by the
driver); the cache-free training forwards (forward_train) still scan.
"""

from production_stack_tpu.models.registry import (
    get_model,
    list_architectures,
)

__all__ = ["get_model", "list_architectures"]
