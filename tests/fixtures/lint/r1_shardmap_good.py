"""Known-good R1: shard_map bodies stay on-device (pure lax/jnp ops —
cross-shard reductions via collectives, never host round-trips)."""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def psum_mean(mesh):
    def body(g):
        n = jax.lax.psum(jnp.ones(()), "data")
        return jax.lax.psum(g, "data") / n

    return shard_map(body, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"))


def scaled(mesh):
    def body2(x):
        return x * jnp.mean(x)

    return jax.shard_map(body2, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))
