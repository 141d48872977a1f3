"""Known-bad R3: hard-coded + reused PRNG key inside a shard_map body
(every shard would draw IDENTICAL noise — data-parallel augmentation
silently degenerates to one effective sample)."""
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def noisy_mean(mesh):
    def body(g):
        key = jax.random.PRNGKey(0)          # R3: hard-coded literal key
        noise = jax.random.normal(key, g.shape)
        mask = jax.random.bernoulli(key, 0.5, g.shape)  # R3: reused, no split
        return jax.lax.psum(g + noise * mask, "data")

    return shard_map(body, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"))
