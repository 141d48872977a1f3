"""Known-good R3: the key enters as an argument, is folded per shard
(axis_index keeps shards decorrelated), and split once per consumer."""
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def noisy_mean(mesh):
    def body(g, key):
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        k1, k2 = jax.random.split(key)
        noise = jax.random.normal(k1, g.shape)
        mask = jax.random.bernoulli(k2, 0.5, g.shape)
        return jax.lax.psum(g + noise * mask, "data")

    return shard_map(body, mesh=mesh, in_specs=(P("data"), P()),
                     out_specs=P("data"))
