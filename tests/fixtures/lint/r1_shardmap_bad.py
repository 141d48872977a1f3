"""Known-bad R1: host syncs inside shard_map-traced bodies (both the
``from jax import shard_map`` spelling and the ``jax.shard_map`` attribute
must mark the body as traced)."""
import jax
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P


def psum_mean(mesh):
    def body(g):
        total = jax.lax.psum(g, "data")
        scale = float(total[0])            # R1a: float() in a traced body
        return np.asarray(total) * scale   # R1a: numpy on a traced value

    return shard_map(body, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"))


def scaled(mesh):
    def body2(x):
        return x * float(x.mean())         # R1a via the jax.shard_map alias

    return jax.shard_map(body2, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))
