"""Adapters between the benchmark's configuration files and the system
under test: its graph records, seeds and encoder configuration."""

from __future__ import annotations

import numpy as np


def graph_dict(g) -> dict:
    return {"node_type": g.node_type, "token": g.token, "pc_norm": g.pc_norm,
            "vstats": g.vstats, "warp_id": g.warp_id, "edge_src": g.edge_src,
            "edge_dst": g.edge_dst, "edge_type": g.edge_type,
            "n_warps": g.n_warps}


def program_seed(seed: int) -> int:
    """The 31-bit seed handed to the program (its planner and any numpy
    generator take it), drawn from the run's seed."""
    return int(np.random.default_rng([seed, 7]).integers(2**31 - 1))


def rgcn_config(cfg: dict, overrides: dict | None = None):
    from repro.core.precision import Policy
    from repro.core.rgcn import RGCNConfig

    r = cfg["rgcn"]
    kw = dict(dims=tuple(r["dims"]), num_bases=r["num_bases"],
              num_relations=r["num_relations"], proj_hidden=r["proj_hidden"],
              proj_out=r["proj_out"], dropout=r["dropout"],
              feat_noise_sigma=r["feat_noise_sigma"])
    overrides = dict(overrides or {})
    if "compute_dtype" in overrides:
        kw["policy"] = Policy(compute_dtype=overrides.pop("compute_dtype"))
    kw.update(overrides)
    return RGCNConfig(**kw)
