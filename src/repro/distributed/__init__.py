"""Distributed-serving toolkit: sharding rules, activation policy,
roofline cost models and HLO analysis.

``sharding`` maps logical param/activation/state axes onto a mesh;
``policy`` is the process-global activation-sharding policy consulted
while tracing; ``roofline`` prices executables (compute / memory /
collective three-term model); ``hlo_analysis`` parses HLO text into a
walkable module for the collective/flops counters and the static checks.
"""
from repro.distributed import policy
from repro.distributed.hlo_analysis import (Computation, HloModule, Instr,
                                            analyse_hlo_text)
from repro.distributed.roofline import (Roofline, collective_bytes,
                                        model_flops_estimate)
from repro.distributed.sharding import (ShardingDegraded, batch_spec,
                                        cache_shardings,
                                        decode_state_shardings,
                                        input_shardings, mesh_axes,
                                        param_shardings,
                                        should_shard_fsdp_serving)

__all__ = [
    # sharding
    "param_shardings", "input_shardings", "cache_shardings",
    "decode_state_shardings", "mesh_axes", "batch_spec",
    "should_shard_fsdp_serving", "ShardingDegraded",
    # policy (module: set_policy/policy/choose_attn_mode/constrain_*)
    "policy",
    # roofline
    "Roofline", "collective_bytes", "model_flops_estimate",
    # hlo analysis
    "HloModule", "Computation", "Instr", "analyse_hlo_text",
]
