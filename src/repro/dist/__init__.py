"""Distributed layer: sharding rules, compressed collectives, elasticity.

``dist.sharding``    — NamedSharding rules for params / batches / caches
``dist.collectives`` — error-bounded compressed gradient psum (+EF),
                       topo-aware variant with an exact top-|g| sidecar
``dist.ring``        — bitpacked ppermute ring all-reduce (the "packed"
                       wire format: actual compressed bytes on the wire)
``dist.elastic``     — largest-valid-mesh rebuild after device loss
"""
from repro.dist import collectives, elastic, ring, sharding
from repro.dist.collectives import (WIRE_FORMATS, code_bits,
                                    compressed_psum_tree, max_code,
                                    protect_k, quantize_dequantize_sum,
                                    sidecar_bits, topk_rank_preservation,
                                    topo_compressed_psum_tree,
                                    topo_quantize_dequantize_sum,
                                    topo_wire_bits)
from repro.dist.elastic import (DeviceLoss, largest_mesh_shape,
                                mesh_shape_dict, rebuild_mesh)
from repro.dist.ring import (packed_psum_tree, packed_wire_summary,
                             simulate_hop_bytes)
from repro.dist.sharding import (adapt_spec, batch_axes, cache_shardings,
                                 data_sharding, param_shardings, replicated,
                                 spec_from_json, spec_to_json)

__all__ = [
    "collectives", "elastic", "ring", "sharding",
    "WIRE_FORMATS", "code_bits", "compressed_psum_tree", "max_code",
    "quantize_dequantize_sum",
    "protect_k", "sidecar_bits", "topk_rank_preservation",
    "topo_compressed_psum_tree", "topo_quantize_dequantize_sum",
    "topo_wire_bits",
    "packed_psum_tree", "packed_wire_summary", "simulate_hop_bytes",
    "DeviceLoss", "largest_mesh_shape", "mesh_shape_dict",
    "rebuild_mesh",
    "adapt_spec", "batch_axes", "cache_shardings", "data_sharding",
    "param_shardings", "replicated", "spec_from_json", "spec_to_json",
]
