"""Roofline-term derivation from a compiled dry-run artifact.

Hardware constants (TPU v5e, per assignment):
  197 TFLOP/s bf16 per chip (int8 ~2x), 819 GB/s HBM, ~50 GB/s/link ICI.

cost_analysis() of the SPMD-partitioned module reports PER-DEVICE flops /
bytes (verified: sharded flops = unsharded / n_devices), so:
  compute_term    = flops_per_dev / PEAK
  memory_term     = bytes_per_dev / HBM_BW
  collective_term = collective_bytes_per_dev / ICI_LINK_BW
MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), 2*N*D forward-only.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.dist.hlo_analysis import analyze_collectives
from repro.launch.mesh import mesh_chips

PEAK_BF16 = 197e12      # FLOP/s per chip
PEAK_INT8 = 394e12
HBM_BW = 819e9          # B/s per chip
ICI_LINK_BW = 50e9      # B/s per link


def active_param_count(cfg: ModelConfig) -> int:
    """Non-embedding active parameters (MoE counts top-k experts only)."""
    n = cfg.param_count(active_only=True)
    n -= cfg.vocab_size * cfg.d_model          # input embedding
    return max(n, 1)


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """Useful model FLOPs per step, whole job (all chips)."""
    n = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per row + attention over the cache
    flops = 2.0 * n * shape.global_batch
    per_layer_kv = {"attn": shape.seq_len,
                    "swa": min(cfg.window_size, shape.seq_len)}
    kv_positions = sum(per_layer_kv.get(m, 0)
                       for m, _ in cfg.blocks) * cfg.num_cycles
    flops += 4.0 * cfg.num_heads * cfg.head_dim * kv_positions \
        * shape.global_batch
    return flops


def analyze_cell(compiled, cfg: ModelConfig, shape: ShapeSpec, mesh,
                 mesh_kind: str, int8: bool = False) -> dict:
    from repro.dist.hlo_analysis import analyze_hlo
    chips = mesh_chips(mesh)
    peak = PEAK_INT8 if int8 else PEAK_BF16
    ca = compiled.cost_analysis() or {}

    # XLA's cost_analysis counts while bodies once (everything here is
    # scanned) -> use our own trip-count-aware HLO cost model instead,
    # keeping XLA's raw numbers for reference.
    cost = analyze_hlo(compiled.as_text())
    flops_dev = float(cost.flops)
    bytes_dev = float(cost.hbm_bytes)
    coll_dev = float(cost.collective_bytes)

    ma = compiled.memory_analysis()
    mem = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem[f] = int(getattr(ma, f, 0))
    hbm_dev = (mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
               + mem["temp_size_in_bytes"] - mem["alias_size_in_bytes"])

    mf = model_flops(cfg, shape)
    terms = {
        "compute_s": flops_dev / peak,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / ICI_LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    mfu = (mf / chips / peak) / bound if bound > 0 else 0.0
    return {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_kind,
        "kind": shape.kind, "chips": chips,
        "flops_per_dev": flops_dev, "bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": coll_dev,
        "collectives": {"total_bytes": coll_dev,
                        "bytes_by_kind": cost.collective_bytes_by_kind,
                        "count_by_kind": cost.collective_count_by_kind},
        "xla_cost_analysis_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))},
        "n_whiles": cost.n_whiles,
        "memory": mem, "hbm_bytes_per_dev": hbm_dev,
        "hbm_gib_per_dev": hbm_dev / 2**30,
        "model_flops_total": mf,
        "model_flops_per_dev": mf / chips,
        "useful_flop_ratio": (mf / chips) / flops_dev if flops_dev else 0.0,
        "terms": terms,
        "dominant": dominant,
        "roofline_fraction": mfu,
        "step_time_lower_bound_s": bound,
    }
