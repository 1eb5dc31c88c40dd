"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh) cell, in seconds (per step):

  compute    = HLO_FLOPs            / PEAK_FLOPS_BF16      (per chip)
  memory     = HLO_bytes_accessed   / HBM_BW               (per chip)
  collective = Σ collective bytes   / (ICI_BW_PER_LINK)    (per chip)

``cost_analysis()`` is per-device (the SPMD program), so no further
division by chip count.  Collective bytes are parsed from the
post-partitioning HLO text (they do not appear in cost_analysis).
"""

from __future__ import annotations

import dataclasses
import re

from repro.launch import mesh as mesh_mod

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# Result types preceding the op name, e.g.
#   %x = bf16[16,128]{1,0} all-gather(...)
#   %y = (f32[8], f32[16]) all-reduce-start(...)
#   %z = (bf16[2,8]{1,0:T(2,128)(2,1)}, u32[]{:S(2)}) collective-permute-start(
# TPU layouts nest one level of parentheses inside a tuple type.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\((?:[^()]|\([^()]*\))*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-collective-op byte totals + counts from post-SPMD HLO."""
    out = {op: {"bytes": 0, "count": 0} for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        # Skip the -done halves of async pairs (counted at -start).
        if f"{op}-done" in line:
            continue
        # Result type(s) sit inside the matched "= <type> op(" span.
        total = sum(
            _shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(m.group(0))
        )
        out[op]["bytes"] += total
        out[op]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hbm_bytes: float
    collective_bytes: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "dominant": self.dominant,
        }


def roofline_terms(cost: dict, collectives: dict) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = float(collectives.get("total_bytes", 0))
    return RooflineTerms(
        compute_s=flops / mesh_mod.PEAK_FLOPS_BF16,
        memory_s=hbm / mesh_mod.HBM_BW,
        collective_s=coll / (mesh_mod.ICI_BW_PER_LINK * mesh_mod.ICI_LINKS),
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=coll,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D (+ attention quadratic term) per step.

    train counts fwd+bwd (×3 of forward's 2ND); prefill counts forward;
    decode counts one token (D = batch tokens).
    """
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n_active * tokens
        attn = _attention_flops(cfg, shape.seq_len, shape.global_batch) * 3
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        base = 2.0 * n_active * tokens
        attn = _attention_flops(cfg, shape.seq_len, shape.global_batch)
    else:  # decode: one new token per sequence
        tokens = shape.global_batch * 1
        base = 2.0 * n_active * tokens
        attn = _decode_attention_flops(cfg, shape.seq_len, shape.global_batch)
    return base + attn


def _attention_flops(cfg, s: int, b: int) -> float:
    total = 0.0
    for kind in cfg.layer_kinds:
        if kind not in ("attn", "local", "global"):
            continue
        window = cfg.attn_window if kind == "local" else None
        eff = min(window, s) if window else s
        # 2 matmuls (QK^T and PV), causal halves the full square.
        per_q = eff if window else s / 2
        total += 2 * 2 * b * s * per_q * cfg.num_heads * cfg.head_dim
    if cfg.is_enc_dec:
        total *= 2.2  # encoder self + decoder self + cross (approx)
    return total


def _decode_attention_flops(cfg, s: int, b: int) -> float:
    total = 0.0
    for kind in cfg.layer_kinds:
        if kind in ("attn", "global"):
            total += 2 * 2 * b * s * cfg.num_heads * cfg.head_dim
        elif kind == "local":
            total += 2 * 2 * b * min(cfg.attn_window or s, s) * cfg.num_heads * cfg.head_dim
        elif kind in ("rec", "rwkv"):
            total += 2 * b * (cfg.d_rnn or cfg.d_model) * 4
    return total


def analytic_hbm_bytes(cfg, shape, n_chips: int, mode: str) -> float:
    """Fused-execution HBM traffic estimate per chip (roofline lower bound).

    ``cost_analysis()['bytes accessed']`` on the CPU-compiled module counts
    every unfused intermediate, overstating TPU traffic by ~10-100x (XLA:TPU
    fuses elementwise chains into single HBM passes; Pallas kernels keep
    block working sets in VMEM).  This model counts only irreducible HBM
    passes; the table reports both (see EXPERIMENTS.md §Roofline method).
    """
    itemsize = 2  # bf16 params/activations
    params = cfg.param_count()
    params_active = cfg.active_param_count()
    p_dev = params * itemsize / n_chips
    d = cfg.d_model
    tp = 16  # model-axis width

    if mode == "train":
        m = max(1, cfg.microbatch)
        tokens_dev = shape.global_batch * shape.seq_len / (n_chips / tp)
        # Params: per microbatch the data-axis all-gather materializes the
        # model-shard (params/tp) for fwd + bwd reads; optimizer rw in fp32.
        gathered = params * itemsize / tp
        param_traffic = m * 2 * gathered + p_dev / itemsize * (4 + 8 + 8 + 2 + 8)
        if cfg.num_experts:
            # Only routed experts' weights stream per microbatch.
            param_traffic *= params_active / params * 0.5 + 0.5
        # Activations: residual stream per layer (fwd save + bwd read +
        # recompute write/read) ~4 passes; ~6 intermediate tensors per layer
        # fused into ~3 extra passes of d-width traffic.
        act = tokens_dev * d * itemsize * cfg.num_layers * 7
        # Logits + CE in fp32 (vocab sharded over tp).
        logits = tokens_dev * cfg.vocab_size / tp * 4 * 3
        return (param_traffic + act + logits) / 1.0
    if mode == "prefill":
        tokens_dev = shape.global_batch * shape.seq_len / (n_chips / tp)
        param_traffic = params * itemsize / tp
        act = tokens_dev * d * itemsize * cfg.num_layers * 3
        logits = tokens_dev * cfg.vocab_size / tp * 4
        return param_traffic + act + logits
    # decode: every step streams active params once + reads the KV cache.
    param_traffic = params_active * itemsize / n_chips
    kv = 0.0
    for kind in cfg.layer_kinds:
        if kind in ("attn", "global"):
            s_eff = shape.seq_len
        elif kind == "local":
            s_eff = min(cfg.attn_window or shape.seq_len, shape.seq_len)
        else:
            s_eff = (cfg.d_rnn or d)  # recurrent state, not seq-length bound
            kv += shape.global_batch * s_eff * 4 * 2 / n_chips
            continue
        kv += (
            shape.global_batch * cfg.num_kv_heads * s_eff * cfg.head_dim
            * itemsize * 2 / n_chips
        )
    return param_traffic + kv
