"""Operations and bytes, computed from shapes.

Model FLOPs count multiply-adds as two operations.  A token's layer work
is every projection it passes through plus, for attention, the scores and
the weighted sum over the positions it attends to; for RWKV, the WKV
state update and readout.  The vocabulary projection is counted once per
emitted token: the serving path projects only the position whose logits
it samples.  (The arithmetic follows ``launch/roofline.py``'s model-FLOPs
terms, kept here so that no later change to the program can move it.)
"""

from __future__ import annotations

#: WKV operations per token and head, in units of Dh^2: readout r.S (2),
#: state decay and accumulate S*w + k^T v (3).
WKV_FLOPS_PER_DH2 = 5


def _is_rwkv(sizes: dict) -> bool:
    return bool(sizes.get("rwkv", False))


def layer_flops(sizes: dict, context: int) -> float:
    """FLOPs of one token through every layer, attending to ``context``
    positions (itself included)."""
    d, f = sizes["d_model"], sizes["d_ff"]
    mlp = 3 * d * f
    if _is_rwkv(sizes):
        dh = sizes["head_dim"]
        h = d // dh
        proj = 5 * d * d + 2 * d * sizes.get("decay_lora_rank", 64)
        mix = WKV_FLOPS_PER_DH2 * h * dh * dh
        return sizes["num_layers"] * (2 * (proj + mlp) + mix)
    hq, hkv, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    proj = d * hq * hd * 2 + 2 * d * hkv * hd
    attn = 4 * hq * hd * context
    return sizes["num_layers"] * (2 * (proj + mlp) + attn)


def head_flops(sizes: dict) -> float:
    return 2 * sizes["d_model"] * sizes["vocab_size"]


def prompt_flops(sizes: dict, prompt_len: int) -> float:
    """Layer FLOPs of a whole prompt plus the one vocabulary projection
    that yields its first token."""
    return sum(layer_flops(sizes, c) for c in range(1, prompt_len + 1)) + head_flops(sizes)


def decode_flops(sizes: dict, position: int) -> float:
    """FLOPs of one decode step whose input token sits at ``position``."""
    return layer_flops(sizes, position + 1) + head_flops(sizes)


_DTYPE_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1}


def shape_bytes(dtype: str, dims) -> int:
    n = 1
    for x in dims:
        n *= int(x)
    return n * _DTYPE_BYTES[dtype]


def wkv_call_cost(operands, results) -> tuple[float, float]:
    """(FLOPs, bytes) of one WKV kernel call from its operand and result
    shapes, each a list of (dtype, dims).  Operands are r, k, v, w
    (B, H, T, Dh), u (H, Dh), S0 (B, H, Dh, Dh); the state is read once
    and written once per call, whatever T is."""
    b, h, t, dh = operands[0][1]
    flops = float(WKV_FLOPS_PER_DH2 * b * h * t * dh * dh)
    nbytes = float(sum(shape_bytes(dt, dims) for dt, dims in operands)
                   + sum(shape_bytes(dt, dims) for dt, dims in results))
    return flops, nbytes
