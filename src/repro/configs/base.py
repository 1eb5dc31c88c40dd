"""Architecture configuration schema.

One :class:`ArchConfig` per assigned architecture (exact numbers from the
assignment table) plus a ``reduced()`` smoke-test variant of the same family.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

Family = Literal["dense", "moe", "hybrid", "ssm", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None          # default: d_model // num_heads

    # --- attention ---------------------------------------------------------
    qkv_bias: bool = False
    qk_norm: bool = False                # per-head RMSNorm on q/k (Qwen3)
    rope_theta: float = 10_000.0
    mrope_sections: tuple[int, ...] | None = None   # qwen2-vl M-RoPE (t,h,w)
    attn_window: int | None = None       # sliding-window size for local layers
    pattern: tuple[str, ...] = ("attn",)  # repeating layer pattern, e.g.
    #   gemma3: ("local",)*5 + ("global",)  recurrentgemma: ("rec","rec","attn")
    attn_logit_softcap: float | None = None
    attention_multiplier: float | None = None  # score scale (default
    #   1/sqrt(head_dim)); granite: a fixed 0.015625
    rope: bool = True                    # False: no position rotation (NoPE)

    # --- mlp ----------------------------------------------------------------
    mlp_type: str = "swiglu"             # swiglu | geglu | relu2
    mlp_bias: bool = False

    # --- moe ----------------------------------------------------------------
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25

    # --- recurrent ----------------------------------------------------------
    rglru: bool = False                  # RG-LRU recurrent blocks ("rec" kind)
    conv_width: int = 4                  # temporal conv in recurrent blocks
    d_rnn: int | None = None             # recurrence width (default d_model)
    rwkv: bool = False                   # RWKV6 blocks ("rwkv" kind)
    mamba_heads: int = 0                 # Mamba-2 SSD blocks ("mamba" kind):
    mamba_head_dim: int = 64             #   heads x head_dim = d_inner, one
    mamba_d_state: int = 128             #   (head_dim, d_state) state a head,
    #   one group: B and C shared by every head

    # --- encoder-decoder ----------------------------------------------------
    encoder_layers: int = 0              # >0 => enc-dec; num_layers = decoder

    # --- embeddings / misc --------------------------------------------------
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    max_seq_len: int = 524_288
    sub_quadratic: bool = False          # can run long_500k
    frontend: str | None = None          # 'vision' | 'audio' stub embeddings
    dtype: str = "bfloat16"
    # muP multipliers (granite): the embedding scale (default sqrt(d_model)),
    # the factor on both residual branches of every block, and the divisor
    # of the logits.
    embedding_multiplier: float | None = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # --- distribution defaults (overridable per run) ------------------------
    remat: str = "full"                  # none | dots | full
    microbatch: int = 1                  # grad-accumulation chunks
    prefill_chunks: int = 1              # batch-split chunks for prefill
    moe_impl: str = "gather"             # gather | a2a (shard_map all-to-all)
    attn_batch_over_model: bool = False  # shard attention batch over model
    fsdp_gather_weights: bool = False    # explicitly all-gather FSDP-
    #   sharded weights at use (ZeRO-3 weight gathering) instead of
    #   letting GSPMD all-reduce partial activations (perf variant)
    admit_rows: int = 0                  # slots one serve admission
    #   prefills, the admitted ones and masked fillers (0: every slot);
    #   more waiting requests wait for the next scheduler step
    head_pad: int = 0                    # zero-capacity extra q heads so
    #   (num_heads + head_pad) divides the TP width (perf variant)
    #   axis too (for head counts that don't divide the TP width)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.d_rnn is None:
            object.__setattr__(self, "d_rnn", self.d_model)
        if self.num_heads and self.num_heads % max(self.num_kv_heads, 1):
            raise ValueError(f"{self.name}: heads {self.num_heads} % kv {self.num_kv_heads}")

    # -- derived -------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows padded to a 128 multiple (shardable by 16).

        Standard production practice (e.g. seamless's 256206 -> 256256);
        padded logits are masked to -inf so semantics are unchanged.
        """
        return -(-self.vocab_size // 128) * 128

    @property
    def embed_scale(self) -> float:
        if self.embedding_multiplier is None:
            return self.d_model ** 0.5
        return self.embedding_multiplier

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the causal conv: x, then B and C."""
        return self.mamba_d_inner + 2 * self.mamba_d_state

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """Kind of each of the ``num_layers`` decoder layers, from pattern."""
        p = self.pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    @property
    def is_enc_dec(self) -> bool:
        return self.encoder_layers > 0

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + layers)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        attn = d * hd * nq + 2 * d * hd * nkv + hd * nq * d
        if self.mlp_type in ("swiglu", "geglu"):
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        if self.num_experts:
            mlp = mlp * self.num_experts + d * self.num_experts
        rec = 0
        if self.rglru:
            dr = self.d_rnn
            rec = 2 * d * dr + dr * d + self.conv_width * dr + 3 * dr
        if self.rwkv:
            rec = 6 * d * d
        if self.mamba_heads:
            di, nh = self.mamba_d_inner, self.mamba_heads
            cd = self.mamba_conv_dim
            rec = (d * (di + cd + nh) + self.conv_width * cd + cd + 3 * nh
                   + di + di * d)
        total = 0
        for kind in self.layer_kinds:
            if kind in ("attn", "local", "global"):
                total += attn + mlp
            elif kind == "rec":
                total += rec + mlp
            elif kind in ("rwkv", "mamba"):
                total += rec + mlp
        if self.is_enc_dec:
            # encoder self-attn + mlp, decoder already counted + cross-attn
            total += self.encoder_layers * (attn + mlp)
            total += self.num_layers * attn  # cross-attention
        total += v * d * (1 if self.tie_embeddings else 2)
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts)."""
        if not self.num_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        full_mlp = (3 if self.mlp_type in ("swiglu", "geglu") else 2) * d * f
        inactive = (self.num_experts - self.num_experts_per_tok) * full_mlp
        return self.param_count() - inactive * self.num_layers

    def reduced(self) -> "ArchConfig":
        """Smoke-test configuration of the same family (CPU-friendly)."""
        kv = max(1, min(self.num_kv_heads, 2))
        heads = max(kv, min(self.num_heads, 4))
        heads = (heads // kv) * kv
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            # Two periods of the pattern; one of a hybrid's ten-layer period.
            num_layers=min(self.num_layers,
                           len(self.pattern) * (1 if self.mamba_heads else 2)),
            d_model=128,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=128 // heads if 128 % heads == 0 else 32,
            d_ff=256,
            vocab_size=512,
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            num_experts_per_tok=min(self.num_experts_per_tok, 2)
            if self.num_experts
            else 0,
            d_rnn=128,
            mamba_heads=4 if self.mamba_heads else 0,
            mamba_head_dim=32,
            mamba_d_state=16,
            encoder_layers=min(self.encoder_layers, 2),
            max_seq_len=512,
            mrope_sections=(8, 4, 4) if self.mrope_sections else None,
            attn_window=min(self.attn_window, 64) if self.attn_window else None,
            dtype="float32",
            remat="none",
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}
