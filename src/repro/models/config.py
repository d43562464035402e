"""Model configuration - one dataclass covers the whole assigned pool.

Families: dense (GQA transformer), moe (dense + expert FFNs), ssm (a stack
of Mamba-2 layers, optionally interleaved with attention layers by
``layer_pattern``), hybrid (parallel attn+SSM heads, Hymba-style), encdec
(Whisper-style), vlm/audio (LM backbone + stub modality frontend feeding
precomputed embeddings).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    act: str = "silu"                # silu | gelu
    glu: bool = True                 # gated FFN (SwiGLU / GeGLU)
    qkv_bias: bool = False
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0                # expert hidden dim (d_ff if 0)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_every: int = 1               # every k-th layer is MoE
    moe_grouped: bool = False        # per-batch-row (EP-local) dispatch

    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_heads: int = 0               # 0 -> d_inner // ssm_head_dim
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 64

    # layer stack by kind: one period of "mamba" | "attention" layers,
    # repeated n_layers / len(layer_pattern) times (the ssm family); every
    # layer is followed by the FFN when d_ff > 0. Empty: one uniform block.
    layer_pattern: Tuple[str, ...] = ()

    # hybrid (Hymba)
    window: Optional[int] = None          # sliding window for local layers
    global_layers: Tuple[int, ...] = ()   # full-attention layer indices

    # encoder-decoder (Whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0                  # precomputed frame count (1500)

    # modality frontend stub (vlm/audio)
    frontend: Optional[str] = None        # 'vision' | 'audio'
    num_prefix_tokens: int = 0            # patch embeddings prepended

    # positions / norm
    rope_theta: float = 10_000.0
    pos: str = "rope"                     # rope | sinusoidal | none (NoPE)
    norm_eps: float = 1e-6
    logit_softcap: Optional[float] = None

    # muP multipliers (Granite): the embedding is scaled up, each residual
    # branch scaled, the softmax scale set (None -> 1/sqrt(head_dim)), the
    # logits divided; the defaults leave a model unchanged
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    # numerics / compilation
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"       # full | dots | none (hillclimb lever)
    scan_layers: bool = True

    # distribution/runtime defaults (overridable per run)
    accum_steps: int = 1                  # gradient accumulation microbatches
    opt_8bit: bool = False                # 8-bit AdamW moments
    master_fp32: bool = True              # fp32 master params

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.ssm_heads or self.d_inner // self.ssm_head_dim

    def __post_init__(self):
        if self.layer_pattern and self.n_layers % len(self.layer_pattern):
            raise ValueError(f"{self.name}: {self.n_layers} layers are not "
                             f"whole periods of {self.layer_pattern}")

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm" and "attention" not in self.layer_pattern

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run long_500k? Attention-free SSM and windowed
        hybrid: yes; one full-attention layer in the pattern makes it no."""
        return self.attention_free or (self.family == "hybrid"
                                       and self.window is not None)

    def layer_kind(self, i: int) -> str:
        """Kind of layer ``i`` of a patterned stack."""
        return self.layer_pattern[i % len(self.layer_pattern)]

    def param_count(self) -> int:
        """Analytical parameter count (for 6ND roofline math)."""
        d, v = self.d_model, self.vocab
        n = v * d + d                                 # embedding, final norm
        if not self.tie_embeddings:
            n += d * v                                      # lm head
        for i in range(self.n_layers):
            n += self._layer_params(i)
        if self.family == "encdec":
            for _ in range(self.encoder_layers):
                n += self._attn_params() + self._ffn_params() + 2 * d
            n += self.n_layers * (self._attn_params() + d)  # cross-attn
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        de = self.d_expert or self.d_ff
        per_expert = d * de * (3 if self.glu else 2)
        total = self.param_count()
        moe_layers = len([i for i in range(self.n_layers)
                          if i % self.moe_every == 0])
        return (total - moe_layers * self.n_experts * per_expert
                + moe_layers * self.top_k * per_expert)

    def _attn_params(self) -> int:
        d, hq, hkv, hd = self.d_model, self.n_heads, self.n_kv, self.hd
        return d * hq * hd + 2 * d * hkv * hd + hq * hd * d

    def _ffn_params(self) -> int:
        f = self.d_ff
        return self.d_model * f * (3 if self.glu else 2)

    def _moe_params(self) -> int:
        de = self.d_expert or self.d_ff
        per = self.d_model * de * (3 if self.glu else 2)
        return self.n_experts * per + self.d_model * self.n_experts

    def _ssm_params(self) -> int:
        d, di = self.d_model, self.d_inner
        g, nst, h = self.ssm_groups, self.ssm_state, self.n_ssm_heads
        in_proj = d * (2 * di + 2 * g * nst + h)
        conv = (di + 2 * g * nst) * (self.ssm_conv + 1)   # weights + bias
        return in_proj + conv + 3 * h + di + di * d   # A, dt_bias, D, norm, out

    def _layer_params(self, i: int) -> int:
        d = self.d_model
        if self.layer_pattern:
            mixer = (self._ssm_params() if self.layer_kind(i) == "mamba"
                     else self._attn_params())
            ffn = d + self._ffn_params() if self.d_ff else 0   # ln2 + FFN
            return d + mixer + ffn
        n = 2 * d                                          # two rmsnorms
        if self.family == "hybrid":
            return n + self._attn_params() + self._ssm_params() // 2 \
                + self._ffn_params()
        n += self._attn_params()
        if self.family == "moe" and i % self.moe_every == 0:
            n += self._moe_params()
        else:
            n += self._ffn_params()
        return n
