"""Architecture configuration schema.

Every assigned architecture gets a ``ModelConfig`` at its published size
plus a ``reduced()`` variant (<=2 layers, d_model<=512, <=4 experts) that the
CPU smoke tests instantiate for real.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    # identity -----------------------------------------------------------
    name: str = "model"
    family: str = "dense"          # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""               # citation for the config numbers

    # trunk dimensions ----------------------------------------------------
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0              # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # attention features --------------------------------------------------
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None     # pre-softmax logit softcap
    final_softcap: Optional[float] = None    # lm-head logit softcap
    sliding_window: Optional[int] = None     # SWA width (None = full)
    local_global: bool = False               # gemma2: alternate local/global
    causal: bool = True                      # False => encoder-only

    # MoE ------------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    router_aux_coef: float = 0.01
    moe_capacity_factor: float = 1.25
    # Routing-block size R (0 = whole sequence): capacity competition is
    # confined to R-token blocks at absolute positions, making routing
    # independent of batch composition AND of prefill chunking whenever
    # chunk boundaries land on multiples of R.
    moe_route_block: int = 0
    # DeepSeek-V3-style experts (``models/moe.py::routed_moe_apply``):
    # sigmoid scores, a fixed choice-only bias, normalised top-k gates
    # times ``routed_scale``, ``num_shared_experts`` always-on experts;
    # this chip holds ``experts_held`` of the ``num_experts`` the router
    # scores (0: all); the first ``first_dense_layers`` layers are dense
    # with MLP width ``dense_d_ff`` (``d_ff`` is then the expert width)
    num_shared_experts: int = 0
    routed_scale: float = 1.0
    experts_held: int = 0
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    router_bias_std: float = 0.0             # the seeded choice bias

    # latent attention (MLA) -------------------------------------------------
    kv_lora_rank: int = 0                    # 0: not latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM / hybrid ---------------------------------------------------------
    # block pattern within one "super-block"; the stack is
    # num_super * len(pattern) layer applications.  "attn_shared" entries all
    # reuse ONE weight set (zamba2-style shared block).
    block_pattern: Tuple[str, ...] = ()      # e.g. ("mlstm",)*7 + ("slstm",)
    num_super: int = 0
    ssm_state_dim: int = 0
    ssm_expansion: int = 2         # inner-dim expansion of recurrent blocks
    conv_width: int = 4

    # modality frontend stubs ----------------------------------------------
    frontend: Optional[str] = None           # "audio" | "vision"
    frontend_feat_dim: int = 0               # raw embedding dim fed by stub
    num_patches: int = 0                     # vision: patches per request

    # misc -------------------------------------------------------------------
    act: str = "silu"
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    dtype: str = "float32"                   # compute dtype

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Flat sequence of per-layer block kinds for the whole stack."""
        if self.block_pattern:
            return tuple(self.block_pattern) * self.num_super
        return ("attn",) * self.num_layers

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # rough parameter count (embedding + trunk), for config sanity tests ----
    def approx_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        q = self.num_heads * hd
        kv = self.num_kv_heads * hd
        n = 0
        n += v * d                                   # embed
        if not self.tie_embeddings:
            n += v * d                               # unembed
        per_attn = d * q + 2 * d * kv + q * d
        per_mlp = 3 * d * f if self.act in ("silu", "swiglu") else 2 * d * f
        if self.num_experts:
            per_mlp *= self.num_experts
            per_mlp += d * self.num_experts          # router
        for kind in self.layer_kinds:
            if kind in ("attn", "attn_shared"):
                n += per_attn + per_mlp if kind == "attn" else 0
            elif kind == "mlstm":
                n += 2 * d * (2 * d) + 2 * d * d     # up/gate + qkv-ish + down
            elif kind == "slstm":
                n += 8 * d * d // 4
            elif kind == "mamba2":
                n += 2 * d * (2 * d) + d * self.ssm_state_dim * 4
        if "attn_shared" in self.layer_kinds:
            n += per_attn + per_mlp                  # one shared copy
        return n


@dataclass(frozen=True)
class InputShape:
    """A workload shape: sequence length, global batch and step mode."""
    name: str
    seq_len: int
    global_batch: int
    mode: str        # "train" | "prefill" | "decode"

