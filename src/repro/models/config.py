"""ModelConfig: one dataclass covering every assigned architecture family.

A model is a stack of blocks; each block is (mixer, ffn) where mixer is one
of  attn | attn_nc | attn_local | attn_chunked | mamba2 | rglru  and ffn is
swiglu | gelu | moe | none.  ``pattern`` is the repeating block pattern
(scan-stacked superblocks + unscanned tail), which expresses dense LMs
(P=1), RecurrentGemma's rec-rec-attn 1:2 pattern, and Llama-4's
3-chunked:1-global layout uniformly.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|ssm|hybrid|encdec|vlm|audio
    n_layers: int
    d_model: int
    vocab_size: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0                 # 0 -> d_model // n_heads
    d_ff: int = 0
    ffn_kind: str = "swiglu"          # swiglu | gelu
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 1e4
    # YaRN on the full-attention layers: (factor, original positions,
    # beta_fast, beta_slow, attention_factor); () = plain RoPE
    yarn: tuple = ()
    pos_embed: str = "none"           # none (rope) | learned
    attn_bias: bool = False
    vocab_pad: int = 256              # embedding table padded to multiple
    tie_embeddings: bool = True
    # block pattern: tuple of (mixer, ffn) tuples
    pattern: tuple = (("attn", "swiglu"),)
    window: int = 0                   # local-attention window
    chunk: int = 0                    # chunked-attention chunk
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_ff: int = 0                # shared-expert hidden (Llama-4)
    capacity_factor: float = 1.25
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssd_chunk: int = 256
    # RG-LRU
    lru_width: int = 0                # 0 -> d_model
    # encoder-decoder (whisper)
    n_enc_layers: int = 0
    enc_len: int = 0                  # fixed encoder length (1500 frames)
    # modality frontend stubs
    frontend: str = "none"            # none | audio_stub | patch_stub
    n_patches: int = 0                # VLM patches prepended to the sequence
    frontend_dim: int = 0             # stub feature dim (pre-projection)
    # numerics
    dtype: str = "bfloat16"
    norm_kind: str = "rms"            # rms | layer (whisper)
    max_seq: int = 65536              # learned-pos table length
    remat: str = "full"               # none | full | dots

    def __post_init__(self):
        if self.n_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.family in ("hybrid",) and not self.lru_width:
            object.__setattr__(self, "lru_width", self.d_model)

    # ---- derived ----
    @property
    def padded_vocab(self) -> int:
        v = self.vocab_size
        return -(-v // self.vocab_pad) * self.vocab_pad

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_super(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def n_tail(self) -> int:
        return self.n_layers % len(self.pattern)

    def layer_spec(self, i: int) -> tuple:
        return self.pattern[i % len(self.pattern)]

    def param_count(self) -> int:
        """Analytic parameter count N (used for MODEL_FLOPS = 6*N*D)."""
        d, v = self.d_model, self.padded_vocab
        total = v * d                                    # embedding
        if not self.tie_embeddings:
            total += v * d
        for i in range(self.n_layers):
            mixer, ffn = self.layer_spec(i)
            total += self._mixer_params(mixer) + self._ffn_params(ffn)
            total += 2 * d                               # norms
        if self.n_enc_layers:
            for _ in range(self.n_enc_layers):
                total += self._mixer_params("attn") + self._ffn_params(
                    self.ffn_kind) + 2 * d
            total += self.n_layers * (self._mixer_params("attn") + d)  # cross
        return total

    def _mixer_params(self, mixer: str) -> int:
        d = self.d_model
        if mixer.startswith("attn"):
            hd = self.head_dim
            return d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd \
                + self.n_heads * hd * d
        if mixer == "mamba2":
            din = self.ssm_expand * d
            gn = self.ssm_groups * self.ssm_state
            nh = din // self.ssm_head_dim
            in_dim = 2 * din + 2 * gn + nh
            return d * in_dim + din * d + self.conv_kernel * (din + 2 * gn)
        if mixer == "rglru":
            w = self.lru_width or d
            return 2 * d * w + 2 * w * w + w * d + self.conv_kernel * w
        raise ValueError(mixer)

    def _ffn_params(self, ffn: str) -> int:
        d = self.d_model
        if ffn == "none":
            return 0
        if ffn == "swiglu":
            return 3 * d * self.d_ff
        if ffn == "gelu":
            return 2 * d * self.d_ff
        if ffn == "moe":
            total = d * self.n_experts \
                + self.n_experts * 3 * d * self.moe_d_ff
            if self.shared_ff:
                total += 3 * d * self.shared_ff
            return total
        raise ValueError(ffn)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        if self.n_experts == 0:
            return self.param_count()
        d = self.d_model
        per_expert = 3 * d * self.moe_d_ff
        inactive = (self.n_experts - self.top_k) * per_expert
        n_moe_layers = sum(1 for i in range(self.n_layers)
                           if self.layer_spec(i)[1] == "moe")
        return self.param_count() - n_moe_layers * inactive
