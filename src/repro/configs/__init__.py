"""Architecture configs: the 10 assigned archs, the paper's Llama-7b and
Mellum2-12B-A2.5B.

Each ``<arch>.py`` exports ``CONFIG`` (the exact published geometry) and the
registry offers ``smoke_config()`` — a reduced same-family variant for CPU
tests.  Full configs are only ever lowered via ShapeDtypeStructs
(launch/dryrun.py); they are never materialized on this host.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Literal

__all__ = ["ArchConfig", "ARCH_IDS", "get_config", "smoke_config", "SHAPES",
           "ShapeSuite", "shape_applicable", "LONG_CONTEXT_OK"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: Literal["dense", "moe", "audio", "vlm", "hybrid", "ssm"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # layer pattern: repeating unit of kinds in
    #   {"attn_full", "attn_local", "rglru", "ssd"}
    pattern: tuple[str, ...] = ("attn_full",)
    window: int = 0  # sliding-window size for attn_local
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_group: int = 512  # GShard dispatch group size (tokens)
    # SSM / recurrent
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    d_conv: int = 4
    ssd_chunk: int = 256  # SSD intra-chunk length (memory/compute knob)
    rnn_width: int = 0  # RG-LRU width (0 -> d_model)
    # modality frontend stubs
    frontend: Literal["none", "audio_codebooks", "vlm_patches"] = "none"
    n_codebooks: int = 0
    n_image_tokens: int = 0
    # numerics / misc
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    # YaRN rotary scaling on the 'attn_full' layers (HF's rope_type
    # "yarn"); yarn_factor 0 leaves every layer on the default rotary
    yarn_factor: float = 0.0
    yarn_original_max_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    norm_eps: float = 1e-6
    dtype: str = "float32"  # activation/param compute dtype
    remat: bool = True
    # lax.scan unroll for the layer-stack scan.  The dry-run lowers each
    # cell at unroll=1 and unroll=2: XLA's cost_analysis counts a while-loop
    # body ONCE, so the delta gives exact per-unit FLOPs/bytes/collectives
    # to scale by n_units (repro/roofline/analysis.py).
    scan_unroll: int = 1
    # DSBP quantization preset for projections (None = bf16/f32 baseline)
    quant: str | None = None
    # quantized-linear method executing the preset — a repro.core.packed
    # registry name ('dsbp_ref', 'dsbp_kernel'); None auto-selects
    # 'dsbp_ref' when quant is set (DESIGN.md §2)
    quant_method: str | None = None
    source: str = ""

    @property
    def n_units(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def tail(self) -> tuple[str, ...]:
        return self.pattern[: self.n_layers % len(self.pattern)]

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssd_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def rnn_dim(self) -> int:
        return self.rnn_width or self.d_model

    @property
    def padded_vocab_size(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding/head shard
        over any mesh axis (mamba2's 50280 -> 50432); padded logit rows are
        masked to -inf in the head.  Standard practice (MaxText/Megatron)."""
        return -(-self.vocab_size // 256) * 256

    @property
    def is_attention_free(self) -> bool:
        return all(k == "ssd" for k in self.pattern)

    @property
    def sub_quadratic(self) -> bool:
        """True if no layer kind attends over unbounded full context...
        'attn_local' with a window and recurrent kinds are sub-quadratic;
        a single 'attn_full' in the pattern makes decode caches O(S)."""
        return "attn_full" not in self.pattern

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks), for 6ND."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        attn = d * self.n_heads * self.d_head * 2  # q, o
        attn += d * self.n_kv_heads * self.d_head * 2  # k, v
        dense_ffn = 3 * d * ff
        per_kind = {}
        per_kind["attn_full"] = per_kind["attn_local"] = attn + (
            dense_ffn if not self.n_experts else 3 * d * ff * self.n_experts + d * self.n_experts
        )
        rd = self.rnn_dim
        per_kind["rglru"] = 3 * d * rd + 2 * rd + 4 * rd + rd * self.d_conv + dense_ffn
        din, ns = self.d_inner, self.ssm_state
        nh = self.n_ssd_heads if self.ssm_state else 0
        conv_dim = din + 2 * ns
        per_kind["ssd"] = d * (2 * din + 2 * ns + nh) + conv_dim * self.d_conv + din * d + 2 * nh
        total = 0
        kinds = list(self.pattern) * self.n_units + list(self.tail)
        for k in kinds:
            total += per_kind[k] + 2 * d  # 2 norms/block
        total += v * d * (1 if self.tie_embeddings else 2)
        if self.frontend == "audio_codebooks":
            total += (self.n_codebooks - 1) * v * d * (2 if not self.tie_embeddings else 1)
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of n_experts)."""
        if not self.n_experts:
            return self.param_count()
        d, ff = self.d_model, self.d_ff
        inactive = 3 * d * ff * (self.n_experts - self.top_k)
        n_moe_layers = sum(
            1 for k in (list(self.pattern) * self.n_units + list(self.tail))
            if k.startswith("attn")
        )
        return self.param_count() - inactive * n_moe_layers


ARCH_IDS = [
    "musicgen-large",
    "gemma3-12b",
    "yi-9b",
    "deepseek-coder-33b",
    "phi3-medium-14b",
    "mixtral-8x7b",
    "grok-1-314b",
    "llava-next-34b",
    "recurrentgemma-2b",
    "mamba2-370m",
    "llama-7b-paper",
    "mellum2-12b",
]

_MODULES = {a: a.replace("-", "_") for a in ARCH_IDS}


def get_config(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise ValueError(f"unknown arch {name!r}; have {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def smoke_config(name: str) -> ArchConfig:
    """Reduced same-family config: small widths/layers, tiny vocab."""
    cfg = get_config(name)
    pat_len = len(cfg.pattern)
    n_layers = max(2 * pat_len, pat_len) + (1 if cfg.tail else 0)
    # keep the tail structure exercised when the full config has one
    if cfg.tail:
        n_layers = 2 * pat_len + len(cfg.tail)
    kw = dict(
        n_layers=n_layers,
        d_model=128,
        n_heads=4,
        n_kv_heads=max(1, min(cfg.n_kv_heads, 2)),
        d_head=32,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        window=min(cfg.window, 64) if cfg.window else 0,
        n_experts=4 if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2),
        moe_group=64,
        ssm_state=32 if cfg.ssm_state else 0,
        ssm_headdim=32,
        rnn_width=64 if cfg.rnn_width else 0,
        n_image_tokens=16 if cfg.frontend == "vlm_patches" else 0,
        remat=False,
    )
    return cfg.replace(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeSuite:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, ShapeSuite] = {
    "train_4k": ShapeSuite("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSuite("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSuite("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSuite("long_500k", 524_288, 1, "decode"),
}

# long_500k runs only for sub-quadratic archs (SSM / hybrid / windowed);
# pure full-attention archs skip it (DESIGN.md §5).
LONG_CONTEXT_OK = {"mamba2-370m", "recurrentgemma-2b", "mixtral-8x7b", "gemma3-12b"}


def shape_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k":
        return arch in LONG_CONTEXT_OK
    return True
