"""Builds the program under test from a mixture-of-experts configuration.

``moe_config`` completes the ``ArchConfig`` that ``bench/program.py`` makes
from the file with what that reader does not map: the expert width
(``moe_intermediate_size``; the file's ``intermediate_size`` is the
published dense width, which no layer of these models uses), the experts,
the layer pattern, the sliding window and the rotary parameters of each
layer kind.  ``build_params`` hands the seeded float weights
(``bench/weights_moe.py``) to the program's own offline DSBP packing, one
layer per step of a jitted ``lax.map`` and the experts a slice at a time,
so only the packed model is ever stacked on the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import program, weights_moe as WM

__all__ = ["moe_config", "dims_of", "build_params", "packed_stats",
           "EXPERT_SLICE"]

# experts packed per step: the float slice and its packing temporaries
# stay near a gigabyte at Mellum2's widths
EXPERT_SLICE = 8

_KINDS = {"sliding_attention": "attn_local", "full_attention": "attn_full"}


def moe_config(cfg, conf: dict):
    """``cfg`` (from ``program.arch_config``) with the file's experts,
    layer kinds, window and per-kind rotary parameters."""
    kinds = [_KINDS[t] for t in conf["layer_types"]]
    period = len(cfg.pattern)
    pattern = tuple(kinds[:period])
    if kinds != list(pattern) * (len(kinds) // period):
        raise ValueError(f"{conf['name']}: layer_types do not repeat the "
                         f"arch's period of {period}")
    if len(kinds) < conf["num_hidden_layers"]:
        raise ValueError(f"{conf['name']}: fewer layer_types than layers")
    rope = conf["rope_parameters"]
    local, full = rope["sliding_attention"], rope["full_attention"]
    if local["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError(f"{conf['name']}: expects default rotary on "
                         f"sliding layers and yarn on full ones")
    if local["rope_theta"] != full["rope_theta"]:
        raise ValueError(f"{conf['name']}: one rope_theta for both kinds")
    return cfg.replace(
        d_ff=conf["moe_intermediate_size"], n_experts=conf["num_experts"],
        top_k=conf["num_experts_per_tok"], pattern=pattern,
        window=conf["sliding_window"], rope_theta=float(local["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_len=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]))


def dims_of(cfg) -> WM.Dims:
    yarn = ((cfg.yarn_factor, cfg.yarn_original_max_len, cfg.yarn_beta_fast,
             cfg.yarn_beta_slow, cfg.yarn_attention_factor)
            if cfg.yarn_factor else None)
    return WM.Dims(n_layers=cfg.n_layers, d_model=cfg.d_model,
                   n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                   d_head=cfg.d_head, d_ff=cfg.d_ff,
                   n_experts=cfg.n_experts, top_k=cfg.top_k,
                   vocab=cfg.vocab_size, rope_theta=float(cfg.rope_theta),
                   norm_eps=float(cfg.norm_eps), pattern=tuple(cfg.pattern),
                   window=cfg.window, yarn=yarn)


def _packed_experts(key, i, dims: WM.Dims, preset: str) -> dict:
    """Layer ``i``'s experts packed a slice at a time: gate and up side by
    side as one (E, d, 2f) container, down as (E, f, d)."""
    from repro.core.quantized import pack_weights

    n = min(EXPERT_SLICE, dims.n_experts)
    assert dims.n_experts % n == 0, (dims.n_experts, n)

    def pack_slice(first):
        w = WM.experts(key, i, dims, first, n)
        return {"w13": pack_weights(
                    jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1),
                    preset),
                "w2": pack_weights(w["w_down"], preset)}

    slices = jax.lax.map(pack_slice, jnp.arange(0, dims.n_experts, n))
    return jax.tree.map(lambda a: a.reshape(dims.n_experts, *a.shape[2:]),
                        slices)


def _program_layer(key, i, dims: WM.Dims, preset: str) -> dict:
    from repro.serve.engine import pack_tree

    w = WM.layer(key, i, dims)
    attn = pack_tree({k: w[k] for k in ("wq", "wk", "wv", "wo")}, preset)
    return {"norm1": {"scale": w["attn_norm"]}, "attn": attn,
            "norm2": {"scale": w["mlp_norm"]},
            "moe": {"router": w["router"],
                    **_packed_experts(key, i, dims, preset)}}


@partial(jax.jit, static_argnames=("dims", "preset"))
def _build(key, dims: WM.Dims, preset: str):
    period = len(dims.pattern)
    units = [jax.lax.map(
                 lambda u, li=li: _program_layer(key, u * period + li, dims,
                                                 preset),
                 jnp.arange(dims.n_layers // period))
             for li in range(period)]
    return {"embed": WM.embed(key, dims),
            "final_norm": {"scale": WM.final_norm(key, dims)},
            "lm_head": WM.head(key, dims),
            "units": units, "tail": []}


def build_params(seed: int, cfg):
    """Packed program parameters for ``cfg`` from ``seed``, made on the
    device in one jitted call (compiled once per configuration: the key is
    an argument).  Pattern position ``li`` of unit ``u`` is layer
    ``u * len(pattern) + li``, the order the reference runs them in."""
    if cfg.padded_vocab_size != cfg.vocab_size or cfg.tail:
        raise ValueError(f"{cfg.name}: the benchmark's MoE weights cover "
                         f"whole periods and an unpadded vocabulary")
    return _build(WM.seed_key(seed), dims_of(cfg), cfg.quant)


def packed_stats(params) -> dict:
    """``program.packed_stats`` over every packed projection, and the mean
    stored width of the experts' containers alone
    (``expert_avg_w_bits``)."""
    out = program.packed_stats(params)
    experts = [u["moe"] for u in params["units"]]
    out["expert_avg_w_bits"] = program.packed_stats(experts)["avg_w_bits"]
    return out
