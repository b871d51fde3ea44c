"""The one place the harness builds the program under test from a cell.

``arch_config`` turns a configuration file (``bench/configs/<name>.json``)
into the program's ``ArchConfig``; ``build_params`` hands the benchmark's
seeded float weights (``bench/weights.py``) to the program's own offline
DSBP packing, one layer per step of a single jitted ``lax.map``, so only
the packed model is ever stacked on the device.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import weights as W

__all__ = ["arch_config", "dims_of", "build_params", "packed_stats"]

# configuration-file key (the published config.json's name) -> ArchConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


def arch_config(conf: dict, preset: str | None = None,
                dtype: str | None = None):
    """The program's ``ArchConfig`` for a configuration file, as it is run:
    every size from the file, the DSBP preset and the activation dtype
    (``preset`` and ``dtype`` override the file's, for the controls)."""
    from repro.configs import get_config

    kw = {field: conf[key] for key, field in _FIELDS.items() if key in conf}
    if "head_dim" not in conf:
        kw["d_head"] = conf["hidden_size"] // conf["num_attention_heads"]
    return get_config(conf["arch"]).replace(
        **kw, quant=preset or conf["dsbp_preset"],
        dtype=dtype or conf["activation_dtype"], tie_embeddings=False)


def dims_of(cfg) -> W.Dims:
    return W.Dims(n_layers=cfg.n_layers, d_model=cfg.d_model,
                  n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                  d_head=cfg.d_head, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                  rope_theta=float(cfg.rope_theta),
                  norm_eps=float(cfg.norm_eps))


def _program_layer(w: dict) -> dict:
    return {"norm1": {"scale": w["attn_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": w["mlp_norm"]},
            "ffn": {"w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]}}


@partial(jax.jit, static_argnames=("dims", "preset"))
def _build(key, dims: W.Dims, preset: str):
    from repro.serve.engine import pack_tree

    units = jax.lax.map(
        lambda i: pack_tree(_program_layer(W.layer(key, i, dims)), preset,
                            prefix=("units", "0")),
        jnp.arange(dims.n_layers))
    return {"embed": W.embed(key, dims),
            "final_norm": {"scale": W.final_norm(key, dims)},
            "lm_head": W.head(key, dims),
            "units": [units], "tail": []}


def build_params(seed: int, cfg):
    """Packed program parameters for ``cfg`` from ``seed``, made on the
    device in one jitted call (compiled once per configuration: the key is
    an argument)."""
    if cfg.padded_vocab_size != cfg.vocab_size or cfg.pattern != ("attn_full",):
        raise ValueError(f"{cfg.name}: the benchmark's weights cover dense "
                         f"llama-style decoders with an unpadded vocabulary")
    return _build(W.seed_key(seed), dims_of(cfg), cfg.quant)


def packed_stats(params) -> dict:
    """Elements and mean stored weight width (sign included) of the packed
    projections: what the least-bytes count of ``bench/work.py`` takes."""
    from repro.core.packed import PackedDSBPWeight

    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    elems, bits_sum, groups = 0, 0.0, 0
    for leaf in jax.tree.leaves(params, is_leaf=is_pw):
        if is_pw(leaf):
            elems += math.prod(leaf.bits.shape[:-2]) * leaf.k * leaf.n
            bits_sum += float(jnp.sum(leaf.bits.astype(jnp.int32) + 1))
            groups += leaf.bits.size
    return {"elements": elems, "avg_w_bits": bits_sum / max(groups, 1)}
