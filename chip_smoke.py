#!/usr/bin/env python3
"""Chip smoke test: yi-9b at its published widths, served on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # (data=1, model=4) mesh vs one chip

Builds yi-9b (48 layers, d_model 4096, vocab 64000; ``configs/yi_9b.py``)
with random weights from ``--seed``, packed layer by layer under the
``precise`` DSBP preset, and drives the main serving path through
``serve.Engine`` with its compiled Pallas kernels.  Activations are
float32: dense/paged token parity is a float32 invariant (with bfloat16
activations the two schedulers already diverge on the CPU, where a
request's logits depend on which requests share its prefill batch).

  1. correctness — prefill logits of one short prompt through the fused
     kernel (``dsbp_fused``) against the jnp reference (``dsbp_ref``) on
     the same packed containers, the reference at matmul precision
     "highest"; fails when ``max|fused - ref| / max|ref|`` exceeds
     ``LOGIT_REL_TOL``;
  2. serving — six requests (prompts of 32-512 tokens, 16 new tokens
     each) through the dense scheduler, then the same requests through
     the paged one (``ServeConfig(paged=True)``) with whole-prompt
     admissions, then through the paged one with its default chunked
     prefill; every request must end with status ``ok``, and the dense
     and whole-prompt paged schedulers must agree token for token.

The chunked run is held to status and length only; how many leading
tokens it shares with the dense run is printed.  A chunked prefill
attends through the verify path (softmax over cache and fresh keys,
then two value products) where a whole prompt takes one blockwise pass,
and a prompt admitted in another group is padded to another length and
batched with other rows.  Each of these moves the last bits of some
activations; 48 layers of random weights carry that through flipped FP8
inputs into the logits (a 51-token prompt prefilled alone rather than
beside a 180-token one: logits moved by 0.22-0.31 of a largest 4.2 on a
TPU v5e, 1e-6 at the CPU smoke size) and then into the argmax.
Whole-prompt admission gives the paged scheduler the dense one's prefill
trunk on the same groups, so equal tokens are owed there.

``--four-chips`` runs only the mesh path: the six requests through an
``Engine`` on a (data=1, model=4) mesh, then on a one-device ``Engine`` in
the same process, with token parity between them.

Every time printed is a single cold run.  The last line of standard output
is ``{"ok": true, "device": {...}}``; any failure raises, so the script
exits nonzero and prints no such line.  Without a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Relative logit error the fused kernel may show against the reference.
# The fused GEMM's integer products are exact on the MXU too (Precision
# HIGHEST); what may differ is the f32 accumulation order, which moves a
# GEMM output by about an f32 ulp.  Where that straddles an FP8 rounding
# boundary at the next projection's input, one element moves by one E4M3
# step (2**-4 relative) and its 64-group may re-align.  Such isolated
# flips, carried through 48 layers, stay well under 5% of the logit range;
# a wrong group scale, lane order or K tile gives errors of the order of
# the logits themselves, and so does running attention or the LM head at
# the TPU's default (bfloat16) precision: 0.118 on a v5e.
LOGIT_REL_TOL = 5e-2

N_REQUESTS = 6
NEW_TOKENS = 16
PROMPT_LENS = (32, 512)
CORRECTNESS_PROMPT = 32


def make_requests(cfg, seed: int, n: int = N_REQUESTS,
                  lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    sizes = rng.integers(lens[0], lens[1] + 1, n)
    return [Request(uid=i, tokens=rng.integers(0, cfg.vocab_size, (int(s),)),
                    max_new_tokens=new_tokens)
            for i, s in enumerate(sizes)]


def max_len_for(requests, block: int = 16) -> int:
    longest = max(len(r.tokens) + r.max_new_tokens for r in requests)
    return -(-longest // block) * block


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def build(cfg, seed: int, mesh=None):
    """Packed yi-9b params, built layer by layer (no float model)."""
    import jax
    from repro.core.packed import PackedDSBPWeight, packed_nbytes
    from repro.serve.engine import init_packed

    t0 = time.perf_counter()
    params, stats = init_packed(jax.random.PRNGKey(seed), cfg,
                                cfg.quant, mesh=mesh)
    jax.block_until_ready(params)
    is_pw = lambda x: isinstance(x, PackedDSBPWeight)
    packed = sum(l.nbytes for l in jax.tree.leaves(params, is_leaf=is_pw)
                 if is_pw(l))
    print(f"build: {stats['layers_packed']} packed projection stacks, "
          f"packed-weight bytes {packed}, all parameter bytes "
          f"{packed_nbytes(params)}, avg weight bits "
          f"{stats['avg_w_bits']:.4f}, {time.perf_counter() - t0:.1f}s "
          f"(single cold run)", flush=True)
    return params


def check_fused_vs_ref(params, cfg, seed: int, tol: float = LOGIT_REL_TOL):
    """Prefill logits, fused kernel vs jnp reference, same containers."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (1, CORRECTNESS_PROMPT)), jnp.int32)

    def logits_fn(c):
        return jax.jit(lambda p, t: M.prefill(p, {"tokens": t}, c,
                                              max_len=CORRECTNESS_PROMPT)[0])

    t0 = time.perf_counter()
    fused = logits_fn(cfg.replace(quant_method="dsbp_fused")).lower(
        params, toks).compile()
    compile_s = time.perf_counter() - t0
    if (jax.default_backend() == "tpu"
            and "tpu_custom_call" not in fused.as_text()):
        raise RuntimeError("the fused prefill holds no compiled Pallas kernel")
    got = np.asarray(fused(params, toks), np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(logits_fn(cfg.replace(quant_method="dsbp_ref"))(
            params, toks), np.float32)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        raise RuntimeError("non-finite prefill logits")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    rel = err / scale
    print(f"fused vs ref prefill logits ({got.shape}): max abs err {err:.6g}, "
          f"max rel err {rel:.6g} (of max |ref| {scale:.6g}; tol {tol}), "
          f"argmax agree {bool((got.argmax(-1) == ref.argmax(-1)).all())}; "
          f"fused prefill compile {compile_s:.1f}s (single cold run)",
          flush=True)
    if not rel <= tol:
        raise RuntimeError(f"fused vs ref relative error {rel} > {tol}")


def serve(params, cfg, requests, label: str, **scfg_kw):
    """Serve ``requests`` through a fresh Engine; all must end 'ok'."""
    from repro.serve.engine import Engine, ServeConfig

    eng = Engine(params, cfg, ServeConfig(max_len=max_len_for(requests),
                                          batch_size=4, **scfg_kw))
    t0 = time.perf_counter()
    out = eng.serve(requests, max_new_tokens=NEW_TOKENS)
    wall = time.perf_counter() - t0
    status = eng.last_stats["request_status"]
    bad = {u: s for u, s in status.items() if s != "ok"}
    if bad or len(out) != len(requests):
        raise RuntimeError(f"{label}: requests not ok: {bad}")
    for r in requests:
        toks = out[r.uid]
        if len(toks) != r.max_new_tokens:
            raise RuntimeError(f"{label}: request {r.uid} returned "
                               f"{len(toks)} tokens")
    print(f"{label}: {len(requests)} requests ok, "
          f"{eng.last_stats['decode_steps']} decode steps, serve wall "
          f"{wall:.1f}s (single cold run, compiles included)", flush=True)
    return {r.uid: np.asarray(out[r.uid]) for r in requests}


def require_parity(a: dict, b: dict, what: str):
    diff = [u for u in a if not np.array_equal(a[u], b[u])]
    if diff:
        raise RuntimeError(f"{what}: token mismatch for requests {diff}: "
                           f"{[(a[u].tolist(), b[u].tolist()) for u in diff]}")
    print(f"{what}: token-for-token parity over {len(a)} requests", flush=True)


def leading_agreement(a, b) -> int:
    """How many leading tokens two streams share."""
    diff = np.flatnonzero(a != b)
    return int(diff[0]) if diff.size else len(a)


def run_one_chip(cfg, seed: int, requests):
    import jax

    params = build(cfg, seed)
    check_fused_vs_ref(params, cfg, seed)
    dense = serve(params, cfg, requests, "dense scheduler")
    longest = max(len(r.tokens) for r in requests)
    paged = serve(params, cfg, requests, "paged scheduler", paged=True,
                  chunk_prefill_tokens=longest)
    require_parity(dense, paged, "dense vs paged")
    chunked = serve(params, cfg, requests, "paged scheduler, chunked "
                    "prefill", paged=True)
    print("dense vs chunked paged: leading tokens equal per request "
          f"{[leading_agreement(dense[u], chunked[u]) for u in dense]} "
          f"of {NEW_TOKENS}", flush=True)
    print(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}", flush=True)


def run_four_chips(cfg, seed: int, requests):
    import jax
    from repro.parallel.sharding import make_mesh

    shape, axes = (1, 4), ("data", "model")
    mesh = make_mesh(shape, axes, devices=jax.devices()[:4])
    params = build(cfg, seed, mesh=mesh)
    sharded = serve(params, cfg, requests, "mesh (data=1, model=4)",
                    mesh_shape=shape, mesh_axes=axes)
    print("per-device peak_bytes_in_use: "
          f"{[peak_bytes(d) for d in jax.devices()[:4]]}", flush=True)
    del params
    params = build(cfg, seed)
    single = serve(params, cfg, requests, "one device")
    require_parity(sharded, single, "mesh vs one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=1, model=4) mesh path and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind}, count {len(devices)}",
          flush=True)

    from repro.configs import get_config
    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}", flush=True)
    cfg = get_config("yi-9b").replace(quant="precise", remat=False)
    requests = make_requests(cfg, args.seed)
    print(f"yi-9b: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}; prompts {[len(r.tokens) for r in requests]}",
          flush=True)
    if args.four_chips:
        run_four_chips(cfg, args.seed, requests)
    else:
        run_one_chip(cfg, args.seed, requests)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
