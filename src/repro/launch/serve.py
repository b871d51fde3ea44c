"""Production serving launcher: continuous batching under a mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 8 [--packed] [--ragged]

``--ragged`` draws mixed-length prompts (2 per slot) and runs them through
the ``Engine.serve`` slot scheduler — per-request generations, slot reuse
and occupancy stats — instead of one uniform ``generate`` batch.

``--spec-k K`` serves speculatively (DESIGN.md §10): each pool step drafts
K tokens per slot with the MSB-slice view of the packed weights
(``--spec-draft-bits``), verifies them in one batched target forward and
commits the longest matching greedy prefix.  Token-for-token identical to
the non-speculative stream; implies the slot-scheduler (--ragged) path.

``--mesh DxM[xE]`` serves multi-device (DESIGN.md §11): a (data, model[,
expert]) mesh — weights pack straight into per-shard kernel layouts, every
projection runs the fused GEMM under shard_map (bit-exact vs one device),
KV caches shard over the batch axes.  With ``--per-device-batch B`` the
slot pool scales to ``mesh.size * B`` slots instead of the flat --batch.
On CPU, simulate devices first:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M
from repro.serve.engine import (Engine, Request, ServeConfig, init_packed,
                                packed_nbytes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--packed", action="store_true",
                    help="serve pack-once DSBP int8 weights (quantized path)")
    ap.add_argument("--preset", default="precise")
    ap.add_argument("--ragged", action="store_true",
                    help="mixed-length prompts through the slot scheduler")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative serving: draft tokens per pool step "
                         "(0 = off; implies the --ragged scheduler path)")
    ap.add_argument("--spec-draft-bits", type=int, default=4,
                    help="aligned-mantissa bits of the MSB-slice draft view")
    ap.add_argument("--mesh", default=None, metavar="DxM[xE]",
                    help="serve on a (data, model[, expert]) device mesh, "
                         "e.g. '2x4': sharded packed containers + fused "
                         "GEMM under shard_map, bit-exact vs one device "
                         "(DESIGN.md §11).  Needs prod(mesh) <= "
                         "jax.device_count(); on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N before "
                         "launch")
    ap.add_argument("--per-device-batch", type=int, default=None,
                    help="scale the slot pool to mesh.size * B slots "
                         "(device-scaled continuous batching; default: "
                         "keep the flat --batch pool)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (DESIGN.md §12): block-pool "
                         "storage, per-lane block tables, copy-on-write "
                         "prefix sharing and chunked prefill; token-for-"
                         "token identical to the dense scheduler (implies "
                         "--ragged)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="ring slots per physical KV block (must divide "
                         "every KV layer's cache length)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="physical blocks in the pool incl. scratch "
                         "(default: --batch dense slots' worth — same KV "
                         "HBM budget as the dense engine)")
    ap.add_argument("--max-active", type=int, default=None,
                    help="paged lane count; with prefix sharing this can "
                         "exceed --batch at the same --kv-blocks budget")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="scheduler iterations a request may stay resident "
                         "after admission before it is released with status "
                         "'deadline' (robustness layer, DESIGN.md §13; "
                         "implies --ragged)")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority of every EVEN-indexed request (odd stay "
                         "0): higher admits first and, on the paged "
                         "scheduler, preempts strictly-lower lanes under "
                         "pool pressure (implies --ragged)")
    ap.add_argument("--kv-quant", default=None,
                    help="DSBP-quantized KV cache (DESIGN.md §14): a "
                         "KV_PRESETS name ('kv8' is the token-parity "
                         "8-bit preset, 'kv6'/'kv4' trade accuracy for "
                         "bytes); K/V quantize at cache-write time into "
                         "int8 aligned mantissas + pow2 group scales")
    ap.add_argument("--kv-bits", type=int, default=None,
                    help="uniform KV bitwidth shorthand in [2, 8] "
                         "(alternative to --kv-quant; set one, not both)")
    ap.add_argument("--kv-draft-bits", type=int, default=None,
                    help="with --spec-k and a packed KV cache: draft over "
                         "an MSB-slice view of the cached mantissas at "
                         "this width (served tokens unchanged; only "
                         "acceptance can move)")
    ap.add_argument("--observe", action="store_true",
                    help="observability layer (DESIGN.md §15): per-request "
                         "lifecycle spans, a metrics registry and guard "
                         "telemetry; prints a per-request TTFT/total/tok-s "
                         "summary (implies --ragged)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the obs registry + health snapshot as JSON "
                         "after serving (implies --observe)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump the Chrome trace-event timeline after "
                         "serving — open in Perfetto / chrome://tracing "
                         "(implies --observe)")
    ap.add_argument("--numeric-guard", default=None,
                    choices=["off", "fail-fast", "quarantine-lane",
                             "fallback"],
                    help="per-step isfinite guard on sampling logits: "
                         "fail-fast raises, quarantine-lane releases the "
                         "bad lane with partial output, fallback retries "
                         "the step through the dsbp_ref reference path "
                         "(DESIGN.md §13)")
    args = ap.parse_args()
    setup_compile_cache()
    if args.deadline_steps or args.priority:
        args.ragged = True  # per-request lifecycle lives in serve()
    if args.spec_k or args.paged:
        args.ragged = True  # both live in the serve() scheduler
    if args.metrics_json or args.trace:
        args.observe = True
    if args.observe:
        args.ragged = True  # the recorder hooks live in serve()

    cfg = (smoke_config(args.arch) if args.smoke
           else get_config(args.arch).replace(dtype="bfloat16")).replace(remat=False)
    key = jax.random.PRNGKey(0)
    pack_stats = None
    if args.packed:
        # packed layer by layer: at published widths the float model may
        # not fit the device (yi-9b: 17.7 GB in bf16 on a 16 GB v5e)
        cfg = cfg.replace(quant=args.preset)
        params, pack_stats = init_packed(key, cfg, args.preset)
    else:
        params = M.init(key, cfg)

    mesh_shape = mesh_axes = None
    if args.mesh:
        mesh_shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        mesh_axes = ("data", "model", "expert")[: len(mesh_shape)]
    max_len = args.prompt_len + args.new_tokens + args.spec_k + 8
    if args.paged:  # block pools need block-aligned ring lengths
        max_len = -(-max_len // args.kv_block_size) * args.kv_block_size
    eng = Engine(params, cfg, ServeConfig(
        max_len=max_len,
        batch_size=args.batch, spec_k=args.spec_k,
        spec_draft_bits=args.spec_draft_bits,
        mesh_shape=mesh_shape,
        mesh_axes=mesh_axes or ("data", "model"),
        per_device_batch_size=args.per_device_batch,
        paged=args.paged, kv_block_size=args.kv_block_size,
        kv_blocks=args.kv_blocks, max_active=args.max_active,
        kv_quant=args.kv_quant, kv_bits=args.kv_bits,
        kv_draft_bits=args.kv_draft_bits,
        numeric_guard=args.numeric_guard,
        observe=args.observe))
    if eng.kv_spec is not None:
        # pool-size report from the ACTUAL cache leaf dtypes (int8
        # mantissas + f32 scales), not the float layout it replaces
        from repro.kvq import kv_cache_nbytes

        pool = M.init_cache(cfg, args.batch, max_len)
        packed_pool = M.init_cache(cfg, args.batch, max_len, kv=eng.kv_spec)
        fb, qb = kv_cache_nbytes(pool), kv_cache_nbytes(packed_pool)
        print(f"packed KV cache ({eng.kv_spec}): {fb/1e6:.2f} -> "
              f"{qb/1e6:.2f} MB for {args.batch} x {max_len} slots "
              f"({fb/max(qb, 1):.2f}x)")
    if args.paged:
        print(f"paged KV: {eng.kv_blocks} blocks x {args.kv_block_size} "
              f"slots, {eng.lanes} lanes, table width {eng._table_width}")
    if eng.mesh is not None:
        print(f"mesh {dict(eng.mesh.shape)} over {eng.mesh.size} devices, "
              f"slot pool {eng.pool_size}")
    if pack_stats:
        print(f"packed weights: {packed_nbytes(eng.params)/1e6:.1f} MB "
              f"(avg W bits {pack_stats['avg_w_bits']:.2f}, preset "
              f"{args.preset})")
    rng = np.random.default_rng(0)
    if args.ragged:
        lens = rng.integers(args.prompt_len // 2, args.prompt_len + 1,
                            2 * args.batch)
        reqs = [Request(uid=i,
                        tokens=rng.integers(0, cfg.vocab_size, (int(l),)),
                        max_new_tokens=args.new_tokens,
                        priority=args.priority if i % 2 == 0 else 0,
                        deadline_steps=args.deadline_steps)
                for i, l in enumerate(lens)]
        t0 = time.monotonic()
        out = eng.serve(reqs, max_new_tokens=args.new_tokens)
        dt = time.monotonic() - t0
        st = eng.last_stats
        tps = st["decode_tokens"] / dt
        print(f"served {st['requests']} ragged requests (lens {lens.tolist()}) "
              f"in {dt:.2f}s ({tps:.1f} tok/s, "
              f"occupancy {st['occupancy']*100:.0f}%, "
              f"{st['decode_steps']} pool steps, "
              f"{st['kv_bytes_per_token']:.0f} KV B/token"
              f"{' packed' if st['kv_packed'] else ''})")
        if args.spec_k:
            per_slot = ("" if args.paged else
                        f", per-slot "
                        f"{[round(a, 2) for a in st['slot_mean_accepted']]}")
            print(f"speculation: {st['spec_rounds']} rounds, mean accepted "
                  f"{st['mean_accepted']:.2f}/{args.spec_k + 1} "
                  f"(hist {st['accepted_hist']}{per_slot})")
        if args.paged:
            print(f"block pool: peak {st['block_peak_used']}/"
                  f"{max(st['kv_blocks'] - 1, 1)} used "
                  f"({st['block_utilization']*100:.0f}%), "
                  f"{st['shared_blocks_peak']} shared at peak, "
                  f"{st['prefix_hit_blocks']} prefix hits "
                  f"({st['bytes_saved_sharing']/1e6:.2f} MB KV not "
                  f"re-materialized), {st['cow_splits']} COW splits, "
                  f"{st['chunk_steps']} chunk steps "
                  f"({st['chunked_requests']} chunked requests), "
                  f"{st['stalled_decode_steps']} stalled decode steps")
        if args.deadline_steps or args.priority or args.numeric_guard:
            by_state: dict = {}
            for s in st["request_status"].values():
                by_state[s] = by_state.get(s, 0) + 1
            print(f"lifecycle: {by_state} "
                  f"(deadline_expired {st['deadline_expired']}, "
                  f"quarantined {st['quarantined']}, "
                  f"preemptions {st['preemptions']}, "
                  f"guard_checks {st['guard_checks']})")
        if args.observe:
            summ = eng.obs.request_summary()
            for uid in sorted(summ, key=str):
                s = summ[uid]
                ttft = (f"{s['ttft_s'] * 1e3:7.1f}ms"
                        if s["ttft_s"] is not None else "      -")
                total = (f"{s['total_s'] * 1e3:7.1f}ms"
                         if s["total_s"] is not None else "      -")
                print(f"  req{uid}: {str(s['status']):<11} ttft {ttft}  "
                      f"total {total}  {s['tokens']:>3} tok  "
                      f"{s['tok_s']:6.1f} tok/s")
        if args.metrics_json:
            eng.obs.save_metrics(args.metrics_json)
            print(f"metrics snapshot -> {args.metrics_json}")
        if args.trace:
            eng.obs.save_trace(args.trace)
            print(f"chrome trace ({len(eng.obs.trace.events)} events) -> "
                  f"{args.trace} (open in Perfetto / chrome://tracing)")
        for uid in list(out)[:2]:
            print(f"  req{uid}: {out[uid].tolist()}")
        return
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
    t0 = time.monotonic()
    out = eng.generate(prompts, args.new_tokens)
    dt = time.monotonic() - t0
    tps = args.batch * args.new_tokens / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    for b in range(min(2, args.batch)):
        print(f"  seq{b}: {out[b].tolist()}")


if __name__ == "__main__":
    main()
