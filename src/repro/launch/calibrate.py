"""Calibration + policy-autotuning launcher (DESIGN.md §9).

  PYTHONPATH=src python -m repro.launch.calibrate --arch yi-9b --smoke \
      --batches 2 --items 64 [--trained-like] [--max-drop 0.0] \
      [--save /tmp/policy_ckpt]

Runs the full exploration loop on one arch: synthetic calibration batches
-> per-layer DSBP statistics -> synthetic BoolQ/Winogrande gold labels ->
accuracy-constrained greedy autotune -> a servable DSBPPolicy, optionally
checkpointed through ``checkpoint.store`` (reload with
``DSBPPolicy.load(dir)`` and serve via ``ServeConfig(pack_preset=policy)``).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, smoke_config
from repro.launch.compile_cache import setup_compile_cache
from repro.eval import harness
from repro.models import model as M
from repro.policy import autotune, calibrate, synthetic_calibration_batches
from repro.policy.cost import input_bitwidth_ladder


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--items", type=int, default=64)
    ap.add_argument("--margin", type=float, nargs=2, default=(1.0, 2.0),
                    help="decided-item margin floors (boolq, winogrande)")
    ap.add_argument("--ladder", type=int, nargs="+", default=(6, 4, 3, 2),
                    help="input B_fix demotion rungs, most precise first")
    ap.add_argument("--max-drop", type=float, default=0.0)
    ap.add_argument("--trained-like", action="store_true",
                    help="install trained-like projection weights "
                         "(benchmarks.common.llama_like_model_params)")
    ap.add_argument("--save", default=None,
                    help="checkpoint dir for the resulting DSBPPolicy")
    ap.add_argument("--quant-method", default="dsbp_ref",
                    help="trial-engine method (dsbp_ref is fastest on CPU)")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = (smoke_config(args.arch) if args.smoke
           else get_config(args.arch)).replace(remat=False, dtype="float32")
    if args.trained_like:
        from benchmarks.common import llama_like_model_params

        params = llama_like_model_params(cfg, 0)
    else:
        params = M.init(jax.random.PRNGKey(0), cfg)

    report = calibrate(params, cfg, synthetic_calibration_batches(
        cfg, args.batches, args.batch, args.seq, seed=0))
    print(f"calibrated {len(report.layers)} projection paths over "
          f"{report.meta['n_tokens']} tokens "
          f"({report.total_flops / 1e9:.2f} GFLOP observed)")
    for path in sorted(report.layers):
        s = report.layers[path]
        print(f"  {path:28s} K={s.k:5d} N={s.n:5d} "
              f"flop_share={report.flop_share(path):5.1%} nz={s.nz_frac:.2f}")

    tasks, golds = harness.decided_tasks(params, cfg, args.items,
                                         tuple(args.margin))
    for t, lo in zip(tasks, args.margin):
        print(f"{t.name}: {len(t.items)}/{t.meta['subset_of']} decided "
              f"items (margin >= {lo})")

    policy = autotune(params, cfg, report, tasks,
                      ladder=input_bitwidth_ladder(tuple(args.ladder)),
                      max_drop=args.max_drop,
                      quant_method=args.quant_method, log=print)
    print("\nchosen policy:")
    print(policy.summary())
    m = policy.meta["modeled"]
    print(f"modeled: avg I/W {m['avg_i']:.2f}/{m['avg_w']:.2f}, "
          f"{m['eff_tops_w']:.2f} TOPS/W; acc {policy.meta['final_acc']} "
          f"(baseline {policy.meta['baseline_acc']})")
    if args.save:
        path = policy.save(args.save, step=0)
        print(f"policy checkpoint: {path}")


if __name__ == "__main__":
    main()
