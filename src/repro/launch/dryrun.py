import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The 512 placeholder host devices exist ONLY for this dry-run process;
# smoke tests and benchmarks see the real single CPU device.

"""Multi-pod dry-run CLI (deliverable e).

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --mesh pod [--tag baseline] [--seq-shard] \
        [--dtype bfloat16] [--out experiments/dryrun]

Lowers + compiles the requested (architecture × input-shape × mesh) case,
prints memory_analysis() / cost_analysis(), and writes its JSON record.
``--mesh multipod`` proves the `pod` axis shards (2×16×16 = 512 chips).
"""
import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--opt", default="sgd")
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--round", choices=["dynamic", "local", "sync"],
                    default="dynamic", dest="round_kind",
                    help="train-step round specialization (perf)")
    ap.add_argument("--cache-seq-shard", action="store_true",
                    help="decode: shard the KV-cache sequence dim over model")
    ap.add_argument("--donate-cache", action="store_true",
                    help="decode: donate cache buffers (in-place update)")
    ap.add_argument("--remat-policy", choices=["full", "dots", "outs"], default="full",
                    help="train: remat policy (dots saves matmul outputs)")
    ap.add_argument("--moe-shard", action="store_true",
                    help="moe: expert-parallel dispatch sharding constraint")
    ap.add_argument("--flash-train", action="store_true",
                    help="train: blockwise (flash-style) attention path")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.dryrun_lib import DryrunCase, run_case, save_result

    case = DryrunCase(
        arch=args.arch,
        shape=args.shape,
        multi_pod=args.mesh == "multipod",
        opt_name=args.opt,
        remat=not args.no_remat,
        dtype=args.dtype,
        seq_shard=args.seq_shard,
        round_kind=args.round_kind,
        cache_seq_shard=args.cache_seq_shard,
        donate_cache=args.donate_cache,
        remat_policy=args.remat_policy,
        moe_shard=args.moe_shard,
        flash_train=args.flash_train,
        tag=args.tag,
    )
    meta = run_case(case, compile_=not args.lower_only)
    print(json.dumps(meta, indent=1, default=str))
    if not args.lower_only:
        path = save_result(meta, args.out)
        print(f"saved -> {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
