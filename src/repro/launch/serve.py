"""Serving launcher: DFTSP-scheduled epoch serving on a real JAX model.

The paper end-to-end: Poisson arrivals -> DFTSP batch selection under the
P1 constraints -> batched prefill + decode on the model.  Reduced configs
run on the host; the full configs are validated by the dry-run.

Usage:
  python -m repro.launch.serve --arch bloom-3b --epochs 5 --rate 10 \
      --quant W8A16 --reduced
"""
from __future__ import annotations

import argparse

from repro.config import get_arch
from repro.core.environment import paper_env, tpu_env
from repro.core.policy import get_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import ServingEngine
from repro.serving.runtime import EngineExecutor, EpochRuntime

REDUCED = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
               d_ff=512, vocab=2048)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bloom-3b")
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--rate", type=float, default=10.0)
    ap.add_argument("--scheduler", default="dftsp",
                    help="policy registry spec, e.g. dftsp, stb, "
                         "dftsp:d_sweep=false")
    ap.add_argument("--quant", default="W8A16",
                    help="env's deployed method; pass "
                         "--scheduler dftsp:quant=auto to let the "
                         "control plane pick the method per epoch")
    ap.add_argument("--bits", type=int, default=8,
                    help="engine's DEFAULT weight bits (0 = fp); "
                         "per-epoch decisions override via the "
                         "multi-precision weight cache")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tpu-env", action="store_true",
                    help="use the v5e cost model instead of the paper's")
    ap.add_argument("--batch-capacity", type=int, default=8)
    ap.add_argument("--s-max", type=int, default=64)
    ap.add_argument("--n-max", type=int, default=32)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    env_fn = tpu_env if args.tpu_env else paper_env
    env = env_fn(args.arch, args.quant)

    if args.reduced:
        red = dict(REDUCED)
        red["n_kv_heads"] = min(cfg.n_kv_heads, red["n_heads"])
        cfg = cfg.scaled(**red)
    engine = ServingEngine(cfg, batch_capacity=args.batch_capacity,
                           s_max=args.s_max, n_max=args.n_max,
                           quant_bits=args.bits)
    runtime = EpochRuntime(env, get_policy(args.scheduler),
                           EngineExecutor(engine))
    trace = runtime.run(rate=args.rate, n_epochs=args.epochs,
                        warmup_epochs=0)
    print(f"[serve] epochs={trace.epochs} served={trace.served} "
          f"tokens={trace.generated_tokens} "
          f"truncated={trace.truncated} "
          f"throughput={trace.throughput:.2f} req/s "
          f"batches={trace.batches} "
          f"methods={trace.served_by_method}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
