"""Serving entrypoint: continuous-batching engine demo.

    PYTHONPATH=src python -m repro.launch.serve --arch lidc-demo \
        --requests 8 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="lidc-demo")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from ..configs.base import get_config, smoke_of
    from ..kernels.ops import get_impl
    from ..models import bundle_for
    from ..serve.engine import ServeEngine
    from .compile_cache import configure_compile_cache

    configure_compile_cache()
    dev = jax.devices()[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} kernels={get_impl()}")

    cfg = smoke_of(args.arch) if args.smoke else get_config(args.arch)
    bundle = bundle_for(cfg)
    params = bundle.init(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      max_seq=args.max_seq)
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    for i in range(args.requests):
        eng.submit(list(rng.integers(0, cfg.vocab, 8)), max_new=args.max_new)
    done = eng.run()
    dt = time.perf_counter() - t0
    print(f"arch={cfg.arch_id} requests={len(done)} "
          f"tokens={eng.tokens_out} decode_steps={eng.decode_steps} "
          f"wall={dt:.2f}s tok/s={eng.tokens_out / max(dt, 1e-9):.1f}")
    for r in done[:3]:
        print(f"  req {r.rid}: prompt {r.prompt[:4]}... -> {r.out[:8]}...")


if __name__ == "__main__":
    main()
