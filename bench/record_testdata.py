#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction test reads.

    python3 bench/record_testdata.py      # on a TPU v5e chip

It builds the ``qwen3-1.7b.batch-chat`` engine (16 slots, max_seq 2048),
fills every slot, and traces three decode steps inside a
``bench_window`` span, each step inside an ``engine_step`` span.  It
writes ``bench/testdata/decode_steps.xplane.pb`` and, beside it,
``decode_steps.json``: the facts the test checks the reduction against
(three steps, so 84 ``flash_decode`` calls of 28 layers) and the numbers
the reduction gave on the chip.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

STEPS = 3


def main() -> int:
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    import numpy as np
    from bench import run, trace
    from repro.configs.base import get_config
    from repro.models.model import bundle_for
    from repro.serve.engine import ServeEngine

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cfg = get_config("qwen3-1.7b")
    params = jax.jit(lambda k: bundle_for(cfg).init(cfg, k))(
        jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=16, max_seq=2048, device=dev)
    rng = np.random.default_rng(0)
    for _ in range(16):
        eng.submit(list(rng.integers(0, cfg.vocab, 256)), max_new=64)
    eng._admit()
    for _ in range(2):                          # warm the decode step
        eng.step()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("engine_step"):
                eng.step()
    jax.profiler.stop_trace()
    src = trace.latest_xplane(tmp)
    out = os.path.join(HERE, "testdata")
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "decode_steps.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    got = trace.reduce(trace.read(dst), run.SPAN_NAMES, devices=[0])
    facts = {"decode_steps": STEPS, "device_kind": dev.device_kind,
             "window_s": got["window_s"], "busy_s": got["busy_s"],
             "kernel_s": got["kernel_s"], "kernel_calls": got["kernel_calls"]}
    with open(os.path.join(out, "decode_steps.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts), os.path.getsize(dst), glob.glob(out + "/*"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
