"""Record the profiler trace of the serving loop that the span-reader
tests read.

    python chipbench/tests/record_spans_fixture.py OUT_DIR

Runs on the chip only.  A small digital model (2 attention layers, d
256) is served by ``ContinuousEngine`` as the benchmark drives it: each
``eng.step()`` inside a ``chipbench.step`` annotation, all inside one
``chipbench.window``, with telemetry on and a span sink open, so the
trace holds the program's nested ``serve/*`` spans as host events and
its named device programs (``jit_serve_prefill``, ``jit_serve_decode``,
``jit_serve_join``, ``jit_serve_evict``) on the ``XLA Modules`` line.
Six requests of 5-60 prompt tokens and 3-12 output tokens share 4
slots.  The Python tracer is off, to keep the file small.  Writes
``OUT_DIR/spans.xplane.pb`` and ``OUT_DIR/spans.jsonl`` (the program's
spans of the same run).
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROMPTS = (5, 60, 17, 33, 9, 48)
OUTPUTS = (3, 12, 7, 4, 10, 6)


def record(out_dir: str) -> dict:
    """Serve the requests under the profiler; returns a summary."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np

    from repro import telemetry as tm
    from repro.configs.base import CimConfig, ModelConfig
    from repro.models.model import init_params
    from repro.serve import ContinuousEngine

    cfg = ModelConfig(
        name="spans-fixture", n_layers=2, d_model=256, n_heads=4,
        n_kv_heads=4, d_ff=512, vocab_size=512, block_pattern=("attn",),
        remat="none", dtype="bfloat16", attn_chunk=128,
        cim=CimConfig(enabled=False))
    eng = ContinuousEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           capacity=4, max_seq=128, max_prompt=64)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, 512, 8), max_tokens=2)
    eng.run()                                   # compiles every program
    tmp = tempfile.mkdtemp()
    tm.enable()
    tm.trace_to(os.path.join(tmp, "spans.jsonl"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(tmp, "profile"),
                             profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for L, n in zip(PROMPTS, OUTPUTS):
            eng.submit(rng.integers(0, 512, L), max_tokens=n)
        while eng.scheduler.pending:
            with jax.profiler.TraceAnnotation("chipbench.step"):
                eng.step()
    jax.profiler.stop_trace()
    tm.trace_stop()
    tm.disable()
    path = glob.glob(os.path.join(tmp, "profile", "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "spans.xplane.pb"))
    shutil.copy(os.path.join(tmp, "spans.jsonl"),
                os.path.join(out_dir, "spans.jsonl"))
    shutil.rmtree(tmp)
    return {"platform": jax.devices()[0].platform,
            "iterations": eng.iterations, "traces": dict(eng.traces),
            "bytes": os.path.getsize(os.path.join(out_dir,
                                                  "spans.xplane.pb"))}


def main(out_dir: str) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_spans_fixture: no TPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, **record(out_dir)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
