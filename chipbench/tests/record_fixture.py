"""Record the small profiler trace that the trace-reduction tests read.

    python chipbench/tests/record_fixture.py OUT_DIR

Runs on the chip only.  One ideal 256x512 crossbar deployment is
served by the Pallas ``cim_mvm`` kernel at M = 64, beside a plain
bfloat16 matmul, three times inside ``chipbench.step`` annotations with
a 20 ms ``chipbench.wait`` between them, all inside one
``chipbench.window`` annotation.  Writes ``OUT_DIR/small.xplane.pb`` and
``OUT_DIR/small.summary.json`` (planes, lines, event names and stats,
for reading the trace's layout by hand).
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 1
    from repro.core.tiling import CrossbarSpec
    from repro.kernels.cim_mvm import cim_mvm, deploy, served_impl

    kw, kx = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(kw, (256, 512)) * 0.05
    dep, _ = deploy(w, CrossbarSpec(64, 64, 8), "mdm", eta=2e-3)
    assert served_impl(dep) == "pallas", served_impl(dep)
    x = jax.random.normal(kx, (64, 256)).astype(jnp.bfloat16)
    wb = w.astype(jnp.bfloat16)

    @jax.jit
    def step(x):
        y = cim_mvm(x, dep)
        return y, x @ wb

    jax.block_until_ready(step(x))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                jax.block_until_ready(step(x))
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))

    from jax.profiler import ProfileData

    summary = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "n": len(evs), "events": [
                {"name": e.name, "start_ns": e.start_ns,
                 "duration_ns": e.duration_ns,
                 "stats": {k: str(v) for k, v in dict(e.stats).items()}}
                for e in evs[:40]]})
        summary.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out_dir, "small.summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    shutil.rmtree(tmp)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(
        os.path.join(out_dir, "small.xplane.pb"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
