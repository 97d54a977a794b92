#!/usr/bin/env python3
"""Record the small chip trace that ``test_xplane.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

On one TPU: a small jitted program (``probe_step``) runs five times inside
``prefill`` spans, each followed by a 20 ms sleep inside a ``host_read``
span, so the device is idle about 100 ms of the window and the reduction
must put that idle time on ``host_read``. Writes ``<out_dir>/probe.xplane.pb``
and ``<out_dir>/probe.json`` (what the test expects).
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

SLEEP_S = 0.02
CALLS = 5


@jax.jit
def probe_step(x):
    for _ in range(4):
        x = jnp.tanh(x @ x)
    return x


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    probe_step(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(CALLS):
        with jax.profiler.TraceAnnotation("prefill"):
            probe_step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host_read"):
            time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    path = sorted(Path(tmp).glob("plugins/profile/*/*.xplane.pb"))[-1]
    Path(out).mkdir(parents=True, exist_ok=True)
    shutil.copy(path, Path(out) / "probe.xplane.pb")
    (Path(out) / "probe.json").write_text(json.dumps(
        {"program": "probe_step", "calls": CALLS, "sleep_s": SLEEP_S,
         "device_kind": jax.devices()[0].device_kind}))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
