"""Record the small device trace that perfbench/tests/test_trace.py reduces.

    python3 perfbench/tools/record_sample_trace.py OUT.xplane.pb

On one chip: inside a ``bench.window`` span, three ``bench.run`` spans
each launch a jitted float64 product and wait for it, separated by
``bench.open`` spans in which the host sleeps 20 ms and the device idles.
Prints the planes and lines the trace holds.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    jax.config.update("jax_enable_x64", True)
    if jax.devices()[0].platform == "cpu":
        print("no accelerator", file=sys.stderr)
        return 3
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((512, 512), jnp.float64) / 512
    f(a).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.open"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench.run"):
                f(a).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(plane.name, lines)
    print("bytes", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
