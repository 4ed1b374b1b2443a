"""Readings of the control that each solve cell's comparison has to refuse.

    python3 perfbench/tools/control.py --workload w8a-topk-solve --seeds 11,12,13

The cell's configuration states float64.  For each seed this builds the
cell's problem at its own size and compares with the float64 reference:

* ``program_f32``: the program's own float32 path (the problem handed to
  ``open_session`` in float32: the chip's float32 kernels and a float32
  master solve), solving as the window does;
* ``reference_f32``: the reference computed in float32, put in the
  program's place.

It prints one JSON line per seed and control with the compared numbers,
and the cell's limits beside them.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from perfbench import run  # noqa: E402


def readings(cell, seed: int, program_f32: bool = True) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    d = cell.loop
    z_host = d.datagen.make_problem(cell.config, seed)
    st = d.State(cell.config, cell.traffic, seed, z_host, None, d.budget(cell.config))
    ref = d.reference_solve(st, seed)
    out = []
    controls = {"reference_f32": lambda: d.reference_solve(st, seed, dtype=np.float32)}
    if program_f32:
        def prog32():
            st.z = jax.device_put(jnp.asarray(z_host, dtype=jnp.float32))
            got = d._solve(st, seed, run.Spans())
            st.z = None
            return got
        controls = {"program_f32": prog32, **controls}
    for name, fn in controls.items():
        t0 = time.perf_counter()
        try:
            got = fn()
            nums = d.compare(got, ref, cell.traffic["gnorm_floor"])
            nums["tol_ratio"] = float(got.grad_norms[-1]) / cell.traffic["tol"]
            nums["rounds"] = got.rounds
        except Exception as e:  # a control that crashes has failed
            nums = {"error": f"{type(e).__name__}: {e}"[:300]}
        nums.update(control=name, seed=seed, ref_rounds=ref.rounds,
                    seconds=time.perf_counter() - t0)
        out.append(nums)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-program-f32", action="store_true")
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = run.resolve(bench, args.workload)
    run.configure_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for r in readings(cell, seed, program_f32=not args.no_program_f32):
            r["limits"] = cell.limits
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
