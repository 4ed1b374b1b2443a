#!/usr/bin/env python3
"""On-chip smoke test of the default FedNL path.

    python chip_smoke.py                # phases (a)-(d) on one TPU chip
    python chip_smoke.py --four-chips   # the four-chip paths only

Every phase runs in this one process, which holds the chip.  The problem is
the paper's w8a shape (d = 301, 142 clients x 348 samples, f64), generated
from a seed; the specs are ``ExperimentSpec`` defaults (f64,
``hessian="fused"``, local backend) unless a phase says otherwise.

  (a) ``solve()`` for topk (the paper's compressor) and the two proposed
      ones, randseqk and toplek, for ROUNDS rounds — enough to reach
      ||grad f|| <= 1e-10.
  (b) the same specs on this process's host CPU device, compared with (a):
      final x to a relative X_RTOL; per-round ||grad f|| to a relative
      G_RTOL while ||grad f|| > G_WINDOW * ||grad f_0||; both runs end at
      ||grad f|| <= G_FLOOR; equal ``sent_bits`` totals for topk and
      randseqk.  Why tolerances and not bits: f64 on the TPU is emulated
      and does not round like XLA:CPU, and Newton's quadratic phase
      amplifies ulp differences once ||grad f|| nears the f64 floor.
  (c) ``FedNLServer`` serving four w8a tenants with mixed compressors and
      round budgets; every tenant must finish, bit-identical (records and
      final x) to its solo session on this chip.
  (d) each Pallas kernel (SYRK, TopK and RandSeqK selection) compiled at
      w8a width in f32 against its jnp primitive: identical selection
      keep-sets; the SYRK within the f32 summation bound, printed.

``--four-chips`` runs only: the sharded backend on four chips against the
local backend on one chip for one spec (144 clients, since 4 does not
divide w8a's 142), and a ``solve_many`` grid batched over four devices
against ``batch="never"``, both under the tolerances of (b).

Each phase prints its set-up (compile) seconds apart from its warm seconds
per round.  Any failed check exits non-zero.  With no TPU, or outside the
repository, it exits non-zero before printing any result.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ROUNDS = 14
COMPRESSORS = ("topk", "randseqk", "toplek")
X_RTOL = 1e-10
G_RTOL = 1e-6
G_WINDOW = 1e-6
G_FLOOR = 1e-10


class Failures:
    def __init__(self):
        self.items: list[str] = []

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.items.append(what)
            print(f"  FAILED: {what}", flush=True)
        return ok


def say(msg: str) -> None:
    print(msg, flush=True)


def spec_for(comp: str, *, data=None, seed: int = 0, rounds: int = ROUNDS):
    from repro.api import CompressorSpec, DataSpec, ExperimentSpec

    return ExperimentSpec(
        data=data or DataSpec(dataset="w8a", seed=0),
        compressor=CompressorSpec(comp),
        rounds=rounds,
        seed=seed,
    )


def timing(rep) -> str:
    return (f"set-up {rep.init_time_s:.3f} s, warm "
            f"{rep.wall_time_s / max(rep.rounds, 1):.6f} s/round")


def compare(tag: str, got, ref, fails: Failures) -> None:
    """The (b) comparison of two runs of one spec (module docstring)."""
    import numpy as np

    x, xr = np.asarray(got.x), np.asarray(ref.x)
    x_rel = float(np.linalg.norm(x - xr) / np.linalg.norm(xr))
    g, gr = np.asarray(got.grad_norms), np.asarray(ref.grad_norms)
    same_len = fails.check(len(g) == len(gr), f"{tag}: {len(g)} vs {len(gr)} rounds")
    window = gr > G_WINDOW * gr[0]
    g_rel = float(np.max(np.abs(g[window] - gr[window]) / gr[window])) if same_len else math.inf
    say(f"  {tag}: ||x - x_ref||/||x_ref|| {x_rel:.3e} (<= {X_RTOL:g}); "
        f"max rel ||grad|| deviation over {int(window.sum())} rounds above "
        f"{G_WINDOW:g}*||grad_0|| {g_rel:.3e} (<= {G_RTOL:g}); final ||grad|| "
        f"{g[-1]:.3e} vs {gr[-1]:.3e} (<= {G_FLOOR:g}); bit-identical "
        f"{bool(np.array_equal(x, xr) and np.array_equal(g, gr))}")
    fails.check(x_rel <= X_RTOL, f"{tag}: final x rel {x_rel:.3e} > {X_RTOL:g}")
    fails.check(g_rel <= G_RTOL, f"{tag}: ||grad|| rel {g_rel:.3e} > {G_RTOL:g}")
    fails.check(g[-1] <= G_FLOOR and gr[-1] <= G_FLOOR,
                f"{tag}: final ||grad|| {g[-1]:.3e} / {gr[-1]:.3e} > {G_FLOOR:g}")


def custom_calls_in_round(spec) -> int:
    """tpu_custom_call (Pallas) count in the lowered round program of spec."""
    import jax

    from repro.core.fednl import fednl_init, make_fednl_round

    z = spec.data.build()
    cfg = spec.fednl_config()
    state = jax.eval_shape(lambda: fednl_init(z, cfg, seed=spec.seed))
    return jax.jit(make_fednl_round(z, cfg)).lower(state).as_text().count(
        "tpu_custom_call")


def phase_a(fails: Failures) -> dict:
    from repro.api import solve

    t0 = time.perf_counter()
    reps = {}
    for comp in COMPRESSORS:
        rep = solve(spec_for(comp))
        reps[comp] = rep
        g = rep.grad_norms
        say(f"(a) {comp}: {timing(rep)}, ||grad|| {g[0]:.3e} -> {g[-1]:.3e} "
            f"in {rep.rounds} rounds, sent_bits {int(rep.sent_bits.sum())}")
        fails.check(g[-1] <= G_FLOOR, f"(a) {comp}: final ||grad|| {g[-1]:.3e} > {G_FLOOR:g}")
    n_cc = custom_calls_in_round(spec_for("topk"))
    say(f"(a) tpu_custom_call in the f64 round program: {n_cc} (the f64 route "
        "runs the XLA strip SYRK and the jnp selection)")
    fails.check(n_cc == 0, f"(a) {n_cc} Pallas calls in the f64 round")
    say(f"(a) done in {time.perf_counter() - t0:.1f} s")
    return reps


def phase_b(reps: dict, fails: Failures) -> None:
    import jax

    from repro.api import solve

    t0 = time.perf_counter()
    cpu = jax.devices("cpu")[0]
    for comp in COMPRESSORS:
        with jax.default_device(cpu):
            ref = solve(spec_for(comp))
        say(f"(b) {comp} on host CPU: {timing(ref)} (CPU time, not a device metric)")
        compare(f"(b) {comp} TPU vs host CPU", reps[comp], ref, fails)
        bits, bits_ref = int(reps[comp].sent_bits.sum()), int(ref.sent_bits.sum())
        if comp in ("topk", "randseqk"):
            fails.check(bits == bits_ref, f"(b) {comp}: sent_bits {bits} != {bits_ref}")
        say(f"  sent_bits total TPU {bits}, host CPU {bits_ref}")
    say(f"(b) done in {time.perf_counter() - t0:.1f} s")


def phase_c(reps: dict, fails: Failures) -> None:
    import numpy as np

    from repro.api import solve
    from repro.serve_fednl import FedNLServer, ServeConfig

    t0 = time.perf_counter()
    tenants = [(spec_for(c), reps[c]) for c in COMPRESSORS]
    extra = spec_for("randseqk", seed=1, rounds=6)
    tenants.append((extra, solve(extra)))
    say(f"(c) solo randseqk seed 1 x 6 rounds: {timing(tenants[-1][1])}")
    t1 = time.perf_counter()
    with FedNLServer(ServeConfig(max_resident=4)) as server:
        handles = [server.submit(spec) for spec, _ in tenants]
        ticks = server.serve_until_idle()
        stats = server.stats()
    served = time.perf_counter() - t1
    say(f"(c) engine: {ticks} ticks, {stats['compiles']} compiles, "
        f"{stats['groups']} groups, statuses {stats['statuses']}, "
        f"{served:.1f} s served (compile included)")
    for (spec, solo), h in zip(tenants, handles):
        tag = f"(c) {spec.compressor.name} seed {spec.seed} x {spec.rounds} rounds"
        if not fails.check(h.status == "finished", f"{tag}: status {h.status}"):
            continue
        got = h.result()
        same = (len(got.records) == len(solo.records)
                and all(a == b for a, b in zip(got.records, solo.records))
                and np.array_equal(got.x, solo.x))
        say(f"  {tag}: final ||grad|| {got.grad_norms[-1]:.3e}, bit-identical "
            f"to solo session: {same}")
        fails.check(same, f"{tag}: served trajectory differs from solo session")
    say(f"(c) done in {time.perf_counter() - t0:.1f} s")


def _timed(fn, *args, reps: int = 10):
    """(compile s, warm s per call, compiled text, output) of jit(fn)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    setup = time.perf_counter() - t0
    out = compiled(*args)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    jax.block_until_ready(out)
    return setup, (time.perf_counter() - t1) / reps, compiled.as_text(), out


def phase_d(fails: Failures) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import DataSpec
    from repro.compressors import select as csel
    from repro.kernels import ops
    from repro.kernels.compressor_select import (
        select_randseqk_pallas,
        select_topk_pallas,
    )

    t0 = time.perf_counter()
    z64 = np.asarray(DataSpec(dataset="w8a", seed=0).build()[0])
    n, d = z64.shape
    rng = np.random.default_rng(0)
    z = jnp.asarray(z64, jnp.float32)
    h = jnp.asarray(rng.uniform(0.0, 0.25, n) / n, jnp.float32)
    setup, warm, text, got = _timed(
        lambda z, h: ops.hessian_syrk(z, h, interpret=False), z, h)
    zf, hf = np.asarray(z, np.float64), np.asarray(h, np.float64)
    want = zf.T @ (hf[:, None] * zf)
    # |fl(sum) - sum| <= gamma_{n+2} * sum |terms|, gamma_m = m u / (1 - m u)
    u = 2.0 ** -24
    gamma = (n + 2) * u / (1 - (n + 2) * u)
    bound = gamma * (np.abs(zf).T @ (hf[:, None] * np.abs(zf)))
    err = np.abs(np.asarray(got, np.float64) - want)
    ratio = float(np.max(err / np.maximum(bound, np.finfo(np.float64).tiny)))
    say(f"(d) hessian_syrk f32 ({n}x{d}): set-up {setup:.3f} s, warm "
        f"{warm * 1e3:.4f} ms/call, tpu_custom_call {text.count('tpu_custom_call')}, "
        f"max |err| {err.max():.3e}, max |err|/bound {ratio:.3e} "
        f"(bound gamma_(n+2)*|Z|^T diag(h)|Z|, gamma {gamma:.3e})")
    fails.check(ratio <= 1.0, f"(d) SYRK error exceeds the f32 bound ({ratio:.3e})")
    fails.check(text.count("tpu_custom_call") >= 1, "(d) SYRK did not run Pallas")

    t = d * (d + 1) // 2
    k = 8 * d
    # values on a 2**-10 grid: many exact f32 key ties, the tie-break's test
    u32 = jnp.asarray(np.round(rng.standard_normal(t) * 1024) / 1024, jnp.float32)
    s = jax.random.randint(jax.random.PRNGKey(1), (), 0, t)
    for name, kern, ref, args in [
        ("select_topk", lambda u: select_topk_pallas(u, k)[0],
         lambda u: csel.topk_dense(u, k), (u32,)),
        ("select_randseqk", lambda u, s: select_randseqk_pallas(u, k, s)[0],
         lambda u, s: csel.randseqk_dense(u, k, s), (u32, s)),
    ]:
        setup, warm, text, got = _timed(kern, *args)
        want = jax.jit(ref)(*args)
        keep, keep_ref = np.flatnonzero(np.asarray(got)), np.flatnonzero(np.asarray(want))
        same = bool(np.array_equal(keep, keep_ref)
                    and np.array_equal(np.asarray(got), np.asarray(want)))
        say(f"(d) {name} f32 (T={t}, k={k}): set-up {setup:.3f} s, warm "
            f"{warm * 1e3:.4f} ms/call, tpu_custom_call "
            f"{text.count('tpu_custom_call')}, nonzero kept {keep.size}, keep-set and "
            f"values identical to the jnp primitive: {same}")
        fails.check(same, f"(d) {name}: keep-set differs from the jnp primitive")
        fails.check(text.count("tpu_custom_call") >= 1, f"(d) {name} did not run Pallas")
    say(f"(d) done in {time.perf_counter() - t0:.1f} s")


def four_chips(fails: Failures) -> None:
    import jax
    import numpy as np

    from repro.api import DataSpec, solve, solve_many

    t0 = time.perf_counter()
    fails.check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, have {len(jax.devices())}")
    spec = spec_for("topk", data=DataSpec(shape=(301, 144, 348), seed=0))
    say("[4] 144 clients x 348 samples at d = 301: w8a has 142 clients, which "
        "4 does not divide")
    sharded = solve(spec.replace(backend="sharded", devices=4))
    ex = sharded.extras
    say(f"[4] sharded, 4 chips: {timing(sharded)}, ||grad|| "
        f"{sharded.grad_norms[0]:.3e} -> {sharded.grad_norms[-1]:.3e}; samples "
        f"on {ex['data_devices']} devices, client shifts on {ex['state_devices']}")
    fails.check(ex["data_devices"] == 4 and ex["state_devices"] == 4,
                f"[4] state/samples on {ex['state_devices']}/{ex['data_devices']} devices, not 4")
    local = solve(spec)
    say(f"[4] local, 1 chip: {timing(local)}")
    compare("[4] sharded (4 chips) vs local (1 chip)", sharded, local, fails)

    base = spec_for("randseqk")
    seeds = [0, 1, 2, 3]
    t1 = time.perf_counter()
    batched = solve_many(base.grid(seed=seeds))
    t_batched = time.perf_counter() - t1
    say(f"[4] sweep log: {batched.log}")
    log = " ".join(batched.log)
    fails.check("batched" in log and "devices=4" in log and "fallback" not in log,
                "[4] sweep did not run batched over 4 devices without fallback")
    t1 = time.perf_counter()
    never = solve_many(base.grid(seed=seeds, batch="never"))
    t_never = time.perf_counter() - t1
    for seed, a, b in zip(seeds, batched, never):
        compare(f"[4] sweep randseqk seed {seed}: batched over 4 vs batch=never",
                a, b, fails)
    say(f"[4] sweep wall (compile included): batched {t_batched:.1f} s, "
        f"batch=never {t_never:.1f} s")
    say(f"[4] done in {time.perf_counter() - t0:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round and the device-sharded sweep on 4 chips")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro" / "runtime.py").is_file():
        print(f"chip_smoke: {src}/repro not found; run from the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 1
    from repro.runtime import configure_jax

    configure_jax()
    say(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(jax.devices())} device(s), jax {jax.__version__}, compile "
        f"cache {jax.config.jax_compilation_cache_dir}")

    fails = Failures()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(fails)
    else:
        reps = phase_a(fails)
        phase_b(reps, fails)
        phase_c(reps, fails)
        phase_d(fails)
    say(f"{'all phases passed' if not fails.items else f'{len(fails.items)} checks failed'} "
        f"in {time.perf_counter() - t0:.1f} s")
    if fails.items:
        for f in fails.items:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
