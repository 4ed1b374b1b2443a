"""The sweep execution engine behind ``solve_many`` (DESIGN.md §9).

Dispatch (batch mode "auto"):

  batched   specs on the built-in local backend whose algorithm provides a
            ``make_batch_round`` hook are grouped by their trace-shaping key
            (shape x algorithm x option x alpha x rounds x accounting x ...)
            and each group runs as ONE compiled program: ``lax.scan`` over
            rounds of ``lax.map`` over the stacked spec axis, compressor
            variation via ``lax.switch`` into the group's compressor table
            (``repro.core.fednl_batch``).  Per-spec trajectories are
            BIT-identical to sequential ``solve()`` calls.  With multiple
            local devices the spec axis is sharded across a 1-D mesh
            (``repro.launch.mesh.make_sweep_mesh``) via ``shard_map``.
  pool      wire-backend specs (star-loopback / star-tcp) are dispatched
            concurrently through a bounded thread pool — the event loops are
            I/O-bound, and every run owns its transport, so runs interleave
            without sharing state.
  warm      local-backend fallback specs identical except ``rounds`` share
            one trajectory prefix: a single warm-started session
            (``repro.api.session``) steps to each round count in ascending
            order and reports there — bit-identical to per-spec solves (the
            DESIGN.md §10 step-composability contract) with the shared
            prefix computed once.
  fallback  everything else (sharded, PP on local, tol early-stop, custom
            algorithms without a batch hook, ...) runs per spec through
            ``solve()`` — logged with the reason, never silently dropped.

Mode "vmap" swaps ``lax.map`` for ``jax.vmap`` over the spec axis in the
batched groups: maximal throughput on wide accelerators, but the batched
kernels (dot_general / Cholesky) may differ from the sequential ones by a
few ulps — the bit-identity guarantee is explicitly waived and logged.
Mode "never" runs everything sequentially in expansion order (what the
benchmark tables use, so per-spec wall time stays meaningful).
"""

from __future__ import annotations

import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import Algorithm, get_algorithm, get_backend
from repro.api.report import RoundRecord, RunReport, SweepReport

# event-loop backends that profit from concurrent dispatch; TCP spawns one
# OS process per client, so its width stays small
_POOL_WIDTH = {"star-loopback": 4, "star-tcp": 2}


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Plan:
    kind: str  # "batch" | "pool" | "warm" | "seq"
    indices: list[int]
    reason: str = ""


def _warm_key(spec):
    """Specs identical except ``rounds`` share one trajectory prefix: a
    single session solves the longest and reports every intermediate spec
    bit-identically (step composability, DESIGN.md §10).  None = ineligible."""
    from repro.api.backends import LOCAL_BACKEND

    if get_backend(spec.backend) is not LOCAL_BACKEND:
        return None  # session reuse is a local-simulation optimization
    if spec.tol > 0.0:
        return None  # early stop can end runs before the shared prefix
    return spec.replace(rounds=0)


def _batch_blockers(spec, algo: Algorithm, backend) -> list[str]:
    """Why this spec cannot join a vectorized batch (empty = it can)."""
    from repro.api.backends import LOCAL_BACKEND

    reasons = []
    if backend is not LOCAL_BACKEND:
        reasons.append(f"backend {spec.backend!r} is not the builtin local "
                       "simulation")
    if algo.make_batch_round is None:
        reasons.append(f"algorithm {spec.algorithm!r} has no batch-round hook")
    if algo.kind != "full":
        reasons.append("partial participation batches per spec only")
    if spec.tol > 0.0:
        reasons.append("tol early-stop needs a per-round host sync")
    if spec.rounds == 0:
        reasons.append("zero-round run")
    if spec.hessian_impl == "pallas":
        reasons.append("Pallas-wrapper Hessian routing is untested under the "
                       "batched scan")
    return reasons


def _group_key(spec, alpha: float, vectorize: str, dims: tuple) -> tuple:
    """Everything that shapes the batched trace EXCEPT compressor choice and
    seed — specs sharing this key run in one program.

    In the bit-exact "scan" layout the problem data itself is part of the
    key: the sequential path embeds ``z`` as a jit *constant*, and feeding it
    as a sliced ``lax.map`` operand instead changes the matmul kernels by an
    ulp (measured — DESIGN.md §9), so each distinct DataSpec gets its own
    compiled program with ``z`` closed over.  The "vmap" layout waives
    bit-identity and batches across data too.
    """
    return (
        spec.algorithm,
        spec.data if vectorize == "scan" else dims,
        spec.rounds,
        spec.objective,
        spec.lam,
        spec.option,
        spec.mu,
        spec.hess0,
        spec.hessian_impl,  # "fused" vs "jnp" shape different traces for d > 128
        spec.accounting,
        spec.ls_c,
        spec.ls_gamma,
        spec.ls_max_steps,
        spec.ls_tol,
        alpha,
    )


def resolved_alpha(spec, d: int) -> float:
    """The Hessian learning rate the round will actually use (compressor
    default unless the spec overrides it) — part of the group key so it can
    stay a compile-time constant inside the batched kernel."""
    if spec.compressor.alpha is not None:
        return float(spec.compressor.alpha)
    from repro.compressors import get_compressor
    from repro.linalg import triu_size

    cfg = spec.fednl_config()
    return float(get_compressor(spec.compressor.name, triu_size(d), cfg.k_for(d)).alpha)


def plan_sweep(specs: Sequence, batch_mode: str) -> tuple[list[_Plan], list[str]]:
    """Partition the expanded specs into batch groups, pool groups and
    per-spec fallbacks.  Validation (registry lookups, capability checks)
    happens here for EVERY spec before anything runs, so a bad spec fails
    the whole call upfront with the same error ``solve()`` raises."""
    from repro.api.facade import check_spec

    log: list[str] = []
    batch_groups: dict[tuple, list[int]] = {}
    pool_groups: dict[str, list[int]] = {}
    seq: list[tuple[int, str]] = []
    vectorize = "vmap" if batch_mode == "vmap" else "scan"
    # dims() parses LIBSVM files — resolve once per distinct DataSpec
    dims_cache: dict = {}

    for i, spec in enumerate(specs):
        algo = get_algorithm(spec.algorithm)
        backend = get_backend(spec.backend)
        check_spec(spec, algo, backend)
        if batch_mode == "never":
            seq.append((i, "batch='never'"))
            continue
        blockers = _batch_blockers(spec, algo, backend)
        if not blockers:
            if spec.data not in dims_cache:
                dims_cache[spec.data] = spec.data.dims()
            dims = dims_cache[spec.data]
            batch_groups.setdefault(
                _group_key(spec, resolved_alpha(spec, dims[0]), vectorize, dims),
                [],
            ).append(i)
        elif spec.backend in _POOL_WIDTH:
            pool_groups.setdefault(spec.backend, []).append(i)
        else:
            seq.append((i, "; ".join(blockers)))

    plans: list[_Plan] = []
    for key, idxs in batch_groups.items():
        if len(idxs) == 1:
            # a one-spec "batch" would pay switch/map overhead for nothing
            seq.append((idxs[0], "only spec in its batch group"))
            continue
        plans.append(_Plan("batch", idxs, reason=f"group key {key[:3]}..."))
    for backend_name, idxs in pool_groups.items():
        plans.append(_Plan("pool", idxs, reason=backend_name))

    # warm-start session reuse: fallback specs identical except `rounds` run
    # as ONE session stepped to each round count in ascending order (skipped
    # under batch="never", which promises per-spec timing)
    if batch_mode != "never":
        warm_groups: dict = {}
        for i, reason in seq:
            key = _warm_key(specs[i])
            if key is not None:
                warm_groups.setdefault(key, []).append(i)
        warmed: set[int] = set()
        for key, idxs in warm_groups.items():
            if len(idxs) < 2:
                continue
            idxs.sort(key=lambda i: specs[i].rounds)
            warmed.update(idxs)
            plans.append(_Plan("warm", idxs, reason="rounds-prefix group"))
            log.append(
                f"warm-start session reuse: specs {idxs} differ only in "
                f"rounds {[specs[i].rounds for i in idxs]} — one session, "
                "reports emitted at each prefix"
            )
        seq = [(i, reason) for i, reason in seq if i not in warmed]

    for i, reason in seq:
        plans.append(_Plan("seq", [i], reason=reason))
        if batch_mode != "never":
            log.append(f"spec[{i}]: fallback to sequential solve() — {reason}")
    return plans, log


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------


def _stack_states(states):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _run_batched_group(
    specs: Sequence, idxs: list[int], z_for, vectorize: str, log: list[str]
) -> list[RunReport]:
    """Run one shape-compatible group as a single compiled program."""
    from repro.launch.mesh import make_sweep_mesh, sweep_mesh_devices

    group = [specs[i] for i in idxs]
    algo = get_algorithm(group[0].algorithm)
    d, _, _ = group[0].data.dims()
    from repro.compressors import get_compressor
    from repro.linalg import triu_size

    t = triu_size(d)
    # compressor branch table, ordered by first occurrence in the group
    branch_keys: list[tuple[str, int]] = []
    comp_idx: list[int] = []
    for spec in group:
        cfg = spec.fednl_config()
        bk = (cfg.compressor, cfg.k_for(d))
        if bk not in branch_keys:
            branch_keys.append(bk)
        comp_idx.append(branch_keys.index(bk))
    comps = [get_compressor(name, t, k) for name, k in branch_keys]
    cfg0 = group[0].fednl_config()
    alpha = resolved_alpha(group[0], d)
    body = algo.make_batch_round(cfg0, comps, alpha)

    t0 = time.perf_counter()
    zs = [z_for(spec) for spec in group]
    shared_z = all(spec.data == group[0].data for spec in group)
    state0 = _stack_states(
        [
            algo.init(z, spec.fednl_config(), x0=None, seed=spec.seed)
            for spec, z in zip(group, zs)
        ]
    )
    ci = jnp.asarray(comp_idx)
    rounds = group[0].rounds
    n_batch = len(group)

    if shared_z:
        z_const = zs[0]

        def spec_axis_map(ci_b, st_b):
            if vectorize == "vmap":
                return jax.vmap(body, in_axes=(None, 0, 0))(z_const, ci_b, st_b)
            return jax.lax.map(lambda a: body(z_const, *a), (ci_b, st_b))

        operands = (ci, state0)
    else:
        z_b = jnp.stack(zs)

        def spec_axis_map(z_bb, ci_b, st_b):
            if vectorize == "vmap":
                return jax.vmap(body)(z_bb, ci_b, st_b)
            return jax.lax.map(lambda a: body(*a), (z_bb, ci_b, st_b))

        operands = (z_b, ci, state0)

    def program(*args):
        st_b = args[-1]
        rest = args[:-1]

        def step(carry, _):
            return spec_axis_map(*rest, carry)

        return jax.lax.scan(step, st_b, None, length=rounds)

    n_dev = sweep_mesh_devices(n_batch)
    if n_dev > 1:
        from jax.sharding import PartitionSpec as P

        mesh = make_sweep_mesh(n_dev)
        program = jax.shard_map(
            program,
            mesh=mesh,
            in_specs=tuple(P("sweep") for _ in operands),
            out_specs=(P("sweep"), P(None, "sweep")),
        )

    run = jax.jit(program)
    compiled = run.lower(*operands).compile()  # compile outside the timed loop
    init_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    final_state, metrics = compiled(*operands)
    jax.block_until_ready(final_state)
    wall = time.perf_counter() - t1

    log.append(
        f"batched {n_batch} specs as one program: {group[0].algorithm}, "
        f"{len(comps)} compressor branch(es), {rounds} rounds, "
        f"vectorize={vectorize}, devices={n_dev} "
        f"(compile {init_s:.2f}s, run {wall:.2f}s)"
    )

    # materialize per-spec reports from the (rounds, batch) metric arrays
    cols = {
        name: np.asarray(getattr(metrics, name))
        for name in metrics._fields
    }
    x_final = np.asarray(final_state.x)
    reports = []
    for b, spec in enumerate(group):
        records = [
            RoundRecord(
                round=r,
                grad_norm=float(cols["grad_norm"][r, b]),
                f=float(cols["f"][r, b]),
                l=float(cols["l"][r, b]),
                sent_elems=int(cols["sent_elems"][r, b]),
                sent_bits=int(cols["sent_bits"][r, b]),
                sent_bits_payload=int(cols["sent_bits_payload"][r, b]),
                sent_bits_wire=int(cols["sent_bits_wire"][r, b]),
                ls_steps=(
                    int(cols["ls_steps"][r, b]) if "ls_steps" in cols else None
                ),
            )
            for r in range(rounds)
        ]
        reports.append(
            RunReport(
                spec=spec,
                algorithm=spec.algorithm,
                backend=spec.backend,
                x=x_final[b],
                records=records,
                rounds=rounds,
                wall_time_s=wall / n_batch,
                init_time_s=init_s / n_batch,
                extras={
                    "sweep_batched": True,
                    "batch_size": n_batch,
                    "batch_wall_time_s": wall,
                    "batch_init_time_s": init_s,
                    "vectorize": vectorize,
                    "devices": n_dev,
                    "compressor_branch": branch_keys[comp_idx[b]][0],
                },
            )
        )
    return reports


# ---------------------------------------------------------------------------
# the sweep driver
# ---------------------------------------------------------------------------


def run_sweep(specs: Sequence, batch_mode: str, sweep: Any = None) -> SweepReport:
    from repro.api.facade import solve

    t_start = time.perf_counter()
    plans, log = plan_sweep(specs, batch_mode)
    vectorize = "vmap" if batch_mode == "vmap" else "scan"

    # one data build per distinct DataSpec across the whole sweep
    z_cache: dict[Any, Any] = {}

    def z_for(spec):
        if spec.data not in z_cache:
            z_cache[spec.data] = spec.data.build()
        return z_cache[spec.data]

    reports: list[RunReport | None] = [None] * len(specs)
    batched_specs = 0
    for plan in plans:
        if plan.kind == "batch":
            group_reports = _run_batched_group(
                specs, plan.indices, z_for, vectorize, log
            )
            for i, rep in zip(plan.indices, group_reports):
                reports[i] = rep
            batched_specs += len(plan.indices)
        elif plan.kind == "warm":
            from repro.api.session import open_session

            # one session for the whole rounds-prefix group: step to each
            # spec's round count (ascending) and report it there — step
            # composability makes every report bit-identical to its own
            # solve() while the shared prefix is computed once
            spec_max = specs[plan.indices[-1]]
            with open_session(spec_max, z=z_for(spec_max)) as session:
                for i in plan.indices:
                    session.step(specs[i].rounds - session.round)
                    reports[i] = session.report(spec=specs[i])
        elif plan.kind == "pool":
            width = min(_POOL_WIDTH[plan.reason], len(plan.indices))
            log.append(
                f"pool: {len(plan.indices)} specs on {plan.reason} via "
                f"{width} worker thread(s)"
            )
            with ThreadPoolExecutor(max_workers=width) as pool:
                futures = [
                    pool.submit(
                        solve,
                        specs[i],
                        z=(
                            z_for(specs[i])
                            if get_backend(specs[i].backend).needs_problem
                            else None
                        ),
                    )
                    for i in plan.indices
                ]
                for i, fut in zip(plan.indices, futures):
                    reports[i] = fut.result()
        else:
            for i in plan.indices:
                spec = specs[i]
                z = (
                    z_for(spec)
                    if get_backend(spec.backend).needs_problem
                    else None
                )
                reports[i] = solve(spec, z=z)

    wall = time.perf_counter() - t_start
    return SweepReport(
        specs=tuple(specs),
        reports=reports,  # type: ignore[arg-type]
        log=log,
        wall_time_s=wall,
        sweep=sweep,
        extras={
            "batch_mode": batch_mode,
            "batched_specs": batched_specs,
            "n_groups": len(plans),
            "n_data_builds": len(z_cache),
        },
    )
