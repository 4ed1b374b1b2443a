"""solve(spec) / solve_many(sweep): the two entry points of the repro.

``solve`` validates one spec against the registries, builds (or accepts) the
federated problem, dispatches to the backend strategy, and returns the
unified :class:`RunReport`.  ``solve_many`` does the same for a whole
:class:`SweepSpec` grid, grouping compatible specs into single compiled
programs (``repro.api.batch``) and returning a :class:`SweepReport`.
Everything an entry script used to re-plumb — config projection, compressor
choice, bits accounting, metrics collection — happens behind these calls.
"""

from __future__ import annotations

from typing import Iterable

from repro.api.registry import Algorithm, Backend, get_algorithm, get_backend
from repro.api.report import RunReport, SweepReport
from repro.api.spec import ExperimentSpec
from repro.api.sweep import SweepSpec
from repro.runtime import configure_jax


def check_spec(
    spec: ExperimentSpec, algo: Algorithm, backend: Backend, *, z=None, x0=None
) -> None:
    """The capability checks both entry points share — a spec that would
    fail ``solve()`` fails ``solve_many()`` identically, before anything
    runs."""
    if not backend.supports(algo):
        raise ValueError(
            f"backend {backend.name!r} does not support algorithm "
            f"{algo.name!r} (it only speaks the protocols it implements)"
        )
    if x0 is not None and not backend.supports_x0:
        raise ValueError(
            f"backend {backend.name!r} does not support an x0 override (the "
            "wire protocols start every run from the INIT broadcast of the "
            "zero iterate)"
        )
    if spec.fault is not None and not backend.supports_faults:
        raise ValueError(
            f"backend {backend.name!r} cannot inject faults; a FaultSpec "
            "needs a wire backend (star-loopback / star-tcp) — running it "
            "fault-free here would silently change the experiment"
        )
    if z is not None and not backend.needs_problem:
        raise ValueError(
            f"backend {backend.name!r} rebuilds the problem from spec.data in "
            "its worker processes; a pre-built z cannot be shipped to it"
        )
    topo_live = spec.topology is not None and not spec.topology.trivial
    mem_live = spec.membership is not None and not spec.membership.trivial
    if (topo_live or mem_live) and not backend.supports_topology:
        what = "topology" if topo_live else "membership"
        raise ValueError(
            f"backend {backend.name!r} cannot run a non-trivial {what} spec; "
            "trees, async aggregation and membership events need a wire "
            "backend (star-loopback / star-tcp) — running the flat sync "
            "star here would silently change the experiment"
        )


def solve(spec: ExperimentSpec, z=None, x0=None) -> RunReport:
    """Run one experiment described by ``spec``.

    A thin wrapper over the Session protocol: ``open_session(spec).run()``
    under the spec's rounds/tol — bit-identical to the historical monolithic
    drivers (pinned by tests/test_api.py against the golden traces).  Use
    :func:`repro.api.open_session` directly to step rounds incrementally,
    observe records as they stream, or checkpoint/resume the run.

    ``z`` optionally supplies a pre-built problem array ``(n_clients, n_i, d)``
    — e.g. LM backbone features (examples/fednl_probe.py) or a LIBSVM
    round-trip — overriding ``spec.data``.  ``x0`` optionally overrides the
    zero initial iterate (local backend only; the wire protocols start every
    run from the INIT broadcast of the zero iterate).
    """
    configure_jax()  # FP64 + compile cache; idempotent
    algo = get_algorithm(spec.algorithm)
    backend = get_backend(spec.backend)
    if backend.supports_sessions:
        # open_session runs the full validation (check_spec included) itself
        from repro.api.session import open_session

        with open_session(spec, z=z, x0=x0) as session:
            return session.run()
    # legacy run-to-completion backends (custom registrations without open())
    check_spec(spec, algo, backend, z=z, x0=x0)
    if z is None and backend.needs_problem:
        z = spec.data.build()
    return backend.run(spec, algo, z, x0)


def solve_many(sweep: SweepSpec | Iterable[ExperimentSpec]) -> SweepReport:
    """Run a whole sweep — a :class:`SweepSpec` grid (``spec.grid(...)``) or
    any iterable of specs — and return a :class:`SweepReport` with one
    :class:`RunReport` per spec in expansion order.

    On the local backend, shape-compatible full-participation specs are
    grouped and executed as ONE jitted scan program per group (bit-identical
    per-spec results, compressor variation via ``lax.switch``, spec axis
    sharded across local devices when available); wire-backend specs are
    dispatched through a bounded worker pool; everything else falls back to
    per-spec ``solve()`` — each decision recorded in ``SweepReport.log``.
    """
    configure_jax()
    from repro.api.batch import run_sweep

    if isinstance(sweep, SweepSpec):
        specs, batch_mode, sweep_obj = sweep.specs(), sweep.batch, sweep
    else:
        specs, batch_mode, sweep_obj = tuple(sweep), "auto", None
        for s in specs:
            if not isinstance(s, ExperimentSpec):
                raise TypeError(
                    f"solve_many takes a SweepSpec or ExperimentSpecs, got "
                    f"{type(s).__name__}"
                )
    if not specs:
        raise ValueError("empty sweep: nothing to solve")
    return run_sweep(specs, batch_mode, sweep_obj)
