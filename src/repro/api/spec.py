"""Declarative experiment description: one frozen dataclass per run.

``ExperimentSpec`` is the single configuration object of the repro —
algorithm x compressor x accounting x backend x faults in one value.  It is
deliberately *data only* (strings, numbers, nested frozen dataclasses): a
spec can be printed, hashed into a cache key, serialized with
``dataclasses.asdict``, swept over with ``dataclasses.replace``, and re-run
on a different execution backend by changing nothing but the ``backend``
field.  ``repro.api.solve`` turns a spec into a :class:`repro.api.RunReport`.

The algorithmic hyper-parameters map 1:1 onto :class:`FedNLConfig` (the
jit-level config the round builders consume); :meth:`ExperimentSpec.fednl_config`
performs that projection, so the facade never re-plumbs individual fields.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from repro.comm.transport import FaultSpec
from repro.api.accounting import ACCOUNTINGS

# TopologySpec / MembershipSpec live in repro.comm.topology and are imported
# lazily (string annotations below): topology.py pulls the jax-heavy star
# stack, and `import repro.api` must stay cheap.

# named problem shapes live in repro.data.DATASET_SHAPES (paper Tables 1-3)


def _algorithm_kind(name: str) -> str | None:
    """Registered ``Algorithm.kind`` ("full" | "pp"), or None when unknown.

    Spec validation must not pre-empt solve()'s loud unknown-algorithm error,
    so unregistered names skip the kind-dependent checks here.  Consulting
    the registry (not a hard-coded name list) keeps ``register_algorithm``
    first-class: a custom kind="pp" algorithm gets tau/fault/tol validation
    identical to the built-in fednl-pp.
    """
    from repro.api.registry import ALGORITHMS

    try:
        return ALGORITHMS.get(name).kind
    except KeyError:
        return None


@dataclasses.dataclass(frozen=True)
class DataSpec:
    """Where the federated problem comes from.

    Exactly one source:
      * ``dataset`` — a named synthetic shape from ``repro.data.DATASET_SHAPES``
        (w8a / a9a / phishing / tiny), regenerated deterministically from
        ``seed`` (this is the only source the star-tcp backend supports:
        workers rebuild their shard from the seed, no data crosses the wire);
      * ``shape`` — an explicit ``(d, n_clients, n_i)`` synthetic instance;
      * ``libsvm`` — a real LIBSVM file on disk, partitioned into
        ``clients`` x ``per_client`` shards.

    ``seed`` drives both the synthetic generator and the u.a.r. reshuffle of
    ``partition_clients`` (the paper's preprocessing pipeline).
    """

    dataset: str = "tiny"
    shape: tuple[int, int, int] | None = None
    libsvm: str | None = None
    clients: int | None = None
    per_client: int | None = None
    seed: int = 0

    def dims(self) -> tuple[int, int, int]:
        """(d, n_clients, n_i) of the problem this spec builds."""
        if self.libsvm is not None:
            if self.clients is None or self.per_client is None:
                raise ValueError("libsvm data needs clients and per_client")
            from repro.data import parse_libsvm

            x, _ = parse_libsvm(self.libsvm)
            return x.shape[1] + 1, self.clients, self.per_client
        if self.shape is not None:
            return tuple(self.shape)
        from repro.data import DATASET_SHAPES

        return DATASET_SHAPES[self.dataset]

    def build(self):
        """Materialize z: (n_clients, n_i, d) label-absorbed design matrices."""
        import jax.numpy as jnp

        from repro.data import (
            DATASET_SHAPES,
            add_intercept,
            make_synthetic_logreg,
            parse_libsvm,
            partition_clients,
        )

        if self.libsvm is not None:
            if self.clients is None or self.per_client is None:
                raise ValueError("libsvm data needs clients and per_client")
            x, y = parse_libsvm(self.libsvm)
            n, n_i = self.clients, self.per_client
        else:
            name_or_dims = self.shape if self.shape is not None else self.dataset
            if isinstance(name_or_dims, str):
                _, n, n_i = DATASET_SHAPES[name_or_dims]
            else:
                _, n, n_i = name_or_dims
            x, y = make_synthetic_logreg(name_or_dims, seed=self.seed)
        return jnp.asarray(
            partition_clients(add_intercept(x), y, n, n_i, seed=self.seed)
        )


@dataclasses.dataclass(frozen=True)
class CompressorSpec:
    """Which compressor a spec runs, in paper units.

    ``name`` must be registered (six built-ins; ``repro.api.register_compressor``
    adds more).  ``k_multiplier`` is the paper's K = k_multiplier * d sparsity
    budget; ``alpha`` overrides the compressor-recommended Hessian learning
    rate (None keeps the scaled-form default of 1.0).
    """

    name: str = "topk"
    k_multiplier: float = 8.0
    alpha: float | None = None


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One declarative FedNL experiment: solve(spec) runs it anywhere.

    Backends (``repro.api.register_backend`` adds more):
      local          single-process simulation (vmapped clients, jitted round)
      sharded        shard_mapped clients across mesh devices
      star-loopback  full wire protocol over in-process loopback transport
      star-tcp       master + one OS process per client over TCP localhost

    Algorithms (``repro.api.register_algorithm`` adds more):
      fednl / fednl-ls / fednl-pp (Algorithms 1-3 of the paper).
    """

    # --- objective -------------------------------------------------------
    objective: str = "logreg"  # L2-regularized logistic regression
    lam: float = 1e-3  # L2 regularization strength
    data: DataSpec = dataclasses.field(default_factory=DataSpec)

    # --- algorithm -------------------------------------------------------
    algorithm: str = "fednl"  # registered name: fednl | fednl-ls | fednl-pp
    compressor: CompressorSpec = dataclasses.field(default_factory=CompressorSpec)
    option: str = "B"  # master step rule: "A" (projection) | "B" (l-shift)
    mu: float = 1e-3  # strong-convexity lower bound for Option A
    hess0: str = "exact"  # "exact" | "zero" H_i^0 initialization
    # Hessian SYRK implementation (DESIGN.md §12): "fused" (default) routes
    # through kernels.ops.hessian_fused — bit-identical to "jnp" for
    # d <= 128, documented ulp drift above; "jnp" is the single-dot_general
    # parity reference; "pallas" forces the Pallas wrapper (compiled on TPU,
    # f32/bf16 only; interpret mode elsewhere — the kernel-validation path)
    hessian: str = "fused"
    use_kernel: bool = False  # deprecated spelling of hessian="pallas"
    # line-search parameters (fednl-ls)
    ls_c: float = 0.49
    ls_gamma: float = 0.5
    ls_max_steps: int = 30
    ls_tol: float = 1e-12

    # --- participation (fednl-pp) ---------------------------------------
    tau: int | None = None  # sampled clients per round (None -> n // 2)
    on_dropout: str = "partial"  # "partial" | "resample" master fallback
    fault: FaultSpec | None = None  # dropout/straggler injection

    # --- topology + membership (repro.comm.topology) ---------------------
    # how uplinks reach the root: None/star = flat PR-1 star; tree inserts
    # AggregatorNodes; mode="async" bounds staleness instead of barriering
    topology: "TopologySpec | None" = None
    # declarative join/leave schedule (flat sync star, wire backends only)
    membership: "MembershipSpec | None" = None

    # --- accounting + execution backend ---------------------------------
    accounting: str = "payload"  # "payload" | "wire" sent_bits model
    backend: str = "local"  # registered backend name
    aggregate: str = "dense_psum"  # sharded collective: dense_psum | sparse_allgather
    devices: int | None = None  # sharded mesh size (None -> all local devices)
    host: str = "127.0.0.1"  # star-tcp bind address

    # --- run control -----------------------------------------------------
    rounds: int = 100
    # grad-norm early stop (0 = run all rounds).  Full-participation
    # algorithms only: the PP server never sees the global gradient, so a
    # nonzero tol on a PP spec is rejected rather than silently ignored.
    tol: float = 0.0
    seed: int = 0  # algorithm PRNG seed (client sampling + compression)

    def __post_init__(self):
        if self.objective != "logreg":
            raise ValueError(
                f"unknown objective {self.objective!r}; only 'logreg' is "
                "implemented (the paper's problem class)"
            )
        if self.accounting not in ACCOUNTINGS:
            raise ValueError(
                f"unknown accounting {self.accounting!r}; use "
                f"{' | '.join(ACCOUNTINGS)}"
            )
        if self.option not in ("A", "B"):
            raise ValueError(f"unknown option {self.option!r}; use 'A' | 'B'")
        if self.hess0 not in ("exact", "zero"):
            raise ValueError(f"unknown hess0 {self.hess0!r}")
        if self.hessian not in ("fused", "jnp", "pallas"):
            raise ValueError(
                f"unknown hessian {self.hessian!r}; use 'fused' | 'jnp' | "
                "'pallas'"
            )
        if self.on_dropout not in ("partial", "resample"):
            raise ValueError(f"unknown on_dropout {self.on_dropout!r}")
        if self.rounds < 0:
            raise ValueError("rounds must be >= 0")
        kind = _algorithm_kind(self.algorithm)
        needs_tau = kind == "pp"
        if kind == "full" and (self.tau is not None or self.fault is not None):
            raise ValueError(
                f"tau/fault only apply to partial participation, not "
                f"{self.algorithm!r}"
            )
        if needs_tau and self.tol > 0.0:
            raise ValueError(
                "tol-based early stopping is undefined for partial "
                "participation (the server never sees the global gradient); "
                "bound the run with rounds instead"
            )
        if self.topology is not None or self.membership is not None:
            from repro.comm.topology import MembershipSpec, TopologySpec

            if self.topology is not None and not isinstance(
                self.topology, TopologySpec
            ):
                raise TypeError(
                    f"topology must be a TopologySpec, got "
                    f"{type(self.topology).__name__}"
                )
            if self.membership is not None and not isinstance(
                self.membership, MembershipSpec
            ):
                raise TypeError(
                    f"membership must be a MembershipSpec, got "
                    f"{type(self.membership).__name__}"
                )
            topo_live = self.topology is not None and not self.topology.trivial
            mem_live = self.membership is not None and not self.membership.trivial
            if topo_live and mem_live:
                raise ValueError(
                    "membership events compose with the flat sync star only "
                    "(drop the non-trivial topology or the membership events)"
                )
            if (topo_live or mem_live) and kind == "pp":
                raise ValueError(
                    f"topology/membership do not compose with partial "
                    f"participation ({self.algorithm!r}): PP samples a "
                    "cohort per round already — spec one participation "
                    "model at a time"
                )

    # --- projections ------------------------------------------------------

    def fednl_config(self):
        """Project onto the jit-level :class:`repro.core.fednl.FedNLConfig`."""
        from repro.core.fednl import FedNLConfig

        return FedNLConfig(
            compressor=self.compressor.name,
            k_multiplier=self.compressor.k_multiplier,
            alpha=self.compressor.alpha,
            option=self.option,
            mu=self.mu,
            lam=self.lam,
            hess0=self.hess0,
            hessian=self.hessian,
            use_kernel=self.use_kernel,
            ls_c=self.ls_c,
            ls_gamma=self.ls_gamma,
            ls_max_steps=self.ls_max_steps,
            ls_tol=self.ls_tol,
            accounting=self.accounting,
        )

    @property
    def hessian_impl(self) -> str:
        """Effective Hessian SYRK implementation (``use_kernel`` back-compat)."""
        return "pallas" if self.use_kernel else self.hessian

    def tau_for(self, n_clients: int) -> int:
        """Resolve the participation size (default: half the cohort)."""
        tau = self.tau if self.tau is not None else max(1, n_clients // 2)
        if not 0 < tau <= n_clients:
            raise ValueError(f"need 0 < tau <= n, got tau={tau}, n={n_clients}")
        return tau

    def replace(self, **changes: Any) -> "ExperimentSpec":
        """Functional update — ``spec.replace(backend='star-tcp')`` re-runs the
        identical experiment on another backend."""
        return dataclasses.replace(self, **changes)

    # fields a restored session may change: pure run control.  Everything
    # else shapes the serialized state or the trajectory — algorithm, data,
    # compressor, tau, fault model, accounting, backend (checkpoint layouts
    # are backend-specific), seed — and must match the checkpoint exactly.
    RESTORE_VARIABLE_FIELDS = frozenset({"rounds", "tol", "host"})

    def check_restore_from(self, saved: "ExperimentSpec") -> None:
        """Reject restore-incompatible spec/checkpoint combinations loudly.

        A checkpoint resumes the *same experiment*: restoring a FedNL-PP
        state into a spec with a different ``tau`` or compressor would
        silently run an experiment neither the checkpoint nor the spec
        describes.  Only :data:`RESTORE_VARIABLE_FIELDS` may differ (extend
        the round budget, change the early-stop tol, rebind the TCP host).
        """
        def diff(mine, theirs, prefix=""):
            """Mismatched field names; same-type nested spec dataclasses
            (TopologySpec, CompressorSpec, ...) are descended so the error
            names the exact subfield ("topology.fanout"), not the blob."""
            out = []
            for f in dataclasses.fields(mine):
                name = f"{prefix}{f.name}"
                if not prefix and f.name in self.RESTORE_VARIABLE_FIELDS:
                    continue
                a, b = getattr(mine, f.name), getattr(theirs, f.name)
                if a == b:
                    continue
                if (
                    dataclasses.is_dataclass(a)
                    and not isinstance(a, type)
                    and type(a) is type(b)
                ):
                    out.extend(diff(a, b, prefix=f"{name}."))
                else:
                    out.append(name)
            return out

        def resolve(obj, dotted):
            for part in dotted.split("."):
                obj = getattr(obj, part)
            return obj

        mismatched = diff(self, saved)
        if mismatched:
            detail = "; ".join(
                f"{name}: checkpoint ran with {resolve(saved, name)!r}, "
                f"spec asks for {resolve(self, name)!r}"
                for name in mismatched
            )
            raise ValueError(
                f"spec is incompatible with the checkpoint it restores "
                f"({detail}).  A checkpoint resumes the same experiment — "
                f"only {sorted(self.RESTORE_VARIABLE_FIELDS)} may change on "
                f"restore; to vary {', '.join(mismatched)}, start a fresh "
                f"run (open_session / solve without restore)"
            )

    def grid(self, *, batch: str = "auto", **axes: Any) -> "SweepSpec":
        """Expand this spec into a :class:`repro.api.SweepSpec` —
        ``spec.grid(seed=range(4), compressor=["topk", "randk"])`` is the
        whole compressor x seed table; ``solve_many`` runs it as one (or a
        few) compiled programs.  Axis names are ExperimentSpec fields plus
        the nested aliases (``compressor`` by name, ``k_multiplier``,
        ``dataset``, ``data_seed``, ...)."""
        from repro.api.sweep import grid as _grid

        return _grid(self, batch=batch, **axes)


