"""Incremental execution sessions: open_session(spec) -> step/observe/save/resume.

``solve(spec)`` is a run-to-completion black box; this module is the
round-granular form underneath it (DESIGN.md §10).  ``open_session`` builds
the same validated problem + backend machinery ``solve`` would, but hands
back a :class:`Session` that advances **one round at a time**:

    s = open_session(spec)
    s.on_round(lambda rec: print(rec.round, rec.grad_norm))
    s.step(5)                       # 5 rounds, records streamed to observers
    s.save("run.fnlsess")           # serialize mid-run
    report = s.run()                # finish under the spec's rounds/tol
    s.close()

    s2 = open_session(spec, restore="run.fnlsess")   # later / elsewhere
    report2 = s2.run()              # bit-identical to the uninterrupted run

Numerics contract (the acceptance bar, pinned by tests/test_session.py and
scripts/smoke_api.py): ``step(k)`` then ``step(m)`` is bit-identical to
``step(k + m)`` and to sequential ``solve()`` on every session-capable
backend, and save -> restore mid-run is bit-identical to an uninterrupted
run.  Backends honor it by executing chunked segments between yields without
letting the chunking shape the trajectory (``registry.SessionHandle``).

Checkpoint wire format ``FNLS1`` (documented in DESIGN.md §10): a flat
deterministic binary — magic ``FNLSESS1``, u64 header length, a sorted-key
JSON header (spec, round index, backend meta, per-round records with float
fields as ``float.hex`` strings, array manifest), then the raw little-endian
array blobs in manifest order.  Deliberately not npz: zip containers embed
timestamps, and the byte-stability property (save -> load -> save is the
identity on bytes) is part of the contract.  Only master-side state is
serialized; wire-backend clients rebuild their state from the spec plus a
replayed PRNG spine (DESIGN.md §10).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Callable

import numpy as np

from repro.api.report import RoundRecord, RunReport, RunReportBuilder
from repro.obs import core as _obs
from repro.api.spec import CompressorSpec, DataSpec, ExperimentSpec

_MAGIC = b"FNLSESS1"
_VERSION = 1

# record fields that hold floats / ints / tuples, for the hex-exact encoding
_REC_FLOAT = ("grad_norm", "f", "l")
_REC_INT = ("round", "sent_elems", "sent_bits", "sent_bits_payload",
            "sent_bits_wire", "ls_steps")
_REC_TUPLE = ("participants", "dropped")


# ---------------------------------------------------------------------------
# stop policies
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StopPolicy:
    """When :meth:`Session.run` stops, beyond exhausting the round budget.

    ``max_rounds`` caps the TOTAL round count (None -> ``spec.rounds``);
    ``tol`` stops once a round's grad norm drops below it (full-participation
    algorithms only — the PP server never sees the gradient); ``predicate``
    is an arbitrary ``RoundRecord -> bool`` custom criterion, stopping on the
    first True.  The stopping round is always included in the records,
    matching ``solve()``'s early-stop semantics.
    """

    max_rounds: int | None = None
    tol: float | None = None
    predicate: Callable[[RoundRecord], bool] | None = None

    @property
    def streaming(self) -> bool:
        """True when stopping needs a per-round look at the records."""
        return self.tol is not None or self.predicate is not None

    def hit(self, rec: RoundRecord) -> bool:
        """True when ``rec`` satisfies a streaming stop criterion (tol or
        predicate; the round-budget cap is checked against the round count,
        not a record).  The single stop test shared by :meth:`Session.run`
        and the serving engine (``repro.serve_fednl``), so a session served
        behind the engine stops on exactly the record a solo ``run()``
        would."""
        if (
            self.tol is not None
            and rec.grad_norm is not None
            and rec.grad_norm < self.tol
        ):
            return True
        return self.predicate is not None and bool(self.predicate(rec))


def resolve_policy(until, spec: ExperimentSpec) -> StopPolicy:
    """Normalize a ``run(until=...)`` argument into a :class:`StopPolicy`
    under ``spec``'s defaults (public so external drivers — the serving
    engine — resolve stop conditions exactly like :meth:`Session.run`)."""
    if until is None:
        return StopPolicy(
            max_rounds=spec.rounds,
            tol=spec.tol if spec.tol > 0.0 else None,
        )
    if isinstance(until, StopPolicy):
        if until.max_rounds is None:
            return dataclasses.replace(until, max_rounds=spec.rounds)
        return until
    if isinstance(until, bool):
        raise TypeError("until must be None | int | float | StopPolicy")
    if isinstance(until, int):
        return StopPolicy(max_rounds=until)
    if isinstance(until, float):
        return StopPolicy(max_rounds=spec.rounds, tol=until)
    raise TypeError(
        f"until must be None | int (max total rounds) | float (grad tol) | "
        f"StopPolicy, got {type(until).__name__}"
    )


# ---------------------------------------------------------------------------
# SessionState + the FNLS1 checkpoint format
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SessionState:
    """Everything needed to resume a run bit-identically: the spec, the
    round index, the backend's master-side state (model x, Hessian
    estimate/shift, PRNG spine — as ``meta`` scalars + ``arrays``), and the
    accumulated per-round records/bit counters."""

    spec: ExperimentSpec
    algorithm: str
    backend: str
    round: int
    meta: dict[str, Any]
    arrays: dict[str, np.ndarray]
    records: tuple[RoundRecord, ...]
    version: int = _VERSION


def spec_to_dict(spec: ExperimentSpec) -> dict:
    """JSON-able projection of a spec (tuples become lists)."""
    return dataclasses.asdict(spec)


def spec_from_dict(d: dict) -> ExperimentSpec:
    """Rebuild an ExperimentSpec from :func:`spec_to_dict` output."""
    from repro.comm.transport import FaultSpec

    d = dict(d)
    data = dict(d.pop("data"))
    if data.get("shape") is not None:
        data["shape"] = tuple(data["shape"])
    comp = dict(d.pop("compressor"))
    fault = d.pop("fault")
    topo = d.pop("topology", None)
    mem = d.pop("membership", None)
    topology = membership = None
    if topo is not None or mem is not None:
        from repro.comm.topology import (
            MembershipEvent,
            MembershipSpec,
            TopologySpec,
        )

        if topo is not None:
            topo = dict(topo)
            if topo.get("edges") is not None:
                topo["edges"] = tuple(tuple(g) for g in topo["edges"])
            topology = TopologySpec(**topo)
        if mem is not None:
            membership = MembershipSpec(
                events=tuple(
                    MembershipEvent(**dict(e)) for e in dict(mem)["events"]
                )
            )
    return ExperimentSpec(
        data=DataSpec(**data),
        compressor=CompressorSpec(**comp),
        fault=FaultSpec(**fault) if fault is not None else None,
        topology=topology,
        membership=membership,
        **d,
    )


def _hexf(v) -> str | None:
    return None if v is None else float(v).hex()

def _unhexf(v) -> float | None:
    return None if v is None else float.fromhex(v)


def _record_to_jsonable(rec: RoundRecord) -> dict:
    out: dict[str, Any] = {}
    for f in _REC_FLOAT:
        out[f] = _hexf(getattr(rec, f))
    for f in _REC_INT:
        v = getattr(rec, f)
        out[f] = None if v is None else int(v)
    for f in _REC_TUPLE:
        v = getattr(rec, f)
        out[f] = None if v is None else [int(i) for i in v]
    out["has_x"] = rec.x is not None
    return out


def _record_from_jsonable(d: dict, x: np.ndarray | None) -> RoundRecord:
    kw: dict[str, Any] = {"x": x}
    for f in _REC_FLOAT:
        kw[f] = _unhexf(d[f])
    for f in _REC_INT:
        kw[f] = d[f] if d[f] is None else int(d[f])
    for f in _REC_TUPLE:
        kw[f] = None if d[f] is None else tuple(d[f])
    return RoundRecord(**kw)


def save_state(state: SessionState, path) -> pathlib.Path:
    """Write the FNLS1 checkpoint.  Deterministic: identical SessionStates
    produce identical bytes (sorted JSON keys, hex-exact floats, raw
    little-endian array blobs — no container timestamps)."""
    arrays = dict(state.arrays)
    # per-round PP iterates ride as one stacked array, not JSON floats
    xs = [r.x for r in state.records if r.x is not None]
    if xs:
        if len(xs) != len(state.records):
            raise ValueError("records mix x-carrying and x-less rounds")
        arrays["__records_x__"] = np.stack([np.asarray(x) for x in xs])
    manifest = {}
    blobs = []
    for name in sorted(arrays):
        # NB reshape after ascontiguousarray: it promotes 0-d arrays to 1-d
        arr = np.asarray(arrays[name])
        arr = np.ascontiguousarray(arr).reshape(arr.shape)
        if arr.dtype.byteorder == ">":  # pragma: no cover - no BE hosts in CI
            arr = arr.astype(arr.dtype.newbyteorder("<"))
        manifest[name] = {"dtype": arr.dtype.str, "shape": list(arr.shape)}
        blobs.append(arr.tobytes())
    header = {
        "version": state.version,
        "format": "FNLS1",
        "algorithm": state.algorithm,
        "backend": state.backend,
        "round": int(state.round),
        "spec": spec_to_dict(state.spec),
        "meta": state.meta,
        "records": [_record_to_jsonable(r) for r in state.records],
        "arrays": manifest,
    }
    hdr = json.dumps(header, sort_keys=True, separators=(",", ":")).encode()
    path = pathlib.Path(path)
    with open(path, "wb") as f:
        f.write(_MAGIC)
        f.write(len(hdr).to_bytes(8, "little"))
        f.write(hdr)
        for blob in blobs:
            f.write(blob)
    return path


def load_state(path) -> SessionState:
    """Read an FNLS1 checkpoint back into a :class:`SessionState`."""
    raw = pathlib.Path(path).read_bytes()
    if raw[: len(_MAGIC)] != _MAGIC:
        raise ValueError(
            f"{path}: not a FedNL session checkpoint (bad magic "
            f"{raw[:len(_MAGIC)]!r}; expected {_MAGIC!r})"
        )
    n = int.from_bytes(raw[8:16], "little")
    header = json.loads(raw[16 : 16 + n].decode())
    if header.get("version") != _VERSION:
        raise ValueError(
            f"{path}: checkpoint version {header.get('version')} not "
            f"supported (this build reads version {_VERSION})"
        )
    off = 16 + n
    arrays: dict[str, np.ndarray] = {}
    for name in sorted(header["arrays"]):
        info = header["arrays"][name]
        dt = np.dtype(info["dtype"])
        count = int(np.prod(info["shape"], dtype=np.int64)) if info["shape"] else 1
        nbytes = dt.itemsize * count
        arrays[name] = np.frombuffer(
            raw[off : off + nbytes], dtype=dt
        ).reshape(info["shape"]).copy()
        off += nbytes
    rec_x = arrays.pop("__records_x__", None)
    records = tuple(
        _record_from_jsonable(d, rec_x[i] if d["has_x"] else None)
        for i, d in enumerate(header["records"])
    )
    return SessionState(
        spec=spec_from_dict(header["spec"]),
        algorithm=header["algorithm"],
        backend=header["backend"],
        round=int(header["round"]),
        meta=header["meta"],
        arrays=arrays,
        records=records,
        version=int(header["version"]),
    )


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------

class Session:
    """One live run at round granularity.  Created by :func:`open_session`;
    drives a backend :class:`repro.api.registry.SessionHandle`."""

    def __init__(self, spec, algo, backend, handle, records=()):
        self.spec = spec
        self._algo = algo
        self._backend = backend
        self._handle = handle
        self._builder = RunReportBuilder(spec, algo.name, backend.name)
        self._builder.extend(list(records))
        self._observers: list[Callable[[RoundRecord], None]] = []
        self._closed = False

    # --- introspection ----------------------------------------------------

    @property
    def round(self) -> int:
        """Rounds executed so far (checkpoint rounds included after restore)."""
        return self._handle.round

    @property
    def records(self) -> tuple[RoundRecord, ...]:
        return tuple(self._builder.records)

    @property
    def state(self) -> SessionState:
        """Frozen serializable snapshot of the run (see :func:`save_state`)."""
        meta, arrays = self._handle.snapshot()
        return SessionState(
            spec=self.spec,
            algorithm=self._algo.name,
            backend=self._backend.name,
            round=self.round,
            meta=meta,
            arrays=arrays,
            records=self.records,
        )

    # --- observers --------------------------------------------------------

    def on_round(self, fn: Callable[[RoundRecord], None]):
        """Register an observer streamed every produced RoundRecord (in round
        order).  Returns ``fn`` so it can double as a decorator."""
        self._observers.append(fn)
        return fn

    # --- execution --------------------------------------------------------

    def step(self, n: int = 1) -> list[RoundRecord]:
        """Advance exactly ``n`` rounds (not capped by ``spec.rounds`` — the
        cap is :meth:`run`'s job) and return their records.  Composable:
        ``step(k); step(m)`` is bit-identical to ``step(k + m)``."""
        if self._closed:
            raise RuntimeError("session is closed")
        if n < 0:
            raise ValueError(f"step count must be >= 0, got {n}")
        if n == 0:
            return []
        rec = _obs.CURRENT
        t0 = _obs.now()
        recs = self._handle.step_rounds(n)
        if rec.enabled:
            # one step_rounds call == one device->host sync of its records
            rec.observe(
                "session.step.s", _obs.now() - t0, backend=self.spec.backend
            )
            rec.add("session.rounds", len(recs), backend=self.spec.backend)
            rec.add("session.host_syncs", backend=self.spec.backend)
        self._builder.extend(recs)
        for rec in recs:
            for fn in self._observers:
                fn(rec)
        return recs

    def run(self, until=None) -> RunReport:
        """Advance under a stop policy and report.

        ``until``: None (the spec's rounds/tol — what ``solve()`` does), an
        int (max TOTAL rounds), a float (grad-norm tol), or a
        :class:`StopPolicy`.  Callable repeatedly: each call continues from
        the current round and returns the cumulative report.
        """
        policy = resolve_policy(until, self.spec)
        if policy.tol is not None and self._algo.kind == "pp":
            raise ValueError(
                "tol-based stopping is undefined for partial participation "
                "(the server never sees the global gradient); use max_rounds "
                "or a predicate on the records instead"
            )
        target = policy.max_rounds
        if not policy.streaming and not self._observers:
            # no per-round consumer: one chunked segment, deferred host sync
            self.step(max(0, target - self.round))
            return self.report()
        while self.round < target:
            recs = self.step(1)
            if not recs:
                break
            if policy.hit(recs[0]):
                break
        return self.report()

    def report(self, spec=None) -> RunReport:
        """The cumulative :class:`RunReport` for the rounds executed so far
        (non-destructive: the session can keep stepping afterwards)."""
        tail = self._handle.finalize()
        return self._builder.build(
            x=tail["x"],
            wall_time_s=self._handle.wall_time_s,
            init_time_s=self._handle.init_time_s,
            final_grad_norm_fn=tail.get("final_grad_norm_fn"),
            extras=tail.get("extras"),
            spec=spec,
        )

    # --- persistence / lifecycle ------------------------------------------

    def save(self, path) -> pathlib.Path:
        """Serialize the current state to ``path`` (FNLS1 checkpoint);
        ``open_session(spec, restore=path)`` resumes it bit-identically."""
        return save_state(self.state, path)

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._handle.close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# open_session
# ---------------------------------------------------------------------------

def open_session(
    spec: ExperimentSpec,
    z=None,
    x0=None,
    restore: str | pathlib.Path | SessionState | None = None,
) -> Session:
    """Open an incremental run of ``spec`` — the Session form of ``solve``.

    ``z`` / ``x0`` mirror :func:`repro.api.solve`.  ``restore`` resumes from
    a checkpoint (a path written by :meth:`Session.save`, or a
    :class:`SessionState`); the spec must describe the same experiment as the
    checkpoint (only run control — rounds / tol / host — may differ;
    :meth:`ExperimentSpec.check_restore_from` rejects anything else loudly).
    """
    from repro.api.facade import check_spec
    from repro.api.registry import get_algorithm, get_backend
    from repro.runtime import configure_jax

    configure_jax()
    state = None
    if restore is not None:
        state = restore if isinstance(restore, SessionState) else load_state(restore)
        spec.check_restore_from(state.spec)
        if x0 is not None:
            raise ValueError(
                "x0 cannot be combined with restore: the checkpoint already "
                "fixes the trajectory (x0 only applies to fresh runs)"
            )
    algo = get_algorithm(spec.algorithm)
    backend = get_backend(spec.backend)
    check_spec(spec, algo, backend, z=z, x0=x0)
    if not backend.supports_sessions:
        raise ValueError(
            f"backend {spec.backend!r} does not support sessions (no "
            "Backend.open); run it to completion with solve(spec) instead"
        )
    if z is None and backend.needs_problem:
        z = spec.data.build()
    handle = backend.open(spec, algo, z, x0, restore=state)
    return Session(
        spec, algo, backend, handle,
        records=state.records if state is not None else (),
    )
