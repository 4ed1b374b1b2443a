"""Built-in execution backends + algorithm registrations.

Each backend is a strategy object wrapping an *existing* driver — the
simulation round builders (``repro.core``), the sharded round
(``repro.distributed``), the star masters (``repro.comm.star[_pp]``) and the
multi-process TCP client cluster (``repro.launch.multiproc``) — exposed at
round granularity through ``Backend.open() -> SessionHandle`` (DESIGN.md
§10).  ``solve()`` is the open -> run -> close composition of the same
handles, so the streaming path IS the batch path: the parity suite pins it
to the golden traces bit-for-bit and ``repro.core.runner`` stays the
independent reference.  The simulation handles execute chunked segments
between yields (metrics stay on-device until a chunk ends); the star handles
drive the wire masters one protocol round per step and rebuild client state
on restore by replaying broadcasts (no client state on disk).

Capability matrix (what ``Backend.supports`` encodes):

  backend        fednl  fednl-ls  fednl-pp
  local            x       x         x
  sharded          x       -         -     (no sharded LS/PP round yet)
  star-loopback    x       -         x     (no LS wire protocol)
  star-tcp         x       -         x
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import (
    Algorithm,
    Backend,
    SessionHandle,
    register_algorithm,
    register_backend,
)
from repro.api.report import RoundRecord
from repro.core.fednl import fednl_init, make_fednl_round
from repro.core.fednl_batch import (
    make_fednl_batch_round,
    make_fednl_ls_batch_round,
)
from repro.core.fednl_ls import make_fednl_ls_round
from repro.core.fednl_pp import fednl_pp_init, make_fednl_pp_round
from repro.core.runner import eval_full

# ---------------------------------------------------------------------------
# built-in algorithms (Algorithms 1-3 of the paper)
# ---------------------------------------------------------------------------

FEDNL = register_algorithm(
    Algorithm(
        name="fednl",
        kind="full",
        init=fednl_init,
        make_round=lambda z, cfg, tau=None: make_fednl_round(z, cfg),
        make_batch_round=make_fednl_batch_round,
    )
)

FEDNL_LS = register_algorithm(
    Algorithm(
        name="fednl-ls",
        kind="full",
        line_search=True,
        init=fednl_init,
        make_round=lambda z, cfg, tau=None: make_fednl_ls_round(z, cfg),
        make_batch_round=make_fednl_ls_batch_round,
    )
)

FEDNL_PP = register_algorithm(
    Algorithm(
        name="fednl-pp",
        kind="pp",
        init=fednl_pp_init,
        make_round=make_fednl_pp_round,
    )
)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _opt_int(value) -> int | None:
    return None if value is None else int(value)


def _pp_final_grad_norm(z, x, lam: float) -> float:
    _, g = eval_full(z, jnp.asarray(x), lam)
    return float(jnp.linalg.norm(g))


# ---------------------------------------------------------------------------
# state/record helpers (public: the serving engine steps the same algorithm
# states externally and must serialize/record them byte- and bit-identically)
# ---------------------------------------------------------------------------

def state_arrays(state, prefix: str = "state.") -> dict[str, np.ndarray]:
    """NamedTuple algorithm state -> checkpoint arrays."""
    return {prefix + f: np.asarray(v) for f, v in zip(state._fields, state)}


def restored_state(state0, restore, place=jnp.asarray, prefix: str = "state."):
    """Rebuild an algorithm-state NamedTuple from checkpoint arrays, using a
    freshly initialized ``state0`` as the structural template (``place``
    controls device placement — the sharded backend re-shards per field)."""
    missing = [f for f in state0._fields if prefix + f not in restore.arrays]
    if missing:
        raise ValueError(
            f"checkpoint is missing state arrays {missing} for backend "
            f"{restore.backend!r} (truncated or foreign checkpoint?)"
        )
    return type(state0)(
        **{
            f: place(restore.arrays[prefix + f], ref)
            for f, ref in zip(state0._fields, state0)
        }
    )


def full_round_record(r: int, m) -> RoundRecord:
    """One full-participation simulation-metrics row -> RoundRecord.

    Shared by the local session handle and the serving engine's batched
    lane: the host-side float()/int() materialization is part of the
    bit-parity surface, so there is exactly one copy of it."""
    return RoundRecord(
        round=r,
        grad_norm=float(m.grad_norm),
        f=float(m.f),
        l=float(m.l),
        sent_elems=int(m.sent_elems),
        sent_bits=int(m.sent_bits),
        sent_bits_payload=int(m.sent_bits_payload),
        sent_bits_wire=int(m.sent_bits_wire),
        ls_steps=_opt_int(getattr(m, "ls_steps", None)),
    )


def pp_round_record(r: int, m) -> RoundRecord:
    """One FedNL-PP simulation-metrics row -> RoundRecord."""
    return RoundRecord(
        round=r,
        l=float(m.l),
        sent_elems=int(m.sent_elems),
        sent_bits=int(m.sent_bits),
        sent_bits_payload=int(m.sent_bits_payload),
        sent_bits_wire=int(m.sent_bits_wire),
        x=np.asarray(m.x),
        participants=tuple(int(i) for i in np.asarray(m.idx)),
        dropped=(),
    )


# ---------------------------------------------------------------------------
# local: the single-process simulation (vmapped clients, jitted round)
# ---------------------------------------------------------------------------

class _LocalSessionHandle(SessionHandle):
    """Round-granular form of the ``run_fednl`` / ``run_fednl_pp`` loop:
    identical init -> jit -> warm-up -> iterate sequence (bit-parity pinned
    by tests/test_api.py).  ``step_rounds(n)`` executes one chunked segment:
    metrics stay on-device until the chunk ends, so the chunk is the only
    host sync and an observer-free ``run()`` keeps the monolithic solve's
    deferred-sync profile."""

    def __init__(self, spec, algo: Algorithm, z, x0, restore=None):
        self._spec = spec
        self._algo = algo
        self._z = z
        self._cfg = spec.fednl_config()
        self._tau = spec.tau_for(z.shape[0]) if algo.kind == "pp" else None
        self.round = int(restore.round) if restore is not None else 0
        self.wall_time_s = 0.0
        t0 = time.perf_counter()
        state = algo.init(z, self._cfg, x0=x0, seed=spec.seed)
        if restore is not None:
            state = restored_state(
                state, restore, place=lambda arr, ref: jnp.asarray(arr)
            )
        self._state = state
        self._round_fn = jax.jit(algo.make_round(z, self._cfg, self._tau))
        # warm-up compile outside the solve clock (paper separates init/solve)
        state_c, _ = self._round_fn(state)
        jax.block_until_ready(state_c)
        self.init_time_s = time.perf_counter() - t0

    def step_rounds(self, n: int) -> list[RoundRecord]:
        raw = []
        t1 = time.perf_counter()
        for _ in range(n):
            self._state, m = self._round_fn(self._state)
            raw.append(m)
        jax.block_until_ready(
            self._state.x if self._algo.kind == "full" else self._state.h_global
        )
        self.wall_time_s += time.perf_counter() - t1
        r0 = self.round
        self.round += n
        if self._algo.kind == "full":
            return [full_round_record(r0 + i, m) for i, m in enumerate(raw)]
        return [pp_round_record(r0 + i, m) for i, m in enumerate(raw)]

    def snapshot(self) -> tuple[dict, dict[str, np.ndarray]]:
        return {"kind": self._algo.kind}, state_arrays(self._state)

    def finalize(self) -> dict:
        if self._algo.kind == "full":
            return {"x": np.asarray(self._state.x)}
        # the deployable model: Algorithm-3 line 4 on the current invariants
        # (same eager ops as run_fednl_pp / the star master — bit-comparable)
        from repro.linalg import cholesky_solve, unpack_triu

        z, state, lam = self._z, self._state, self._cfg.lam
        d = z.shape[-1]
        x_final = cholesky_solve(
            unpack_triu(state.h_global, d)
            + state.l_global * jnp.eye(d, dtype=jnp.float64),
            state.g_global,
        )
        return {
            "x": np.asarray(x_final),
            "final_grad_norm_fn": lambda: _pp_final_grad_norm(z, x_final, lam),
            "extras": {"tau": self._tau},
        }


class LocalBackend(Backend):
    name = "local"
    supports_x0 = True
    supports_sessions = True

    def open(self, spec, algo: Algorithm, z, x0, restore=None) -> SessionHandle:
        return _LocalSessionHandle(spec, algo, z, x0, restore=restore)


# ---------------------------------------------------------------------------
# sharded: clients shard_mapped across mesh devices (repro.distributed)
# ---------------------------------------------------------------------------

class _ShardedSessionHandle(SessionHandle):
    """Same chunked-segment discipline as the local handle, over the
    shard_mapped round; restore re-places each checkpoint array with the
    sharding of a freshly initialized state."""

    def __init__(self, spec, algo: Algorithm, z, x0, restore=None):
        from repro.distributed import (
            make_sharded_fednl_round,
            shard_problem,
            sharded_fednl_init,
        )

        self._spec = spec
        cfg = spec.fednl_config()
        self._n_dev = (
            spec.devices if spec.devices is not None else jax.device_count()
        )
        self.round = int(restore.round) if restore is not None else 0
        self.wall_time_s = 0.0
        t0 = time.perf_counter()
        mesh = jax.make_mesh((self._n_dev,), ("data",))
        zs = shard_problem(z, mesh)
        state = sharded_fednl_init(zs, cfg, mesh, seed=spec.seed)
        if restore is not None:
            state = restored_state(
                state,
                restore,
                place=lambda arr, ref: jax.device_put(arr, ref.sharding),
            )
        self._state = state
        self._data_devices = len(zs.sharding.device_set)
        self._round_fn = jax.jit(
            make_sharded_fednl_round(zs, cfg, mesh, aggregate=spec.aggregate)
        )
        state_c, _ = self._round_fn(state)
        jax.block_until_ready(state_c.x)
        self.init_time_s = time.perf_counter() - t0

    def step_rounds(self, n: int) -> list[RoundRecord]:
        raw = []
        t1 = time.perf_counter()
        for _ in range(n):
            self._state, m = self._round_fn(self._state)
            raw.append(m)
        jax.block_until_ready(self._state.x)
        self.wall_time_s += time.perf_counter() - t1
        r0 = self.round
        self.round += n
        return [
            RoundRecord(
                round=r0 + i,
                grad_norm=float(m["grad_norm"]),
                f=float(m["f"]),
                l=float(m["l"]),
                sent_elems=int(m["sent_elems"]),
                sent_bits=int(m["sent_bits"]),
                sent_bits_payload=int(m["sent_bits_payload"]),
                sent_bits_wire=int(m["sent_bits_wire"]),
            )
            for i, m in enumerate(raw)
        ]

    def snapshot(self) -> tuple[dict, dict[str, np.ndarray]]:
        return {"kind": "full"}, state_arrays(self._state)

    def finalize(self) -> dict:
        return {
            "x": np.asarray(self._state.x),
            "extras": {
                "devices": self._n_dev,
                "aggregate": self._spec.aggregate,
                # where the samples and the client shifts really sit
                "data_devices": self._data_devices,
                "state_devices": len(self._state.h_local.sharding.device_set),
            },
        }


class ShardedBackend(Backend):
    name = "sharded"
    supports_sessions = True

    def supports(self, algo: Algorithm) -> bool:
        # identity, not name: this backend drives make_sharded_fednl_round
        # directly, so a re-registered custom "fednl" would silently run the
        # builtin algorithm instead of algo.make_round
        return algo is FEDNL  # no sharded LS/PP round builder yet

    def open(self, spec, algo: Algorithm, z, x0, restore=None) -> SessionHandle:
        return _ShardedSessionHandle(spec, algo, z, x0, restore=restore)


# ---------------------------------------------------------------------------
# star backends: the real wire protocol (loopback transport / TCP processes)
# ---------------------------------------------------------------------------

class _StarFullSessionHandle(SessionHandle):
    """Full-participation star master held open at round granularity.

    ``restore`` resumes from a checkpoint: the master's own state (x, H) is
    deserialized, while the freshly built/spawned clients rebuild theirs by
    replaying the checkpointed broadcast history through the normal wire
    protocol (spec + PRNG spine; the replayed uplinks are consumed
    undecoded).  ``closer`` releases the transport (TCP client cluster)."""

    def __init__(self, spec, master, restore=None, closer=None):
        self._spec = spec
        self._master = master
        self._closer = closer
        self._measured_pbits: list[int] = []
        self._frame_bytes: list[int] = []
        self.round = 0
        self.wall_time_s = 0.0
        t0 = time.perf_counter()
        master.init_handshake()
        if restore is not None:
            for r, x_b in enumerate(restore.arrays["x_hist"]):
                master.replay_round(r, x_b)
            master.x = jnp.asarray(restore.arrays["x"])
            master.h_global = jnp.asarray(restore.arrays["h_global"])
            self._measured_pbits = [
                int(b) for b in restore.arrays["measured_payload_bits"]
            ]
            self._frame_bytes = [
                int(b) for b in restore.arrays["measured_frame_bytes"]
            ]
            self.round = int(restore.round)
        self.init_time_s = time.perf_counter() - t0

    def step_rounds(self, n: int) -> list[RoundRecord]:
        recs = []
        t1 = time.perf_counter()
        for i in range(n):
            r = self.round + i
            m = self._master.step_round(r)
            self._measured_pbits.append(m["measured_payload_bits"])
            self._frame_bytes.append(m["measured_frame_bytes"])
            wire_bits = 8 * m["measured_frame_bytes"]
            parts = m.get("participants")
            recs.append(
                RoundRecord(
                    round=r,
                    grad_norm=m["grad_norm"],
                    f=m["f"],
                    sent_bits=(
                        m["sent_bits"]
                        if self._spec.accounting == "payload"
                        else wire_bits
                    ),
                    sent_bits_payload=m["sent_bits"],
                    sent_bits_wire=wire_bits,
                    # async/elastic masters report who contributed/was active;
                    # the plain star reports nothing (everyone, every round)
                    participants=(
                        tuple(int(i) for i in parts)
                        if parts is not None
                        else None
                    ),
                )
            )
        self.wall_time_s += time.perf_counter() - t1
        self.round += n
        return recs

    def snapshot(self) -> tuple[dict, dict[str, np.ndarray]]:
        m = self._master
        d = m.d
        return {"kind": "full"}, {
            "x": np.asarray(m.x),
            "h_global": np.asarray(m.h_global),
            "x_hist": (
                np.stack(m.x_hist)
                if m.x_hist
                else np.zeros((0, d), dtype=np.float64)
            ),
            "measured_payload_bits": np.asarray(self._measured_pbits, np.int64),
            "measured_frame_bytes": np.asarray(self._frame_bytes, np.int64),
        }

    def finalize(self) -> dict:
        return {
            "x": np.asarray(self._master.x),
            "extras": {
                "measured_payload_bits": np.asarray(self._measured_pbits, np.int64),
                "measured_frame_bytes": np.asarray(self._frame_bytes, np.int64),
            },
        }

    def close(self) -> None:
        self._master.stop()
        if self._closer is not None:
            self._closer()
            self._closer = None


class _StarPPSessionHandle(SessionHandle):
    """FedNL-PP star master held open at round granularity.

    Restore replays the checkpointed per-round iterates as SELECT traffic
    (same PRNG spine, same fault draws — resampled replacements included),
    rebuilding the sampled clients' (H_i, l_i, g_i) without any client
    state on disk, then deserializes the master invariants."""

    def __init__(self, spec, master, tau: int, z_fn, restore=None, closer=None):
        self._spec = spec
        self._master = master
        self._tau = tau
        self._z_fn = z_fn
        self._closer = closer
        self._measured_pbits: list[int] = []
        self._frame_bytes: list[int] = []
        self.round = 0
        self.wall_time_s = 0.0
        t0 = time.perf_counter()
        master._init_handshake()
        if restore is not None:
            # the broadcast history rides in the records (every PP record
            # carries its x) — no separate x_hist array in the checkpoint
            for r, rec in enumerate(restore.records):
                master.replay_round(r, rec.x)
            master.h_global = jnp.asarray(restore.arrays["h_global"])
            master.l_global = jnp.asarray(restore.arrays["l_global"])
            master.g_global = jnp.asarray(restore.arrays["g_global"])
            master.key = jnp.asarray(restore.arrays["key"])
            self._measured_pbits = [
                int(b) for b in restore.arrays["measured_payload_bits"]
            ]
            self._frame_bytes = [
                int(b) for b in restore.arrays["measured_frame_bytes"]
            ]
            self.round = int(restore.round)
        self.init_time_s = time.perf_counter() - t0

    def step_rounds(self, n: int) -> list[RoundRecord]:
        recs = []
        t1 = time.perf_counter()
        for i in range(n):
            r = self.round + i
            m = self._master.step_round(r)
            self._measured_pbits.append(m["measured_payload_bits"])
            self._frame_bytes.append(m["measured_frame_bytes"])
            wire_bits = 8 * m["measured_frame_bytes"]
            recs.append(
                RoundRecord(
                    round=r,
                    l=float(m["l"]),
                    sent_bits=(
                        m["sent_bits"]
                        if self._spec.accounting == "payload"
                        else wire_bits
                    ),
                    sent_bits_payload=m["sent_bits"],
                    sent_bits_wire=wire_bits,
                    x=m["x"],
                    participants=tuple(m["participants"]),
                    dropped=tuple(m["dropped"]),
                )
            )
        self.wall_time_s += time.perf_counter() - t1
        self.round += n
        return recs

    def snapshot(self) -> tuple[dict, dict[str, np.ndarray]]:
        m = self._master
        return {"kind": "pp"}, {
            "h_global": np.asarray(m.h_global),
            "l_global": np.asarray(m.l_global),
            "g_global": np.asarray(m.g_global),
            "key": np.asarray(m.key),
            "measured_payload_bits": np.asarray(self._measured_pbits, np.int64),
            "measured_frame_bytes": np.asarray(self._frame_bytes, np.int64),
        }

    def finalize(self) -> dict:
        x_final = np.asarray(self._master._solve_x())
        z_fn, lam = self._z_fn, self._spec.lam
        return {
            "x": x_final,
            # the master never holds the data (star-tcp); rebuild it lazily
            # only if the caller reads the final_grad_norm diagnostic
            "final_grad_norm_fn": (
                (lambda: _pp_final_grad_norm(z_fn(), x_final, lam))
                if z_fn is not None
                else None
            ),
            "extras": {
                "tau": self._tau,
                "measured_payload_bits": np.asarray(self._measured_pbits, np.int64),
                "measured_frame_bytes": np.asarray(self._frame_bytes, np.int64),
            },
        }

    def close(self) -> None:
        self._master.stop()
        if self._closer is not None:
            self._closer()
            self._closer = None


class StarLoopbackBackend(Backend):
    """Full wire protocol (encode -> frame -> decode) over in-process
    loopback connections — deterministic, socket-free."""

    name = "star-loopback"
    supports_faults = True
    supports_sessions = True
    supports_topology = True

    def supports(self, algo: Algorithm) -> bool:
        # identity, not name: the wire event loops implement the builtin
        # protocols only — a re-registered custom "fednl" must be refused,
        # not silently replaced by the builtin trajectory
        return algo is FEDNL or algo is FEDNL_PP  # no LS wire protocol

    def open(self, spec, algo: Algorithm, z, x0, restore=None) -> SessionHandle:
        n_clients, _, d = z.shape
        cfg = spec.fednl_config()
        if algo.kind == "pp":
            from repro.comm.star_pp import StarPPMaster, make_pp_loopback_clients

            tau = spec.tau_for(n_clients)
            conns, drive = make_pp_loopback_clients(
                z, cfg, seed=spec.seed, fault=spec.fault
            )
            master = StarPPMaster(
                conns, d, cfg, tau,
                seed=spec.seed, on_dropout=spec.on_dropout, drive=drive,
            )
            return _StarPPSessionHandle(
                spec, master, tau, lambda: z, restore=restore
            )

        # all full-participation wiring — plain star, tree-of-stars, async,
        # elastic — goes through the one topology construction seam
        # (migration rule 6: masters are built inside repro.comm)
        from repro.comm.topology import open_loopback_master

        master = open_loopback_master(
            z, cfg,
            topology=spec.topology, membership=spec.membership,
            seed=spec.seed,
        )
        return _StarFullSessionHandle(spec, master, restore=restore)


class StarTCPBackend(Backend):
    """Master + one OS process per client over TCP localhost
    (``repro.launch.multiproc``).  Workers regenerate their shard from
    ``spec.data`` — no training data crosses the wire, so only seeded
    synthetic data specs are supported."""

    name = "star-tcp"
    needs_problem = False  # workers rebuild their shards from the data seed
    supports_faults = True
    supports_sessions = True
    supports_topology = True

    def supports(self, algo: Algorithm) -> bool:
        # identity, not name — same reasoning as StarLoopbackBackend
        return algo is FEDNL or algo is FEDNL_PP

    def open(self, spec, algo: Algorithm, z, x0, restore=None) -> SessionHandle:
        if spec.data.libsvm is not None:
            raise ValueError(
                "star-tcp workers rebuild synthetic data from spec.data.seed; "
                "libsvm problems can only run on local/sharded/star-loopback"
            )
        import dataclasses as _dc

        from repro.launch.multiproc import ClientCluster, TreeClientCluster

        cfg = spec.fednl_config()
        pp = algo.kind == "pp"
        topo = spec.topology
        if topo is not None and topo.kind == "tree":
            # process tree: one aggregator process per root subtree, which
            # spawns (and later tears down, leaves-first) its own children
            cluster = TreeClientCluster(
                spec.data.dataset,
                spec.data.shape,
                spec.seed,
                topo,
                host=spec.host,
                data_seed=spec.data.seed,
                cfg=cfg,
            )
        else:
            cluster = ClientCluster(
                spec.data.dataset,
                spec.data.shape,
                spec.seed,
                host=spec.host,
                pp=pp,
                fault_dict=(
                    _dc.asdict(spec.fault) if spec.fault is not None else None
                ),
                data_seed=spec.data.seed,
                cfg=cfg,
            )
        try:
            if pp:
                from repro.comm.star_pp import StarPPMaster

                tau = spec.tau_for(spec.data.dims()[1])
                master = StarPPMaster(
                    cluster.conns, cluster.d, cfg, tau,
                    seed=spec.seed, on_dropout=spec.on_dropout,
                )
                return _StarPPSessionHandle(
                    spec, master, tau, spec.data.build,
                    restore=restore, closer=cluster.close,
                )
            from repro.comm.topology import make_master

            master = make_master(
                cluster.conns, cluster.d, cfg,
                topology=topo, membership=spec.membership,
                n_clients=cluster.n_clients,
            )
            return _StarFullSessionHandle(
                spec, master, restore=restore, closer=cluster.close
            )
        except Exception:
            cluster.close()
            raise


# bound instances: the sweep engine identity-checks against LOCAL_BACKEND
# (an overwritten "local" registration must not be silently batched around)
LOCAL_BACKEND = register_backend(LocalBackend())
SHARDED_BACKEND = register_backend(ShardedBackend())
STAR_LOOPBACK_BACKEND = register_backend(StarLoopbackBackend())
STAR_TCP_BACKEND = register_backend(StarTCPBackend())
