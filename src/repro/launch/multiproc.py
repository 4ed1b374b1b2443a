"""Multi-process FedNL / FedNL-PP over TCP localhost — master + n workers.

    PYTHONPATH=src python -m repro.launch.multiproc \
        --dataset tiny --compressor topk --rounds 40 --tol 1e-14 --check

    # partial participation (Algorithm 3), 3-of-8 clients per round,
    # 20% fault-injected dropout handled by survivor partial sums:
    PYTHONPATH=src python -m repro.launch.multiproc \
        --algo fednl-pp --tau 3 --drop-prob 0.2 --rounds 60 --check

The master process binds a localhost socket, spawns one OS process per client
(``multiprocessing`` spawn context: each child gets a fresh JAX runtime on
the host CPU — the master keeps any accelerator), and
runs the star event loop of ``repro.comm.star`` (full participation) or
``repro.comm.star_pp`` (FedNL-PP: only the sampled tau clients receive or do
any work each round).  Data distribution follows the paper's experiment
harness: every worker regenerates the deterministic synthetic dataset from
the shared seed and keeps only its own shard — no training data crosses the
wire, exactly the federated premise.

``--check`` reruns the same problem through the single-node simulation
(``run_fednl`` / ``run_fednl_pp``) and reports the max iterate/trajectory
deviation (fault-free runs are designed to be bit-identical; DESIGN.md §5/§5a).
"""

from __future__ import annotations

import argparse
import dataclasses
import multiprocessing as mp
import os
import threading

from repro.core.fednl import FedNLConfig
from repro.runtime import configure_jax


def _build_problem(dataset: str, shape, seed: int):
    # one data pipeline for every backend: the shards a TCP worker builds
    # must be bit-identical to what solve() materializes everywhere else
    from repro.api.spec import DataSpec

    return DataSpec(dataset=dataset or "tiny", shape=shape, seed=seed).build()


def _client_entry(
    client_id: int,
    n_clients: int,
    dataset: str,
    shape,
    cfg_dict: dict,
    seed: int,
    host: str,
    port: int,
    pp: bool = False,
    fault_dict: dict | None = None,
    data_seed: int | None = None,
) -> None:
    """Client process: build shard, dial the master, serve rounds."""
    configure_jax()  # FedNL is FP64 end-to-end
    from repro.comm.transport import connect_to_master

    z = _build_problem(dataset, shape, seed if data_seed is None else data_seed)
    conn = connect_to_master(host, port, client_id)
    if pp:
        from repro.comm.star_pp import StarPPClient
        from repro.comm.transport import FaultSpec

        fault = FaultSpec(**fault_dict) if fault_dict else None
        client = StarPPClient(
            client_id,
            n_clients,
            z[client_id],
            FedNLConfig(**cfg_dict),
            conn,
            seed=seed,
            fault=fault,
        )
    else:
        from repro.comm.star import StarClient

        client = StarClient(
            client_id, n_clients, z[client_id], FedNLConfig(**cfg_dict), conn, seed=seed
        )
    client.run()


def _aggregator_entry(
    agg_id: int,
    subtree,
    n_clients: int,
    d: int,
    dataset: str,
    shape,
    cfg_dict: dict,
    seed: int,
    parent_host: str,
    parent_port: int,
    combine: str,
    data_seed: int | None = None,
) -> None:
    """Aggregator process: bind a listener for the subtree, spawn its
    children (leaf client processes and nested aggregators), dial the
    parent, serve AGG rounds.

    Teardown ordering is the contract (the PR 6 refcount fix, one level
    deeper): the subtree's children are released — connections closed,
    processes joined — BEFORE this node closes its own listener and parent
    connection, so a tree tears down leaves-first and the root's
    ``ClientCluster.close()`` never abandons a grandchild.
    """
    configure_jax()
    from repro.comm.topology import build_aggregator
    from repro.comm.transport import TCPMaster, connect_to_master

    subtree = tuple(subtree)
    listener = TCPMaster(len(subtree), host=parent_host)
    procs: list = []
    children: dict = {}
    parent_conn = None
    try:
        agg_children = set()
        to_spawn = []
        for pos, node in enumerate(subtree):
            if isinstance(node, (tuple, list)):
                agg_children.add(pos)
                # non-daemon: a daemonic process may not spawn its own
                # children, and nested aggregators spawn a subtree
                to_spawn.append(
                    (
                        _aggregator_entry,
                        (
                            pos, tuple(node), n_clients, d, dataset, shape,
                            cfg_dict, seed, parent_host, listener.port,
                            combine, data_seed,
                        ),
                        False,
                    )
                )
            else:
                to_spawn.append(
                    (
                        _client_entry,
                        (
                            int(node), n_clients, dataset, shape, cfg_dict,
                            seed, parent_host, listener.port, False, None,
                            data_seed,
                        ),
                        True,
                    )
                )
        procs = _spawn_procs(to_spawn)
        children = listener.accept_clients()
        parent_conn = connect_to_master(parent_host, parent_port, agg_id)
        cfg = FedNLConfig(**cfg_dict)
        node = build_aggregator(
            agg_id, parent_conn, children, d, cfg,
            combine=combine, agg_children=agg_children,
        )
        node.run()
    finally:
        # children first: conns closed + procs joined before our own
        # listener/parent sockets go away
        for conn in children.values():
            conn.close()
        for p in procs:
            p.join(timeout=60)
        for p in procs:
            if p.is_alive():
                p.terminate()
        listener.close()
        if parent_conn is not None:
            parent_conn.close()


# serializes the PYTHONPATH mutate-spawn-restore window across threads
# (solve_many dispatches star-tcp specs from a worker pool)
_SPAWN_ENV_LOCK = threading.Lock()


def _spawn_procs(targets) -> list:
    """Start one spawn-context process per ``(target, args, daemon)`` triple
    with ``src/`` on the children's PYTHONPATH and ``JAX_PLATFORMS=cpu``
    (mutate-spawn-restore under the shared lock).

    The children model remote clients and aggregators, so they run JAX on
    the host CPU: an accelerator belongs to one process at a time, and the
    master — which has already touched JAX — holds it.  Children capture
    os.environ at start(), so nested spawns — aggregator processes spawning
    their own subtrees — inherit both settings without re-mutating
    anything.  ``daemon`` must be False for any child that spawns processes
    of its own (aggregators)."""
    ctx = mp.get_context("spawn")
    src_dir = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    procs: list = []
    with _SPAWN_ENV_LOCK:
        old = {k: os.environ.get(k) for k in ("PYTHONPATH", "JAX_PLATFORMS")}
        os.environ["PYTHONPATH"] = src_dir + (
            os.pathsep + old["PYTHONPATH"] if old["PYTHONPATH"] else ""
        )
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for target, args, daemon in targets:
                p = ctx.Process(target=target, args=args, daemon=daemon)
                p.start()
                procs.append(p)
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    return procs

# every live (not yet closed) cluster, so a serving engine — or a test —
# can prove no process fleet leaked after shutdown/eviction; guarded by its
# own lock because clusters are created/closed from pool threads
_LIVE_CLUSTERS: "set[ClientCluster]" = set()
_LIVE_LOCK = threading.Lock()


class ClientCluster:
    """A live fleet of TCP client processes around one bound master socket.

    Extracted from the closed run-everything scaffold so the star-tcp
    Session backend can keep the cluster open across ``step()`` calls (and
    across a save/resume boundary: a resumed session simply spawns a fresh
    cluster — client state is rebuilt by protocol replay, never persisted).
    ``run_multiproc[_pp]`` still compose it into the classic bind -> spawn ->
    run -> join shape.

    Lifecycle under shared use (the multi-tenant serving engine holds many
    clusters at once): each cluster is reference-counted — ``acquire()``
    adds a holder, ``release()`` drops one and tears the fleet down when the
    last holder lets go — and ``close()`` is an idempotent force-teardown
    that any holder may call (an engine evicting a star-tcp tenant mid-run,
    or its exception path, must never leak subprocesses no matter how many
    holders remain).  ``live_count()`` / ``close_all()`` expose the global
    registry of not-yet-closed clusters for shutdown sweeps and leak
    assertions.
    """

    def __init__(
        self,
        dataset: str,
        shape,
        seed: int,
        host: str = "127.0.0.1",
        pp: bool = False,
        fault_dict: dict | None = None,
        data_seed: int | None = None,
        cfg: FedNLConfig | None = None,
    ):
        configure_jax()
        from repro.comm.transport import TCPMaster

        # dims only — the master never holds the training data; workers
        # rebuild their shard from the data seed
        from repro.api.spec import DataSpec

        d, n_clients, _ = DataSpec(
            dataset=dataset or "tiny",
            shape=shape,
            seed=seed if data_seed is None else data_seed,
        ).dims()
        self.d = d
        self.n_clients = n_clients
        self._master = TCPMaster(n_clients, host=host)
        self._init_lifecycle()
        cfg_dict = dataclasses.asdict(cfg) if cfg is not None else {}
        self.procs: list = []
        self.conns: dict = {}
        # spawn + accept under one guard: a mid-loop start() failure (fd/pid
        # exhaustion under solve_many's concurrent star-tcp pool) must not
        # leak the bound master socket or already-started children
        try:
            self.procs = _spawn_procs(
                [
                    (
                        _client_entry,
                        (
                            i, n_clients, dataset, shape, cfg_dict, seed,
                            host, self._master.port, pp, fault_dict,
                            data_seed,
                        ),
                        True,
                    )
                    for i in range(n_clients)
                ]
            )
            self.conns = self._master.accept_clients()
        except Exception:
            self.close(join_timeout=5)
            raise

    def _init_lifecycle(self) -> None:
        """Refcount + leak-registry bookkeeping shared with subclasses
        (registration happens only after the master socket bound — a failed
        bind must not leave a phantom entry in the _LIVE registry)."""
        self._refs = 1  # the creator holds the first reference
        self._closed = False
        self._lifecycle_lock = threading.Lock()
        with _LIVE_LOCK:
            _LIVE_CLUSTERS.add(self)

    def acquire(self) -> "ClientCluster":
        """Register another holder of this (open) cluster."""
        with self._lifecycle_lock:
            if self._closed:
                raise RuntimeError("cannot acquire a closed ClientCluster")
            self._refs += 1
        return self

    def release(self, join_timeout: float = 60) -> None:
        """Drop one holder; the last release tears the fleet down."""
        with self._lifecycle_lock:
            self._refs = max(0, self._refs - 1)
            last = self._refs == 0
        if last:
            self.close(join_timeout=join_timeout)

    def close(self, join_timeout: float = 60) -> None:
        """Close connections, join (then terminate) workers, unbind.

        Idempotent force-teardown: safe to call from any holder (or twice —
        e.g. an engine's exception path after a normal release), regardless
        of the reference count.
        """
        with self._lifecycle_lock:
            if self._closed:
                return
            self._closed = True
            self._refs = 0
        with _LIVE_LOCK:
            _LIVE_CLUSTERS.discard(self)
        for conn in self.conns.values():
            conn.close()
        for p in self.procs:
            p.join(timeout=join_timeout)
        for p in self.procs:
            if p.is_alive():
                p.terminate()
        self._master.close()

    @property
    def closed(self) -> bool:
        return self._closed

    @classmethod
    def live_count(cls) -> int:
        """Number of clusters created but not yet closed (leak probe)."""
        with _LIVE_LOCK:
            return len(_LIVE_CLUSTERS)

    @classmethod
    def close_all(cls, join_timeout: float = 10) -> int:
        """Force-close every live cluster (engine shutdown sweep); returns
        how many were closed."""
        with _LIVE_LOCK:
            stragglers = list(_LIVE_CLUSTERS)
        for c in stragglers:
            c.close(join_timeout=join_timeout)
        return len(stragglers)


class TreeClientCluster(ClientCluster):
    """A live process *tree* for a tree-of-stars run (repro.comm.topology).

    The root binds one listener; each immediate child is an aggregator
    process (``_aggregator_entry``) owning a subtree — which in turn spawns
    its leaf client processes and any deeper aggregators.  ``conns`` are
    keyed by root-subtree index (the aggregator node ids a TreeMaster
    expects), not client ids.  Shares :class:`ClientCluster`'s refcounted
    lifecycle and ``_LIVE`` registry, so ``live_count()``/``close_all()``
    leak probes cover process trees too; teardown is leaves-first — each
    aggregator releases its children before closing its own sockets, and
    only then does the root's :meth:`close` join the aggregator processes.
    """

    def __init__(
        self,
        dataset: str,
        shape,
        seed: int,
        topology,
        host: str = "127.0.0.1",
        data_seed: int | None = None,
        cfg: FedNLConfig | None = None,
    ):
        configure_jax()
        from repro.api.spec import DataSpec
        from repro.comm.transport import TCPMaster

        d, n_clients, _ = DataSpec(
            dataset=dataset or "tiny",
            shape=shape,
            seed=seed if data_seed is None else data_seed,
        ).dims()
        self.d = d
        self.n_clients = n_clients
        tree = topology.resolve(n_clients)
        self._master = TCPMaster(len(tree), host=host)
        self._init_lifecycle()
        cfg_dict = dataclasses.asdict(cfg) if cfg is not None else {}
        self.procs = []
        self.conns = {}
        try:
            self.procs = _spawn_procs(
                [
                    (
                        _aggregator_entry,
                        (
                            i, subtree, n_clients, d, dataset, shape,
                            cfg_dict, seed, host, self._master.port,
                            topology.combine, data_seed,
                        ),
                        # aggregators spawn their own children, so they
                        # cannot be daemonic
                        False,
                    )
                    for i, subtree in enumerate(tree)
                ]
            )
            self.conns = self._master.accept_clients()
        except Exception:
            self.close(join_timeout=5)
            raise


def _run_with_clients(
    cfg: FedNLConfig,
    dataset: str,
    shape,
    seed: int,
    host: str,
    master_fn,
    pp: bool = False,
    fault_dict: dict | None = None,
    data_seed: int | None = None,
):
    """Shared scaffold: bind, spawn one process per client, run, join.

    ``master_fn(conns, d) -> result`` is the hub loop (full or PP).
    ``data_seed`` decouples the synthetic-data seed from the algorithm PRNG
    seed (default: same, the historical behaviour).
    """
    cluster = ClientCluster(
        dataset,
        shape,
        seed,
        host=host,
        pp=pp,
        fault_dict=fault_dict,
        data_seed=data_seed,
        cfg=cfg,
    )
    try:
        return master_fn(cluster.conns, cluster.d)
    finally:
        cluster.close()


def run_multiproc(
    cfg: FedNLConfig,
    dataset: str = "tiny",
    shape: tuple[int, int, int] | None = None,
    rounds: int = 100,
    tol: float = 0.0,
    seed: int = 0,
    host: str = "127.0.0.1",
    data_seed: int | None = None,
):
    """Library entry: spawn client processes, run the master loop, join.

    Returns the :class:`repro.comm.star.StarRunResult` of the master.
    (Prefer ``repro.api.solve`` with ``backend='star-tcp'`` — this is the
    driver that backend wraps.)
    """
    from repro.comm.star import run_star_master

    def master_fn(conns, d):
        return run_star_master(conns, d, cfg, rounds=rounds, tol=tol)

    return _run_with_clients(
        cfg, dataset, shape, seed, host, master_fn, data_seed=data_seed
    )


def run_multiproc_pp(
    cfg: FedNLConfig,
    tau: int,
    dataset: str = "tiny",
    shape: tuple[int, int, int] | None = None,
    rounds: int = 100,
    seed: int = 0,
    host: str = "127.0.0.1",
    on_dropout: str = "partial",
    fault=None,
    data_seed: int | None = None,
):
    """FedNL-PP over TCP localhost: tau-of-n sampling per round, optional
    fault injection (``fault``: a :class:`repro.comm.transport.FaultSpec`).

    Returns the :class:`repro.comm.star_pp.StarPPRunResult` of the master.
    (Prefer ``repro.api.solve`` with ``backend='star-tcp'`` — this is the
    driver that backend wraps.)
    """
    from repro.comm.star_pp import StarPPMaster

    def master_fn(conns, d):
        master = StarPPMaster(
            conns, d, cfg, tau, seed=seed, on_dropout=on_dropout
        )
        return master.run(rounds)

    return _run_with_clients(
        cfg,
        dataset,
        shape,
        seed,
        host,
        master_fn,
        pp=True,
        fault_dict=dataclasses.asdict(fault) if fault is not None else None,
        data_seed=data_seed,
    )


def main() -> None:
    """CLI: build one declarative ExperimentSpec, solve it on star-tcp, and
    (with --check) re-solve the *same spec* on the local backend — the
    cross-backend reproducibility claim as a one-field change."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="fednl", choices=["fednl", "fednl-pp"])
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--compressor", default="topk")
    ap.add_argument("--k-multiplier", type=float, default=8.0)
    ap.add_argument("--option", default="B", choices=["A", "B"])
    ap.add_argument("--lam", type=float, default=1e-3)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--tol", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="compare against the single-node simulation trajectory")
    # FedNL-PP options
    ap.add_argument("--tau", type=int, default=0,
                    help="PP: sampled clients per round (default n//2)")
    ap.add_argument("--on-dropout", default="partial",
                    choices=["partial", "resample"])
    ap.add_argument("--drop-prob", type=float, default=0.0)
    ap.add_argument("--straggler-prob", type=float, default=0.0)
    ap.add_argument("--straggler-delay", type=float, default=0.05)
    args = ap.parse_args()

    import numpy as np

    from repro.api import (
        CompressorSpec,
        DataSpec,
        ExperimentSpec,
        FaultSpec,
        solve,
    )

    pp = args.algo == "fednl-pp"
    fault = None
    if pp and (args.drop_prob > 0 or args.straggler_prob > 0):
        fault = FaultSpec(
            drop_prob=args.drop_prob,
            straggler_prob=args.straggler_prob,
            straggler_delay_s=args.straggler_delay,
            seed=args.seed,
        )
    spec = ExperimentSpec(
        lam=args.lam,
        data=DataSpec(dataset=args.dataset, seed=args.seed),
        algorithm=args.algo,
        compressor=CompressorSpec(args.compressor, args.k_multiplier),
        option=args.option,
        mu=args.lam,
        tau=args.tau if (pp and args.tau > 0) else None,
        on_dropout=args.on_dropout,
        fault=fault,
        backend="star-tcp",
        rounds=args.rounds,
        tol=args.tol,
        seed=args.seed,
    )
    rep = solve(spec)
    if rep.rounds == 0:
        print("rounds=0 (nothing to run; INIT/STOP handshake only)")
        return
    print(rep.summary())
    frame_kb = rep.extras["measured_frame_bytes"].sum() / 1e3
    bits_match = (rep.extras["measured_payload_bits"] == rep.sent_bits_payload).all()
    print(f"uplink: measured {frame_kb:.1f} kB framed, payload bits "
          f"measured=={'analytic' if bits_match else 'MISMATCH'}")
    if pp:
        parts = sum(len(p) for p in rep.participants)
        drops = sum(len(d) for d in rep.dropped)
        print(f"tau={rep.extras['tau']} contributions={parts} drops={drops}")

    if args.check:
        if pp:
            # the PP diagnostic rebuilds the problem on the master; only pay
            # for it when the user asked for the parity check
            print(f"||grad(x_final)||={rep.final_grad_norm:.3e}")
        if pp and fault is not None:
            # no fault-free reference to compare a faulted trajectory against
            print("--check skipped: faulted PP runs diverge from the "
                  "fault-free simulation by design")
            return
        ref = solve(spec.replace(backend="local", fault=None))
        if pp:
            dx = float(np.max(np.abs(rep.x_hist - ref.x_hist)))
            print(f"vs single-node PP: max|x_tcp - x_sim|={dx:.3e} "
                  "(fault-free runs are bit-identical; target 0)")
        else:
            r = min(rep.rounds, ref.rounds)
            dx = float(np.max(np.abs(rep.x - ref.x)))
            dg = float(np.max(np.abs(rep.grad_norms[:r] - ref.grad_norms[:r])))
            print(f"vs single-node: max|x_tcp - x_sim|={dx:.3e} "
                  f"max|gn_tcp - gn_sim|={dg:.3e} (paper target <= 1e-8)")


if __name__ == "__main__":
    main()
