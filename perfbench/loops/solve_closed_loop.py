"""Closed loop with one caller: warm FedNL solves to a gradient tolerance.

Traffic parameters (the traffic file): ``compressor``, ``tol``,
``round_cap``, ``check_solves`` (how many of the window's solves the
reference recomputes when the compressor draws from the spec seed) and
``gnorm_floor`` (per-round gradient norms are compared while the
reference's is above this share of its first).

Set-up builds the problem from ``--seed`` once, places it on the device,
and runs one whole solve (open and run) so that every program the window
uses is compiled and in the cache.  The window then calls, back to back,
``open_session(spec_i, z)`` and ``Session.run()`` with spec seed
``--seed + i``, until ``--seconds`` have passed; the solve running at the
close finishes and counts.

End-to-end: ``solve_s``, the wall seconds of all solves the window started
over their count (open and run, each call from x = 0 to the tolerance).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench.lib import datagen, reference, work


@dataclasses.dataclass
class Solve:
    spec_seed: int
    x: np.ndarray
    grad_norms: np.ndarray
    sent_elems: np.ndarray
    sent_bits: np.ndarray

    @property
    def rounds(self) -> int:
        return len(self.grad_norms)


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    seed: int
    z_host: np.ndarray
    z: object  # the problem on the device
    k: int
    solves: list = dataclasses.field(default_factory=list)


def budget(config: dict) -> int:
    d = config["d"]
    return max(1, min(work.triu_size(d), int(config["k_multiplier"] * d)))


def make_spec(config: dict, traffic: dict, spec_seed: int):
    from repro.api import CompressorSpec, DataSpec, ExperimentSpec

    # the problem is handed to open_session, so spec.data only names its shape
    return ExperimentSpec(
        lam=config["lam"],
        data=DataSpec(shape=(config["d"], config["n_clients"], config["n_i"]), seed=0),
        algorithm=config["algorithm"],
        compressor=CompressorSpec(traffic["compressor"], config["k_multiplier"]),
        option=config["option"],
        hess0=config["hess0"],
        backend=config["backend"],
        rounds=traffic["round_cap"],
        tol=traffic["tol"],
        seed=spec_seed,
    )


def _solve(st: State, spec_seed: int, spans) -> Solve:
    from repro.api import open_session

    spec = make_spec(st.config, st.traffic, spec_seed)
    with spans("open"):
        session = open_session(spec, st.z)
    with spans("run"):
        report = session.run()
    session.close()
    return Solve(
        spec_seed=spec_seed,
        x=np.asarray(report.x, dtype=np.float64),
        grad_norms=np.asarray(report.grad_norms, dtype=np.float64),
        sent_elems=np.asarray([r.sent_elems for r in report.records], dtype=np.int64),
        sent_bits=np.asarray(report.sent_bits, dtype=np.int64),
    )


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    import jax
    import jax.numpy as jnp

    z_host = datagen.make_problem(config, seed)
    z = jax.device_put(jnp.asarray(z_host, dtype=config["dtype"]))
    st = State(config, traffic, seed, z_host, z, budget(config))
    _solve(st, seed, spans)  # compiles and warms every program the window runs
    return st


def window(st: State, seconds: float, spans, poll) -> dict:
    end = time.perf_counter() + seconds
    i = 0
    while time.perf_counter() < end:
        with spans("solve"):
            st.solves.append(_solve(st, st.seed + i, spans))
        poll()
        i += 1
    rounds = sum(s.rounds for s in st.solves)
    return {
        "attempted": len(st.solves),
        "failed": sum(1 for s in st.solves if not s.grad_norms[-1] < st.traffic["tol"]),
        "counters": {
            "solves": len(st.solves),
            "rounds": rounds,
            "rounds_per_solve": [s.rounds for s in st.solves],
        },
        "work_per_round": work.round_work(
            st.config["n_clients"], st.config["n_i"], st.config["d"], st.k
        ),
    }


def end_to_end(st: State, spans) -> dict:
    return {"solve_s": sum(spans.seconds("solve")) / len(spans.seconds("solve"))}


def release(st: State) -> None:
    """Free the program's device state before the reference runs."""
    st.z = None


def sample(st: State, n: int) -> list[int]:
    """Window solves the reference recomputes: drawn from the seed, always
    with the longest (most rounds) among them."""
    longest = max(range(len(st.solves)), key=lambda i: st.solves[i].rounds)
    rest = [i for i in range(len(st.solves)) if i != longest]
    rng = np.random.default_rng(st.seed)
    picked = rng.permutation(rest)[: max(0, n - 1)].tolist() if rest else []
    return sorted([longest] + picked)


def compare(got: Solve, ref: reference.RefSolve, floor: float) -> dict:
    """The compared numbers of one solve against its reference."""
    x_rel = float(np.linalg.norm(got.x - ref.x) / np.linalg.norm(ref.x))
    common = min(got.rounds, ref.rounds)
    g, gr = got.grad_norms[:common], ref.grad_norms[:common]
    live = gr > floor * gr[0]
    gnorm_rel = float(np.max(np.abs(g[live] - gr[live]) / gr[live])) if live.any() else 0.0
    return {
        "x_rel": x_rel,
        "gnorm_rel": gnorm_rel,
        "rounds_gap": float(abs(got.rounds - ref.rounds)),
        "sent_elems_gap": float(np.max(np.abs(got.sent_elems[:common] - ref.sent_elems[:common]))),
        "sent_bits_gap": float(np.max(np.abs(got.sent_bits[:common] - ref.sent_bits[:common]))),
    }


def reference_solve(st: State, spec_seed: int, dtype=np.float64) -> reference.RefSolve:
    c, t = st.config, st.traffic
    return reference.fednl_solve(
        st.z_host,
        lam=c["lam"],
        compressor=t["compressor"],
        k=st.k,
        tol=t["tol"],
        round_cap=t["round_cap"],
        seed=spec_seed,
        dtype=dtype,
    )


# compressors whose selection draws from the spec seed; the others give
# every solve of one problem the same trajectory
SEEDED = {"toplek"}


def check(st: State) -> dict:
    """Worst compared numbers over the checked solves, and the worst final
    gradient norm over all window solves as a share of the tolerance.

    With a deterministic compressor one reference covers every solve of
    the window, so all are compared; otherwise a sample of
    ``check_solves`` drawn from the seed, the longest among them."""
    t = st.traffic
    seeded = t["compressor"] in SEEDED
    picked = sample(st, t["check_solves"]) if seeded else range(len(st.solves))
    refs: dict = {}
    worst: dict = {}
    for i in picked:
        got = st.solves[i]
        key = got.spec_seed if seeded else None
        if key not in refs:
            refs[key] = reference_solve(st, got.spec_seed)
        for name, v in compare(got, refs[key], t["gnorm_floor"]).items():
            worst[name] = max(worst.get(name, 0.0), v)
    worst["tol_ratio"] = max(float(s.grad_norms[-1]) / t["tol"] for s in st.solves)
    return worst
