"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):

    compute term    = HLO_FLOPs_per_chip / peak_FLOPs
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = collective_bytes_per_chip / link_bw

HLO_FLOPs / bytes come from `compiled.cost_analysis()` (XLA reports the
post-SPMD, per-device module).  Collective bytes are NOT in cost_analysis:
`collective_bytes(compiled.as_text())` parses the optimized HLO and sums the
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (sync or async-start form).

Target hardware (TPU v5e, Google Cloud documentation "TPU v5e"):
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.  Peaks are
looked up by ``device_kind`` (:func:`machine_for`); a device that is not in
the table is an error, not a default.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes / s / chip
ICI_BW = 50e9  # bytes / s / link


@dataclasses.dataclass(frozen=True)
class Machine:
    """Roofline ceilings of one device.  `analyze` takes one explicitly:
    :func:`machine_for` looks a chip up by ``device_kind``, and
    :func:`measure_cpu_machine` measures a CPU host (whose numbers are
    never device metrics)."""

    name: str
    peak_flops: float  # flop / s
    hbm_bw: float  # bytes / s
    ici_bw: float  # bytes / s / link (0 -> no interconnect term)


TPU_V5E = Machine("tpu-v5e", PEAK_FLOPS, HBM_BW, ICI_BW)

# keyed by jax.Device.device_kind as the TPU runtime reports it
MACHINES = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def machine_for(device_kind: str) -> Machine:
    """The roofline ceilings of a device, by its ``device_kind``."""
    try:
        return MACHINES[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device_kind {device_kind!r}; known: "
            f"{sorted(MACHINES)}"
        ) from None


def measure_cpu_machine(*, n: int = 1024, dtype=None, reps: int = 3) -> Machine:
    """Empirical single-host Machine: peak = best measured dense-gemm flop
    rate (f64 by default — the FedNL payload dtype), memory bw from a big
    copy.  A *measured* ceiling is the honest roofline for gating CPU
    benchmark claims — an advertised spec would let an impossible 'speedup'
    (e.g. a benchmark accidentally timing a cached result) pass the gate.
    """
    import time

    import jax
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), dtype=dtype)
    mm = jax.jit(lambda a: a @ a)
    mm(a).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        mm(a).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    peak = 2.0 * n**3 / best

    cp = jax.jit(lambda a: a + 1.0)
    cp(a).block_until_ready()
    t0 = time.perf_counter()
    cp(a).block_until_ready()
    bw = 2.0 * a.nbytes / (time.perf_counter() - t0)
    return Machine("cpu-measured", peak, bw, 0.0)


def hlo_cost(fn, *args) -> dict[str, float]:
    """{'flops', 'bytes'} of ``jit(fn)`` on args' shapes, from XLA's
    cost_analysis of the compiled module (no execution)."""
    import jax

    costs = jax.jit(fn).lower(*args).compile().cost_analysis()
    return {
        "flops": float(costs.get("flops", 0.0)),
        "bytes": float(costs.get("bytes accessed", 0.0)),
    }

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# result shapes like  bf16[16,4096,384]{2,1,0}
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes per collective kind over an HLO module."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_type, kind = m.group(1), m.group(2)
        total = sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(result_type))
        out[kind] += total
    return out


@dataclasses.dataclass
class Roofline:
    flops: float  # per-chip HLO flops
    hbm_bytes: float  # per-chip HLO bytes accessed
    coll_bytes: float  # per-chip collective payload bytes
    coll_breakdown: dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float  # 6*N*D useful flops (per chip)
    useful_fraction: float  # model_flops / flops
    peak_mem_bytes: float  # per-device temp+output from memory_analysis

    def as_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d.pop("coll_breakdown")
        return d


def analyze(
    compiled, *, chips: int, model_flops_global: float, machine: Machine,
) -> Roofline:
    """Build the three-term roofline from a compiled executable against
    ``machine``'s peaks (:func:`machine_for` on the device's kind)."""
    costs = compiled.cost_analysis()
    flops = float(costs.get("flops", 0.0))
    hbm = float(costs.get("bytes accessed", 0.0))
    colls = collective_bytes(compiled.as_text())
    coll_total = float(sum(colls.values()))

    compute_s = flops / machine.peak_flops
    memory_s = hbm / machine.hbm_bw
    collective_s = coll_total / machine.ici_bw if machine.ici_bw else 0.0
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)

    try:
        ma = compiled.memory_analysis()
        peak = float(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
        )
    except Exception:
        peak = float("nan")

    mf = model_flops_global / chips
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        coll_bytes=coll_total,
        coll_breakdown=colls,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=mf,
        useful_fraction=(mf / flops) if flops else float("nan"),
        peak_mem_bytes=peak,
    )


# ---------------------------------------------------------------------------
# star-topology comm term (multi-node FedNL over repro.comm, DESIGN.md §6)
# ---------------------------------------------------------------------------

def star_comm_s(
    uplink_bits_per_round: float,
    bcast_bits_per_round: float,
    n_clients: int,
    cost=None,
) -> float:
    """Seconds of wire time for one FedNL star round.

    The mesh rooflines above model ICI collectives; the TCP star of
    ``repro.comm`` instead pays hub-and-spoke transfer governed by a
    bandwidth/latency :class:`repro.comm.cost.CommCostModel`.  Feed this the
    *measured* per-round bits from a ``StarRunResult`` (or the analytic
    ``message_bits`` model — they are equal by construction) to rank
    compressors by comm-bound round time.
    """
    if cost is None:
        from repro.comm.cost import DEFAULT_COST as cost
    return cost.round_s(uplink_bits_per_round, bcast_bits_per_round, n_clients)


def star_roofline(
    compute_s: float,
    uplink_bits_per_round: float,
    bcast_bits_per_round: float,
    n_clients: int,
    cost=None,
) -> dict[str, Any]:
    """Two-term (compute vs wire) round model for the multi-node star."""
    comm_s = star_comm_s(uplink_bits_per_round, bcast_bits_per_round, n_clients, cost)
    return {
        "compute_s": compute_s,
        "comm_s": comm_s,
        "round_s": max(compute_s, comm_s),
        "dominant": "comm" if comm_s > compute_s else "compute",
    }


# ---------------------------------------------------------------------------
# MODEL_FLOPS = 6 * N * D  (N = active params, D = tokens)
# ---------------------------------------------------------------------------

def count_params(abstract_params) -> int:
    import jax

    return sum(math.prod(x.shape) for x in jax.tree.leaves(abstract_params))


def active_params(cfg, abstract_params) -> float:
    """MoE: experts count at top_k/n_experts; everything else fully."""
    import jax

    total = 0.0
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    for path, leaf in flat:
        n = math.prod(leaf.shape)
        keys = "/".join(str(p) for p in path)
        if cfg.moe is not None and "moe" in keys and "router" not in keys:
            n = n * cfg.moe.top_k / cfg.moe.n_experts
        total += n
    return total


def model_flops_global(cfg, abstract_params, *, tokens: int, kind: str) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (fwd only)."""
    n_act = active_params(cfg, abstract_params)
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_act * tokens
