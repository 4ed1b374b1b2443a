"""Drive whole runs of the solve cells on the CPU at a small size.

The harness's look for a chip is skipped; the rest of a run (set-up,
window, the reference comparison under the cell's own limits) runs as on
the chip.  A sound run is correct; a run with the timed path broken
underneath is not, once for each fault a solve cell can have (one chip:
there is no exchange between chips to leave out).
"""

import json
import os
import time

import jax.numpy as jnp
import pytest

from perfbench import run
from perfbench.lib import peaks

SMALL = {"d": 40, "n_clients": 8, "n_i": 60}
SEED = 2**31 + 977


def _cell(workload, **shape):
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cell = run.resolve(bench, workload)
    cell.config = dict(cell.config, **(shape or SMALL))
    return cell


def _run(cell, trace=False, seconds=1.0):
    return run.run_cell(cell, SEED, seconds, trace, t_start=time.perf_counter(),
                        require_chip=False, peaks=peaks.TPU_V5E)


@pytest.mark.parametrize("workload", ["w8a-topk-solve", "w8a-toplek-solve"])
def test_sound_run_is_correct(workload):
    res = _run(_cell(workload))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert list(res)[-1] == "checks"


def test_lax_mapped_clients_are_correct():
    # d > 128 takes the program's lax.map client path, as w8a does
    res = _run(_cell("w8a-topk-solve", d=130, n_clients=3, n_i=150))
    assert res["correct"], res["checks"]


def test_traced_run_reports_per_layer_metrics():
    res = _run(_cell("w8a-toplek-solve"), trace=True)
    assert res["correct"], res["checks"]
    # the CPU trace holds no device plane: the idle reader finds nothing
    assert set(res["metrics"]) == {"open_ms.solve", "round_ms.solve", "round_mfu_pct.solve"}
    assert 0.0 < res["metrics"]["round_mfu_pct.solve"]["value"] < 100.0


def _broken(kind):
    from repro.api import backends

    orig = backends.make_fednl_round

    def make(z, cfg):
        if kind == "half_batch":  # the mean over the first half only
            half = z.shape[0] // 2
            z = jnp.concatenate([z[:half], z[:half]], axis=0)
        step = orig(z, cfg)

        def broken(state):
            new, m = step(state)
            if kind == "state_unchanged":
                return state, m
            if kind == "answer_altered":
                new = new._replace(x=new.x.at[0].multiply(1.0 + 1e-7))
            return new, m

        return broken

    return make


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", ["w8a-topk-solve", "w8a-toplek-solve"])
def test_broken_timed_path_is_not_correct(monkeypatch, kind, workload):
    from repro.api import backends

    monkeypatch.setattr(backends, "make_fednl_round", _broken(kind))
    res = _run(_cell(workload))
    assert res["correct"] is False
    failing = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert failing or res["failed"] > 0
