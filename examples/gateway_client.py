"""End-to-end gateway demo: a fleet of prioritized remote submissions.

Starts a gateway in a subprocess (as a real deployment would run
``scripts/gateway_serve.py``), then from this process: submits experiments
across the three priority classes, watches one of them round-by-round over
a second connection, fetches every result, and verifies one trajectory
bit-for-bit against a local solo run — the DESIGN.md §14 contract.  Both
processes run JAX on the host CPU: an accelerator belongs to one process at
a time, and the bit-for-bit check needs both runs on the same platform.

    PYTHONPATH=src python examples/gateway_client.py
"""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX is imported; see docstring


def main() -> int:
    from repro.api import CompressorSpec, DataSpec, ExperimentSpec, solve
    from repro.gateway import GatewayClient, GatewayError, stream_records

    proc = subprocess.Popen(
        [sys.executable, "scripts/gateway_serve.py", "--port", "0",
         "--max-resident", "4"],
        stdout=subprocess.PIPE, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": "src"},
    )
    try:
        _, host, port = proc.stdout.readline().split()  # "LISTENING h p"
        print(f"gateway up on {host}:{port}")

        def spec_of(seed, comp, rounds):
            return ExperimentSpec(
                data=DataSpec(shape=(12, 4, 20), seed=1),
                compressor=CompressorSpec(comp, 8.0),
                rounds=rounds, seed=seed,
            )

        with GatewayClient(host, int(port), connect_retry_s=30) as gwc:
            # a bad submission fails HERE, naming the field — not ticks later
            try:
                gwc.submit(spec_of(0, "topk", 4), priority="platinum")
            except GatewayError as e:
                print(f"rejected synchronously ({e.field}): {e}")

            fleet = [
                ("high", spec_of(0, "topk", 12)),
                ("normal", spec_of(1, "randk", 10)),
                ("normal", spec_of(2, "randseqk", 10)),
                ("low", spec_of(3, "identity", 8)),
            ]
            handles = [(gwc.submit(s, priority=p), s) for p, s in fleet]

            # live-stream the low-priority tenant on its own connection
            watch = handles[-1][0]
            for rec in stream_records(host, int(port), watch.id):
                print(f"  [{watch.id} {watch.priority}] round {rec.round} "
                      f"||grad||={rec.grad_norm:.3e}")

            for h, spec in handles:
                report = h.result()
                print(f"{h.id} ({h.priority}): {report.rounds} rounds, "
                      f"final ||grad||={report.final_grad_norm:.3e}")

            # the §14 bar: remote result == local solve, bit for bit
            h0, spec0 = handles[0]
            local = solve(spec0)
            remote = h0.result()
            same = all(
                float(a.grad_norm).hex() == float(b.grad_norm).hex()
                for a, b in zip(remote.records, local.records)
            ) and (remote.x == local.x).all()
            print(f"bit-identical to local solve: {same}")
            stats = gwc.status()
            print(f"engine stats: ticks={stats['ticks']} "
                  f"admissions_by_class={stats['admissions_by_class']}")
            return 0 if same else 1
    finally:
        proc.kill()
        proc.wait(10)


if __name__ == "__main__":
    sys.exit(main())
