"""round_mfu_pct.solve: the algorithm's FLOPs of all rounds run, over the
seconds spent in ``Session.run`` times the chip's published peak (bf16, the
only FLOP peak the chip publishes).  The FLOPs come from the shapes
(perfbench/lib/work.py), not from the program."""


def read(ctx):
    runs = ctx.spans.seconds("run")
    rounds = ctx.counters.get("rounds", 0)
    if not runs or rounds <= 0 or ctx.work_per_round is None:
        return None
    return 100.0 * ctx.work_per_round.flops * rounds / (sum(runs) * ctx.peaks.flops)
