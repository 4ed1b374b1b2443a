"""round_ms.solve: wall milliseconds per FedNL round inside ``Session.run``:
the benchmark's spans around every run call, summed, over all rounds run
(each round includes its host sync of the round's record)."""


def read(ctx):
    runs = ctx.spans.seconds("run")
    rounds = ctx.counters.get("rounds", 0)
    if not runs or rounds <= 0:
        return None
    return 1e3 * sum(runs) / rounds
