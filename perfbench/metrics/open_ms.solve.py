"""open_ms.solve: mean wall milliseconds of one ``open_session`` call in the
window (problem placement, exact-Hessian init, trace, lower, cache lookup
and the warm-up round), from the benchmark's own span around each call."""


def read(ctx):
    opens = ctx.spans.seconds("open")
    if not opens:
        return None
    return 1e3 * sum(opens) / len(opens)
