"""Host time of the serving loop per window: the harness's span around each
traced window's ``offer`` + ``serve``, less the device busy time inside
it, averaged over the traced windows."""


def read(ctx):
    spans = ctx.trace.span_s("bench.window")
    if not spans:
        return None
    host = [(e - s) * 1e-9 - ctx.trace.busy_within(s, e) for s, e in spans]
    return 1e3 * sum(host) / len(host)
