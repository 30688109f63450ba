"""Seconds JAX spent tracing Python to jaxprs during set-up (its
``jaxpr_trace_duration`` events).  The persistent compile cache does not
cover tracing, so this is paid on every start."""


def read(ctx):
    return ctx.compile.get("jaxpr_trace_duration")
