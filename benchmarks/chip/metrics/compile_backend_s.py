"""Seconds of backend compilation plus persistent-cache reads during set-up
(JAX's ``backend_compile_duration`` and ``cache_retrieval_time_sec``
events)."""


def read(ctx):
    parts = [ctx.compile.get(k) for k in ("backend_compile_duration",
                                          "cache_retrieval_time_sec")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
