"""Device time of the served episode program (``fleet_episode``'s
executable) per slot served in the traced windows."""

# the episode executable is the jitted ``counted`` wrapper of
# ``fleet._get_episode_executable``; no other program of that name runs
# while the fleet is served
EPISODE_MODULE = r"^jit_counted\("


def read(ctx):
    s = ctx.trace.module_s(EPISODE_MODULE)
    if s is None or ctx.slots <= 0:
        return None
    return 1e3 * s / ctx.slots
