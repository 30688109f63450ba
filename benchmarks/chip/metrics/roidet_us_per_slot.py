"""Device time of the episode's operations under the ``roidet`` scope per slot
served in the traced windows: ROIDet (``core/roidet._roidet_fleet_impl``):
the light detector, the edge-motion kernel and connected-component
labelling.

How an operation is given its scope: ``chipbench/scopes.py``."""
from chipbench import scopes

# None (a program from before its scopes and spans) leaves the metric out
# of the line, with a note on stderr; in a program that has them, finding
# nothing fails the run (chipbench/scopes.py)
SILENT_WHEN = "the program predates its device scopes"


def read(ctx):
    return scopes.read_scope(ctx, "roidet")
