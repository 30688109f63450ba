"""Device time of the detectors' stem per slot served in the traced windows:
the episode's operations under the scope ``stem``, which the program opens
in ``models/detector.forward`` around the convs that open both detectors
(their input fold included), so inside ``roidet`` for the light detector
and ``detect`` for the server detector, and those metrics count it too.

The scope is read as ``chipbench/scopes.py`` reads the layer scopes, with
``stem`` as the one scope: an instruction in ``stem`` or in a loop, branch
or call that ``stem`` holds.  The layer split of the same episode goes to
stderr."""
from unittest import mock

from chipbench import scopes

SILENT_WHEN = "the program opens no stem scope"


def read(ctx):
    if not scopes.program_names_layers():
        return None
    with mock.patch.object(scopes, "SCOPES", ("stem",)):
        maps = [m for m in scopes.live_scope_maps()
                if any(s == "stem" for s, _ in m.values())]
        if not maps:
            return None
        secs = scopes.layer_seconds(ctx.trace, maps)
    scopes.read_scope(ctx, "detect")      # prints the layer split
    if ctx.slots <= 0:
        raise scopes.Unread("no slot was served in the traced windows")
    return 1e6 * secs["stem"] / ctx.chips / ctx.slots
