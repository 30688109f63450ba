"""Device time of ROIDet's connected-component labelling per slot served in
the traced windows: the episode's operations under the scope ``cc``, which
the program opens inside ``roidet`` around ``core/cc.label_and_boxes``
(so ``roidet_us_per_slot`` counts it too).

The scope is read as ``chipbench/scopes.py`` reads the layer scopes, with
``cc`` as the one scope: an instruction in ``cc`` or in a loop, branch or
call that ``cc`` holds.  The layer split of the same episode goes to
stderr."""
from unittest import mock

from chipbench import scopes

SILENT_WHEN = "the program opens no cc scope"


def read(ctx):
    if not scopes.program_names_layers():
        return None
    with mock.patch.object(scopes, "SCOPES", ("cc",)):
        maps = [m for m in scopes.live_scope_maps()
                if any(s == "cc" for s, _ in m.values())]
        if not maps:
            return None
        secs = scopes.layer_seconds(ctx.trace, maps)
    scopes.read_scope(ctx, "roidet")      # prints the layer split
    if ctx.slots <= 0:
        raise scopes.Unread("no slot was served in the traced windows")
    return 1e6 * secs["cc"] / ctx.chips / ctx.slots
