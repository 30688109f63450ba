"""Host time before each episode launches, per traced window: the
program's ``serve.prep`` span (control context, padding and placement of
the operands, executable lookup), as the program records it
(``repro.common.obs``), averaged over the traced windows."""
from chipbench import scopes

# None (a program from before its scopes and spans) leaves the metric out
# of the line, with a note on stderr; in a program that has them, finding
# nothing fails the run (chipbench/scopes.py)
SILENT_WHEN = "the program predates its host spans"


def read(ctx):
    return scopes.read_span_ms(ctx, "serve.prep")
