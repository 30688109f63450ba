"""Time the serving loop spends checkpointing per traced window: the
program's ``serve.checkpoint`` span on the serving thread (building the
metadata, joining the previous window's writer, copying the carry to the
host), as the program records it (``repro.common.obs``), averaged over the
traced windows.  The write itself runs on the writer thread and is not in
it."""
from chipbench import scopes

# None (a program from before its scopes and spans) leaves the metric out
# of the line, with a note on stderr; in a program that has them, finding
# nothing fails the run (chipbench/scopes.py)
SILENT_WHEN = "the program predates its host spans"


def read(ctx):
    return scopes.read_span_ms(ctx, "serve.checkpoint")
