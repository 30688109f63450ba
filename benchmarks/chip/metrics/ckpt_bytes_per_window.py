"""Bytes each checkpoint writes (data, manifest and commit marker), over
every checkpoint committed in the run so far: the program's
``ckpt.write_counts()``.  A checkpoint is taken every window."""

from chipbench import scopes

# None (a program from before its counter) leaves the metric out of the line,
# with a note on stderr; in a program that has it, no generation written in
# a checkpointing cell fails the run
SILENT_WHEN = "the program predates its checkpoint write counter"


def read(ctx):
    if not scopes.program_names_layers():
        return None
    from repro.ckpt.checkpoint import write_counts

    n = write_counts()
    if n["generations"] <= 0:
        raise scopes.Unread("the run committed no checkpoint generation")
    return n["bytes"] / n["generations"]
