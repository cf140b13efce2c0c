"""Read path: memory-component tables searched per Get submit (the
program's ``mem.tables_searched`` counter, one per table and batch)."""
from chipbench.program import window


def read(ctx):
    w = window(ctx)
    return None if w is None else w.per_submit("mem.tables_searched",
                                               "gets")
