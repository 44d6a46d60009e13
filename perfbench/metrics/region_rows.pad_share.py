"""Share of the rows the region planner dispatched to its row solve that are
padding (copies of a real row that round the batch up to a power of two),
from the planner's own counters (``rows_solved``, ``rows_dispatched`` in
each plan's diagnostics). Nothing where the program does not count them."""


def read(ctx):
    dispatched = ctx.counters.get("rows_dispatched")
    if not dispatched:
        return None
    return 100.0 * (1.0 - ctx.counters["rows_solved"] / dispatched)
