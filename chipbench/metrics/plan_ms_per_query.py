"""plan_ms_per_query: time in ``query.plan`` (host planning, the
``query.plan.count`` device round trips inside it included), summed over
the requests answered inside the window, over them."""
from chipbench import program_spans


def read(run):
    return program_spans.per_query_ms(run, ("query.plan",))
