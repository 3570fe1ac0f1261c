"""device_wait_ms_per_query: time the host blocked on each dispatch's
overflow flag (``query.device_wait``: the device finishing the plan),
summed over the requests answered inside the window, over them."""
from chipbench import program_spans


def read(run):
    return program_spans.per_query_ms(run, ("query.device_wait",))
