"""answers_ms_per_query: time spent moving the answers to the client:
the device-to-host copy of the rows (``query.fetch``) plus building the
Python answer set (``serving.answers``), summed over the requests
answered inside the window, over them."""
from chipbench import program_spans


def read(run):
    return program_spans.per_query_ms(run, ("query.fetch",
                                            "serving.answers"))
