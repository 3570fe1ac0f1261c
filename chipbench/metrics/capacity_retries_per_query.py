"""capacity_retries_per_query: capacity doublings of the answered
requests (the ``overflow_retry`` events the query engine records on
``query.dispatch`` beside each ``join/capacity_retry`` increment), over
the requests answered inside the window.  None in an untraced run."""
from chipbench import program_spans


def read(run):
    trees = program_spans.answered(run)
    if not trees:
        return None
    retries = sum(e["name"] == "overflow_retry"
                  for spans in trees for s in spans for e in s["events"])
    return retries / len(trees)
