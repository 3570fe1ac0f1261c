"""request_gap_ms_max: the longest stretch of any answered request's
``serving.request`` span (submit to answer) that none of its other spans
covers: host time outside every layer the program names."""
from chipbench import program_spans


def read(run):
    trees = program_spans.answered(run)
    if not trees:
        return None
    return max(program_spans.longest_uncovered(t) for t in trees) * 1e3
