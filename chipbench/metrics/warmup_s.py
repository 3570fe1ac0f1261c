"""warmup_s: seconds of the warm-up passes (host clock)."""


def read(run):
    return run.warmup_s
