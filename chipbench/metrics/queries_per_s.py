"""queries_per_s: queries answered inside the window, over the window."""


def read(run):
    return len(run.completed) / run.seconds
