"""build_s: seconds of ``KnowledgeBase.build`` until its stores are on the
device (host clock)."""


def read(run):
    return run.build_s
