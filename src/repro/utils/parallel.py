"""Concurrent first calls of jitted functions.

A jitted function compiles on its first call.  Tracing holds the GIL but
XLA compiles outside it, so the first calls of N fresh executables made
from N threads take about one compile's wall time on an N-core host
instead of N — what a cold plan cache or a cold build pays on a TPU,
whose sort lowering alone takes seconds per executable.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor


def run_concurrently(calls) -> list:
    """Run zero-argument callables from a thread pool; -> results in order.

    The first exception raised by any call propagates once all have
    finished.
    """
    calls = list(calls)
    if len(calls) <= 1:
        return [c() for c in calls]
    workers = min(len(calls), os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=workers) as pool:
        futures = [pool.submit(c) for c in calls]
    return [f.result() for f in futures]
