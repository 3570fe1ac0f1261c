"""The one traffic generator: every mix is a data file it reads.

A mix (``chipbench/traffic/<name>.json``) fixes the number of closed-loop
clients and the templates of a query set (``chipbench/queries/<set>.json``)
that each client sends in rounds: every round holds each template once,
in the mix's own order, so every seed sends the same work in the same
order.  The seed draws only the constants: a template's parameter
(``$name``) names a class, and its value is drawn uniformly from the
entities the data types with that class.
"""
from __future__ import annotations

import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from chipbench.lubm import Triples, fingerprint

# each client's first queries that the warm-up runs: three times what a
# 44 s window sends today
WARMUP_QUERIES = 96


@dataclass(frozen=True)
class Query:
    template: str
    params: tuple  # ((name, fingerprint), ...)

    def key(self) -> tuple:
        return (self.template, self.params)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def candidates(triples: Triples, qset: dict, templates,
               type_name: str = "rdf:type") -> dict:
    """class -> sorted fingerprints of the entities typed with it, for
    every class a template of the mix takes as a parameter."""
    classes = sorted({c for t in templates
                      for c in qset["templates"][t]["params"].values()})
    is_type = triples.p == fingerprint(type_name)
    out = {}
    for cls in classes:
        out[cls] = np.unique(triples.s[is_type & (triples.o == fingerprint(cls))])
        if out[cls].size == 0:
            raise ValueError(f"no entity of class {cls} in the data")
    return out


def _bind(qset: dict, template: str, rng, pools: dict) -> Query:
    params = qset["templates"][template]["params"]
    return Query(template, tuple(
        (p, int(pools[cls][rng.integers(pools[cls].size)]))
        for p, cls in sorted(params.items())))


def client_stream(mix: dict, qset: dict, pools: dict, seed: int, client: int):
    """Endless stream of one client's queries: rounds of the mix's
    templates in its order, constants drawn from ``seed``."""
    rng = rng_for(seed, 1, client)
    while True:
        for t in mix["templates"]:
            yield _bind(qset, t, rng, pools)


def warmup_queries(mix: dict, qset: dict, pools: dict, seed: int) -> list:
    """The distinct queries among each client's first ``WARMUP_QUERIES``:
    what the window sends first, constants included, in the order first
    sent.  The warm-up replays them, so nothing compiles in the window."""
    seen = {}
    for c in range(int(mix["clients"])):
        stream = client_stream(mix, qset, pools, seed, c)
        for q in itertools.islice(stream, WARMUP_QUERIES):
            seen.setdefault(q.key(), q)
    return list(seen.values())
