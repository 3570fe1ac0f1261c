"""Compile the served path for a TPU v5e without one.

The TPU compiler ships with libtpu and compiles for a described chip that
is not attached, so these tests run the Mosaic lowering that CPU interpret
mode never reaches: every compaction kernel at LUBM-100 widths (2**24
rows), the XLA pair search and device merge at store widths, and one
paper-query plan executable from its shapes.  Nothing executes; a compile
error here is the error the chip would raise.

The topology is described inside a fixture, never at import time: only one
process at a time may load libtpu, and pytest-xdist workers all import
this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import stream_compact as sc

ROWS = 1 << 24  # ~LUBM-100 materialized store rows
PAIR_TABLE = 1 << 20  # the old whole-table VMEM residency ceiling


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["stream", "interval", "masked_interval",
                                    "dual"])
@pytest.mark.parametrize("block", [sc.MIN_BLOCK, sc.DEFAULT_BLOCK])
def test_compaction_kernel_compiles_for_v5e(kernel, block, one_chip):
    col = _spec((ROWS,), one_chip)
    params = _spec((4,), one_chip)
    fns = {
        "stream": (lambda m: sc.stream_compact_pallas(m, block=block), (col,)),
        "interval": (lambda p, o, q: sc.interval_compact_pallas(
            p, o, q, block=block), (col, col, params)),
        "masked_interval": (lambda p, o, a, q: sc.masked_interval_compact_pallas(
            p, o, a, q, block=block), (col, col, col, params)),
        "dual": (lambda a, b: sc.dual_compact_pallas(a, b, block=block),
                 (col, col)),
    }
    fn, args = fns[kernel]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not interpreted


@pytest.mark.parametrize("table", [PAIR_TABLE, ROWS])
def test_pair_search_compiles_for_v5e(table, one_chip):
    t = _spec((table,), one_chip)
    q = _spec((1 << 16,), one_chip)
    _compile(ops.pair_search, t, t, q, q)


def test_merge_gather_compiles_for_v5e(one_chip):
    a = _spec((ROWS,), one_chip)
    b = _spec((1 << 17,), one_chip)
    compiled = _compile(ops.merge_gather, a, a, b, b)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("mode,query", [("litemat", "Q4"), ("rewrite", "Q1")])
def test_paper_query_plan_compiles_for_v5e(mode, query, one_chip,
                                           monkeypatch):
    """Plan a paper query on a small store, then compile its executable
    with every store array widened to ``ROWS`` rows on the described chip.

    Off the TPU the op wrappers pick interpret mode, so the test forces
    the compiled kernels in; the plan's jit caches are cleared so no
    interpret-mode trace is reused.
    """
    from repro.core.engine import PAPER_QUERIES, KnowledgeBase
    from repro.core.query import QueryEngine
    from repro.rdf.generator import generate_random_abox
    from repro.rdf.vocab import lubm_ontology

    raw = generate_random_abox(lubm_ontology(), n_instances=400,
                               n_type_triples=800, n_prop_triples=800,
                               seed=3)
    K = KnowledgeBase.build(raw)
    eng = K.engine(mode)
    sigs, dyns, caps, join_cap, sel, stores = eng._plan(
        PAPER_QUERIES[query], None)[:6]

    def widen(x):
        shape = x.shape
        if x.ndim and shape[0] == eng.view.n:
            shape = (ROWS,) + shape[1:]
        return _spec(shape, one_chip, x.dtype)

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    for f in (ops.compact_indices, ops.dual_compact_indices,
              ops.masked_interval_compact, ops.pair_search):
        f.clear_cache()
    run = QueryEngine._make_run_device(sigs, tuple(caps), join_cap, sel)
    compiled = _compile(run, jax.tree.map(widen, stores),
                        jax.tree.map(widen, dyns))
    assert "tpu_custom_call" in compiled.as_text()
    for f in (ops.compact_indices, ops.dual_compact_indices,
              ops.masked_interval_compact, ops.pair_search):
        f.clear_cache()
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
