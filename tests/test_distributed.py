"""Multi-device semantics via subprocesses (this process keeps 1 device;
XLA locks the device count at first jax init, so each test spawns a child
with XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8):
    """Run ``code`` in a child on ``devices`` forced CPU devices; the child
    never claims an accelerator, so it cannot contend with its parent."""
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560,
        env={
            "PYTHONPATH": str(REPO / "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
            "PATH": "/usr/bin:/bin",
            "HOME": os.environ.get("HOME", str(REPO)),
        },
        cwd=REPO,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_dictionary_matches_local():
    out = _run(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import dictionary as dct
from repro.utils import pair64

rng = np.random.default_rng(0)
n_shards, per = 8, 64
fps = rng.choice(1 << 50, n_shards * per // 2, replace=False)
occ = rng.choice(fps, n_shards * per)  # duplicated occurrences
hi, lo = pair64.split_np(occ)
mesh = jax.make_mesh((n_shards,), ('d',), axis_types=(AxisType.Auto,))
body = dct.sharded_dictionary_fn('d', n_shards, bin_cap=per, base=1000)
f = jax.shard_map(body, mesh=mesh, in_specs=(P('d'), P('d'), P('d')),
                  out_specs=dct.sharded_out_specs(), check_vma=False)
ids, table, overflow, counts = f(jnp.asarray(hi), jnp.asarray(lo),
                                 jnp.ones(occ.shape, bool))
ids = np.asarray(ids)
assert int(np.asarray(overflow).sum()) == 0
# bijectivity: same fp -> same id; distinct fps -> distinct ids
m = {}
for f_, i_ in zip(occ.tolist(), ids.tolist()):
    assert i_ >= 1000
    assert m.setdefault(f_, i_) == i_
assert len(set(m.values())) == len(m)
# density: ids cover [1000, 1000 + n_distinct)
vals = sorted(m.values())
assert vals[0] == 1000 and vals[-1] == 1000 + len(m) - 1
print('sharded dictionary OK', len(m))
"""
    )
    assert "sharded dictionary OK" in out


def test_sharded_kb_shard_map_path_subprocess():
    """ShardedKB's shard_map execution (one device per shard) must equal the
    per-shard dispatch loop AND the single-device KnowledgeBase bit-exactly;
    the serving fan-out merges the same counts."""
    out = _run(
        """
import numpy as np, jax
from repro.core.engine import KnowledgeBase
from repro.core.query import Pattern
from repro.core.shard import ShardedKB
from repro.rdf.generator import generate_random_abox
from repro.rdf.vocab import lubm_ontology
from repro.serving.engine import QueryServer, ShardedQueryServer

assert jax.device_count() == 8
onto = lubm_ontology()
raw = generate_random_abox(onto, n_instances=800, n_type_triples=1500,
                           n_prop_triples=1500, seed=3)
K = KnowledgeBase.build(raw)
S = ShardedKB.build(raw, n_shards=8)
eng = S.engine('litemat')
assert eng._shard_map_on()
q1 = [Pattern('?x', 'rdf:type', 'Professor')]
want1, _ = K.query(q1, select=('?x',), mode='litemat')
got1, _ = eng.run(q1, select=('?x',))
assert np.array_equal(want1, got1)
# single-pattern plans have uniform per-shard signatures: must lower
# through the shard_mapped executable, never the dispatch loop
assert eng.cache_stats['shard_map_runs'] > 0, eng.cache_stats
q = [Pattern('?x', 'rdf:type', 'Professor'), Pattern('?x', 'worksFor', '?y')]
sel = ('?x', '?y')
want, _ = K.query(q, select=sel, mode='litemat')
got, _ = eng.run(q, select=sel)
assert np.array_equal(want, got)
eng.use_shard_map = False
loop, _ = eng.run(q, select=sel)
assert np.array_equal(want, loop)
c1, m1 = QueryServer(K, topk=8).class_members(['Professor', 'Student'])
qss = ShardedQueryServer(S, topk=8)
assert qss._sm()
c2, m2 = qss.class_members(['Professor', 'Student'])
assert np.array_equal(c1, c2) and np.array_equal(m1, m2)
print('sharded shard_map OK', c1.tolist())
"""
    )
    assert "sharded shard_map OK" in out


def test_mini_dryrun_lm_cell():
    """A 2x2x2 'multi-pod' mesh compiles an LM train cell end-to-end and the
    HLO analyzer finds loop-multiplied collectives."""
    out = _run(
        """
import jax
from repro.launch.cells import build_cell
from repro.launch.hlo_analysis import analyze_hlo
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(AxisType.Auto,) * 3)
cell = build_cell('olmoe-1b-7b', 'train_4k', mesh)
jfn = jax.jit(cell.fn, in_shardings=cell.shardings(mesh))
compiled = jfn.lower(*cell.abstract_args).compile()
a = analyze_hlo(compiled.as_text())
assert a['flops'] > 0 and a['collectives'].get('total', 0) > 0
assert a['collectives'].get('all-to-all', 0) >= 0  # MoE dispatch present
print('mini dryrun OK flops=%.2e coll=%.2e' % (a['flops'], a['collectives']['total']))
""",
    )
    assert "mini dryrun OK" in out


def test_repartition_join_shard_map_subprocess():
    """Cross-group (object-keyed) joins fold through the device-side
    hash-repartition join under shard_map — bit-identical to the host fold
    and the single-device engine, with ZERO host re-uploads (the
    `device/transfer_bytes{src=combine_upload}` meter stays flat)."""
    out = _run(
        """
import numpy as np, jax
from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.core.shard import ShardedKB
from repro.obs.metrics import REGISTRY
from repro.rdf.generator import generate_lubm

assert jax.device_count() == 8
raw = generate_lubm(1, seed=7)
K = KnowledgeBase.build(raw)
S = ShardedKB.build(raw, n_shards=8)
eng = S.engine('litemat')
assert eng._shard_map_on() and eng._repartition_on()
want3, _ = K.query(PAPER_QUERIES['Q3'], mode='litemat')
got3, _ = eng.run(PAPER_QUERIES['Q3'])
assert np.array_equal(np.asarray(got3), want3)
# Q4 is the multi-group (object-keyed) plan: its combine must stay on
# device — Q3's single-group run above may legitimately meter an upload
# through the host combine, so the pin brackets Q4 alone
c = REGISTRY.counter('device/transfer_bytes', src='combine_upload')
before = c.value
want, _ = K.query(PAPER_QUERIES['Q4'], mode='litemat')
got, _ = eng.run(PAPER_QUERIES['Q4'])
assert np.array_equal(np.asarray(got), want)
assert eng.cache_stats['repartition_runs'] >= 1, eng.cache_stats
assert c.value == before, 'device combine leaked a host re-upload'
eng.use_repartition_join = False
host, _ = eng.run(PAPER_QUERIES['Q4'])
want4, _ = K.query(PAPER_QUERIES['Q4'], mode='litemat')
assert np.array_equal(np.asarray(host), want4)
assert c.value > before  # the host fold pays the upload the device path skips
print('repartition shard_map OK', eng.cache_stats['repartition_runs'])
"""
    )
    assert "repartition shard_map OK" in out


def test_sharded_encode_ingest_subprocess():
    """`ShardedKB.ingest` encodes through the all-to-all sharded dictionary
    when a device per shard exists; answers match a host-encode control in
    fingerprint space (the two encodes rank instance ids differently)."""
    out = _run(
        """
import numpy as np, jax
import jax.numpy as jnp
from repro.core.engine import PAPER_QUERIES
from repro.core.shard import ShardedKB
from repro.core.tbox import build_tbox
from repro.rdf.generator import generate_lubm
from repro.utils import pair64

assert jax.device_count() == 8
raw = generate_lubm(1, seed=11)
n = raw.s.shape[0]; half = n // 2
parts = [(raw.s[:half], raw.p[:half], raw.o[:half]),
         (raw.s[half:], raw.p[half:], raw.o[half:])]
S = ShardedKB.ingest(iter(parts), onto=raw.onto, n_shards=8)
assert S.use_sharded_encode and S._sharded_encode_on()
ctrl = ShardedKB.empty(build_tbox(raw.onto), n_shards=8)
for p in parts:
    ctrl.insert(p, auto_compact=False)

def answers_fp(kb, pats, mode):
    rows, _ = kb.query(pats, mode=mode)
    if rows.size == 0:
        return set()
    ids = jnp.asarray(np.asarray(rows).reshape(-1).astype(np.int32))
    hi, lo, hit = kb.kb.table.extract_fp(ids)
    fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))
    fps = np.where(np.asarray(hit), fps, np.asarray(rows).reshape(-1))
    return {tuple(r) for r in fps.reshape(rows.shape).tolist()}

for mode in ('litemat', 'rewrite'):
    for qn in ('Q1', 'Q4'):
        a = answers_fp(S, PAPER_QUERIES[qn], mode)
        b = answers_fp(ctrl, PAPER_QUERIES[qn], mode)
        assert a == b and len(a) > 0, (mode, qn)
print('sharded encode ingest OK')
"""
    )
    assert "sharded encode ingest OK" in out
