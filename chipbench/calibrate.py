"""Readings that set a cell's limits: many seeds in one process.

    python3 chipbench/calibrate.py --workload lubm30-litemat.rounds \
        --seeds 2147483701 2147483702 ... --seconds 12 [--probe Q2]

Builds the store once, then for each seed warms up, measures one window
at the cell's own load and compares every answer with the reference
(the lower reading: the program's), and compares the reference's
control, which leaves out domain and range entailment, in the program's
place over the same queries (the upper reading).  ``--probe`` sends each
named template once through the runtime and reports its outcome beside
the reference's answer size.  One JSON line per reading on stdout.
"""
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL = ("subclass", "subprop")


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    from chipbench import harness, traffic

    ap = argparse.ArgumentParser(prog="chipbench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--probe", nargs="*", default=[])
    args = ap.parse_args(argv)

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    try:
        s = harness.open_session(cell)
    except harness.Refused as e:
        harness.log(f"refused: {e}")
        return 3
    try:
        for name in args.probe:
            t = cell.qset["templates"][name]
            pools = traffic.candidates(s.data, cell.qset, [name],
                                       s.onto["rdf_type"])
            for end in (0, -1):  # two of its candidates
                q = traffic.Query(name, tuple(
                    (p, int(pools[c][end]))
                    for p, c in sorted(t["params"].items())))
                fps = np.asarray([fp for _, fp in q.params], np.int64)
                client = harness.Client(
                    s.rt, cell.qset, cell.config["mode"],
                    harness.locate_ids(s.K, fps) if fps.size else {})
                t0 = time.perf_counter()
                o = client.submit(q).result()
                dt = time.perf_counter() - t0
                want = harness.reference_answers(s.reference(), cell.qset, [q])
                print(json.dumps({
                    "probe": name, "end": end, "status": o.status,
                    "error": o.error, "seconds": dt,
                    "rows": None if o.answers is None else len(o.answers),
                    "reference_rows": len(want[q.key()])}), flush=True)
                if not t["params"]:
                    break
        for seed in args.seeds:
            run = harness.measure(s, seed, args.seconds)
            decoded = harness.decode(s.K, run.requests, cell.qset)
            got = harness.check(s, run, decoded)
            ctl = harness.check(s, run, decoded, CONTROL)
            print(json.dumps({
                "seed": seed, "requests": len(run.requests),
                "executables_in_window": run.executables_in_window,
                "warmup_passes": run.warmup_passes,
                "program": {k: v["value"] for k, v in got.items()},
                "control": {k: v["value"] for k, v in ctl.items()}}),
                flush=True)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
