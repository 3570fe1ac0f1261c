"""Scratch benchmark roots for the CPU tests: the checkout's data files
plus small cells, built in a temporary directory."""
import json
import shutil
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DATA_DIRS = ("configs", "traffic", "queries", "ontologies", "metrics")


def scratch_root(tmp: Path, universities: int = 3) -> Path:
    """A benchmark root in ``tmp``: the checkout's data files, plus a
    configuration ``tiny`` (LUBM-``universities``, litemat) with the cell
    ``tiny.rounds``.  Nothing is added to the checkout."""
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for d in DATA_DIRS:
        shutil.copytree(CHECKOUT / "chipbench" / d, tmp / "chipbench" / d)
    conf = json.loads((CHECKOUT / bench["configs"][0]["file"]).read_text())
    conf.update(name="tiny", universities=universities)
    (tmp / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    bench["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                             "file": "chipbench/configs/tiny.json",
                             "why": "a store a CPU test can build"})
    add_cell(bench, "tiny", "rounds")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def add_cell(bench: dict, config: str, mix: str) -> str:
    name = f"{config}.{mix}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return name
