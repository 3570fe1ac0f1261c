"""The harness finds configurations, mixes and metrics by name, and its
contract: a result line with exactly the contract's keys, and no result
without a chip."""
import json

from chipbench import harness


def test_added_files_are_found_by_name(cpu_root, capsys, monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    # a new configuration, traffic mix and metric: files plus entries
    conf = json.loads((cpu_root / "chipbench/configs/tiny.json").read_text())
    conf.update(name="tiny2", universities=2)
    (cpu_root / "chipbench/configs/tiny2.json").write_text(json.dumps(conf))
    mix = json.loads((cpu_root / "chipbench/traffic/rounds.json").read_text())
    mix.update(templates=["Q4", "Q5", "Q12"])
    (cpu_root / "chipbench/traffic/short.json").write_text(json.dumps(mix))
    (cpu_root / "chipbench/metrics/answered_share.py").write_text(
        "def read(run):\n"
        "    return 100.0 * len(run.completed) / max(len(run.requests), 1)\n")
    bench = json.loads((cpu_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny2", "source": "test", "reduced": [],
                             "file": "chipbench/configs/tiny2.json",
                             "why": "test"})
    bench["workloads"].append({"name": "tiny2.short", "config": "tiny2",
                               "traffic": "short", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "answered_share", "unit": "%",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["tiny2.short"]})
    (cpu_root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(cpu_root, "tiny2.short")
    assert cell.config["universities"] == 2
    assert cell.mix["templates"] == ["Q4", "Q5", "Q12"]
    rc = harness.main(["--workload", "tiny2.short", "--seed", "4294967311",
                       "--seconds", "2", "--trace", "0"],
                      root=cpu_root, require_tpu=False)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # exactly the contract's keys, the compared numbers last
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "queries_per_s",
                                    "query_p65_ms", "query_p50_ms",
                                    "bytes_per_triple", "answered_share"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_refuses_a_cpu_backend(cpu_root, capsys):
    rc = harness.main(["--workload", "tiny.rounds", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=cpu_root)
    assert rc == 3
    assert capsys.readouterr().out == ""


def test_refuses_an_unknown_workload(cpu_root, capsys):
    rc = harness.main(["--workload", "nope.rounds", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=cpu_root)
    assert rc == 3
    assert capsys.readouterr().out == ""


def test_peaks_table_names_the_v5e():
    peaks = json.loads((harness.CHECKOUT / "chipbench/peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops"] == 1.97e14
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 8.19e11
