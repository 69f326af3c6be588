"""A new configuration, traffic mix or metric is found by its name in
``BENCHMARK.json``: adding one is adding its file and its entry, with
no edit to a file the benchmark has."""

import json
import shutil

from bench.tests.conftest import FAKE_PEAKS, ROOT, tiny_config

NEW_METRIC = '''
def read(ctx):
    return float(len(ctx.window_served()))
'''

NEW_END_TO_END = '''
def read(run):
    return float(len(run.record.served))
'''


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    cfg = json.loads((ROOT / "bench/configs/tuner_narrow.json").read_text())
    cfg.update(tiny_config(12_000))
    (tmp_path / "bench/configs/tuner_tiny.json").write_text(json.dumps(cfg))
    tr = json.loads(
        (ROOT / "bench/traffic/shift_read_heavy.json").read_text())
    tr["loop"] = {"kind": "closed", "clients": 4}
    tr["mix"]["update_frac"] = 0.0
    tr["index_keys"] = []
    (tmp_path / "bench/traffic/tiny_scans.json").write_text(json.dumps(tr))
    (tmp_path / "bench/metrics/statements_in_window.py").write_text(
        NEW_METRIC)
    (tmp_path / "bench/end_to_end/statements_served.py").write_text(
        NEW_END_TO_END)

    bench["configs"].append({"name": "tuner_tiny", "source": "test",
                             "file": "bench/configs/tuner_tiny.json",
                             "reduced": ["n_rows"], "why": "test"})
    cell = "tuner_tiny.tiny_scans"
    bench["workloads"].append({"name": cell, "config": "tuner_tiny",
                               "traffic": "tiny_scans", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({
        "name": "statements_served", "unit": "stmts", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append({
        "name": "statements_in_window", "unit": "stmts",
        "better": "higher", "source": "program_counter", "layer": "test",
        "moves": "statements_served", "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    from bench.run import run_cell

    r0 = run_cell(cell, 99, 1.0, False, require_tpu=False,
                  peaks_override=FAKE_PEAKS, root=tmp_path)
    assert r0["correct"]
    assert set(r0["metrics"]) == {"statements_served", "setup_s"}
    r1 = run_cell(cell, 99, 1.0, True, require_tpu=False,
                  peaks_override=FAKE_PEAKS, root=tmp_path)
    assert r1["correct"]
    assert set(r1["metrics"]) == {"statements_in_window"}
    assert r1["metrics"]["statements_in_window"]["value"] > 0
