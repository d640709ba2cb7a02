"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and new entries of BENCHMARK.json, and edits no file
the benchmark already has."""
import hashlib
import json
import shutil
import subprocess
import sys
from pathlib import Path

import harness
import run as run_mod
from conftest import TINY, TINY_CHAT

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_files_and_entries(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digests(tmp_path)
    # the three new files
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "bench/traffic/tiny-chat.json").write_text(
        json.dumps(TINY_CHAT))
    (tmp_path / "bench/metrics/steps_per_s.py").write_text(
        '"""Scheduler steps per second of the window."""\n\n\n'
        "def read(run):\n"
        "    return len(run.window.steps) / run.window.span_s\n")
    # the new entries
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tiny-chat", "config": "tiny",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["tiny.tiny-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(tmp_path)
    assert all(after[p] == d for p, d in before.items())

    _, cell, spec, mix = harness.load_cell("tiny.tiny-chat", tmp_path)
    assert spec.name == "tiny" and mix["rate_per_s"] == TINY_CHAT["rate_per_s"]
    from weights import program_params
    eng = harness.build_engine(spec, program_params(spec, 5))
    rec = harness.Recorder(eng)
    harness.warm(eng, spec)
    w = harness.drive(eng, rec, spec, mix, 5, 2.0, harness.CompileClock())
    view = harness.RunView(spec=spec, cell=cell, mix=mix, window=w, rec=rec,
                           setup_s=1.0, trace=None,
                           peak={"bf16_flops_per_s": 1e12,
                                 "hbm_bytes_per_s": 1e11})
    names = [m["name"] for m in run_mod.metrics_for(bench, "tiny.tiny-chat",
                                                    True)]
    assert "steps_per_s" in names
    got = {n: run_mod.reader(n, tmp_path / "bench")(view) for n in names}
    assert got["steps_per_s"] > 0
    # host metrics read; trace metrics find nothing and are left out
    assert got["slot_occupancy"] > 0 and got["decode_step_ms"] > 0
    assert got["device_idle"] is None and got["packed_matmul_roofline"] is None


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"),
                        "--workload", bench["workloads"][0]["name"],
                        "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
    for w in bench["workloads"]:
        _, _, spec, mix = harness.load_cell(w["name"])
        assert spec.max_len >= max(
            mix["prompt"].get("max", 0),
            max(mix["prompt"].get("values", [0]))) + mix["output"]["max"]
