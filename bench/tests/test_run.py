"""A whole run of a cell on the CPU, past the look for a chip: set-up,
window, metrics and the check, as ``run.py`` makes them.  Sound, the run is
correct; with the timed path broken underneath, it is not."""
import argparse
import json
import shutil
from pathlib import Path

import pytest

import harness
import roofline
import run as run_mod
from conftest import (TINY, TINY_CHAT, TINY_DECODE, alter_tokens,
                      config_limits, control_answers, half_batch,
                      stale_cache)

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json holds two tiny cells, the model held
    to the tightest limit of the configurations."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = dict(TINY, check=dict(TINY["check"],
                                 max_gap_sigma=min(config_limits().values())))
    (root / "bench/configs/tiny.json").write_text(json.dumps(tiny))
    (root / "bench/traffic/tiny-chat.json").write_text(json.dumps(TINY_CHAT))
    (root / "bench/traffic/tiny-decode.json").write_text(
        json.dumps(TINY_DECODE))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for mix in ("tiny-chat", "tiny-decode"):
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny",
                                   "traffic": mix, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, workload, monkeypatch, breaker=None):
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    if breaker is not None:
        build = harness.build_engine

        def broken(*a, **k):
            eng = build(*a, **k)
            breaker(eng)
            return eng
        monkeypatch.setattr(harness, "build_engine", broken)
    import jax
    args = argparse.Namespace(workload=workload, seed=SEED, seconds=3.0,
                              trace=0)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return run_mod.run_cell(args, bench, jax.devices(), root)


@pytest.mark.parametrize("workload", ["tiny.tiny-chat", "tiny.tiny-decode"])
def test_sound_run_is_correct(tiny_root, workload, monkeypatch):
    r = _run(tiny_root, workload, monkeypatch)
    assert r["correct"], r["check"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"]["output_tok_s"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert list(r)[-1] == "check"
    assert r["check"]["max_gap_sigma"]["value"] <= \
        r["check"]["max_gap_sigma"]["limit"]


@pytest.mark.parametrize("breaker", [alter_tokens, stale_cache, half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_broken_timed_path_makes_the_run_not_correct(tiny_root, breaker,
                                                      monkeypatch):
    r = _run(tiny_root, "tiny.tiny-decode", monkeypatch, breaker)
    assert r["attempted"] > 0
    assert not r["correct"], r["check"]


def test_control_in_the_programs_place_is_not_correct(tiny_root,
                                                      monkeypatch):
    """The float8 reference answers every request in the program's place,
    and the run's own check reads it over the limit.  The decode cell's
    check compares some hundreds of tokens; at this width the control's
    widest gap over as many reads 0.7 to 1.3 across seeds."""
    drive = harness.drive

    def control_drive(eng, rec, spec, mix, seed, *a, **k):
        w = drive(eng, rec, spec, mix, seed, *a, **k)
        control_answers(spec, seed, [r for r in w.arrived if r.done])
        return w
    monkeypatch.setattr(harness, "drive", control_drive)
    r = _run(tiny_root, "tiny.tiny-decode", monkeypatch)
    assert r["attempted"] > 0 and r["check"]["bad_tokens"]["value"] == 0
    assert not r["correct"], r["check"]
    gap = r["check"]["max_gap_sigma"]
    assert gap["value"] > gap["limit"]


def test_profiler_time_is_left_out_of_the_window(tiny_root, tmp_path,
                                                 monkeypatch):
    """A traced window serves its whole length: the seconds in which the
    profiler starts and stops are not counted in it."""
    import time

    import jax
    import traffic
    from weights import program_params
    _, _, spec, mix = harness.load_cell("tiny.tiny-chat", tiny_root)
    eng = harness.build_engine(spec, program_params(spec, SEED))
    rec = harness.Recorder(eng)
    harness.warm(eng, spec)
    start = jax.profiler.start_trace

    def slow_start(*a, **k):
        time.sleep(1.0)
        return start(*a, **k)
    monkeypatch.setattr(jax.profiler, "start_trace", slow_start)
    t = time.perf_counter()
    w = harness.drive(eng, rec, spec, mix, SEED, 3.0,
                      harness.CompileClock(), tmp_path / "trace")
    assert w.held_s >= 1.0 and "stop" in w.trace
    assert time.perf_counter() - t >= 3.0 + w.held_s
    assert w.span_s < 3.0 + 1.0
    assert len(w.arrived) == len(traffic.open_loop(mix, SEED, 3.0, 10))
