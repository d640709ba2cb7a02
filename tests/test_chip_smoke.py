"""CPU rehearsal of chip_smoke.py: the program refuses to run without a
TPU, and its phases pass at the reduced config with the Pallas kernels in
interpret mode (the chip-only checks — the TPU platform and the Mosaic
kernel in the int4 decode program — are left to the chip run)."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses resolve it by name
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.modules.pop("chip_smoke", None)


def test_chip_smoke_exits_nonzero_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_phases_rehearse_on_cpu(chip_smoke, monkeypatch,
                                           tmp_path, capsys):
    # with the variable set the entry point leaves this process's
    # persistent compilation cache as it is
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    smoke = chip_smoke.Smoke(reduced=True, calib=(2, 4, 64), interpret=True,
                             chip_checks=False)
    chip_smoke.run(smoke, [0.0])
    out = capsys.readouterr().out
    for phase in ("a_kernel_parity", "b_serve_wbits16", "b_serve_wbits4",
                  "c_watersic"):
        assert f"{phase} compile_s" in out and f"{phase} run_s" in out
    assert out.count("kernel vs XLA twin") == 2 * 3 * 2
    assert out.count("WaterSIC entropy") == 7
