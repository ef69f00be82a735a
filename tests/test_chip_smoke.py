"""``chip_smoke.py``: refuses a CPU, and its flow runs at reduced width."""
import importlib.util
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_without_ok_line(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_flow_at_reduced_width(chip_smoke):
    """Calibrate -> export -> verified load -> engine serve -> logit
    comparison, end to end at the config's reduced widths. On the CPU
    both engines run the XLA references, so this checks the flow and the
    failure checks, not the Pallas kernels."""
    out = chip_smoke.smoke(reduced=True, iters=2)
    assert set(out["walls_s"]) == {"calibrate", "artifact", "serve",
                                   "serve_ref"}
    assert out["decode_kernels"] == set()  # no tpu_custom_call on the CPU
