"""chip_smoke.py's phases at small sizes on the CPU (Pallas interpreted):
the same entry points and reference checks the chip run makes."""

import importlib.util
import sys
from pathlib import Path

import pytest

_path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _path)
chip_smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SMALL = chip_smoke.Sizes(sort=12, native_max=12, query_rows=12, query_ids=8,
                         stream=14, kernel_sort=12, moe_tokens=7,
                         distributed=12, distributed_stream=13)


@pytest.mark.parametrize("phase", ["sort", "native", "query", "stream"])
def test_phase_small(phase):
    getattr(chip_smoke, f"phase_{phase}")(0, SMALL)


def test_phase_kernels_interpreted():
    chip_smoke.phase_kernels(0, SMALL, interpret=True)


def test_phase_distributed_one_device():
    chip_smoke.phase_distributed(0, SMALL, 1)


def test_no_tpu_exits_nonzero(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
