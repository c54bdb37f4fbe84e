"""``chip_smoke.py`` refuses to run anywhere but on a TPU: on the CPU it
exits non-zero and never prints its ``"ok": true`` line."""
import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def test_chip_smoke_exits_nonzero_without_a_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok": true' not in capsys.readouterr().out
