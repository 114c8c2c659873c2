"""chip_smoke.py's phases at tiny shapes on the CPU (Pallas kernels in
interpret mode), and its refusal to run anywhere but on a TPU. The
device checks of phase (a) are bypassed by calling the phases directly;
the script itself has no CPU option."""
import importlib.util
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_exits_nonzero_off_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_option_exits_nonzero_off_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--four-chips"])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("name,shape", [("nyx", (10, 9, 8)),
                                        ("climate", (20, 24))])
def test_roundtrip_phase(smoke, name, shape):
    out = smoke.roundtrip("rt", name, shape)
    assert out["backend"] == "pallas" and out["path"] == "device"
    assert out["mss_preserved"] and out["bound_ok"]


def test_service_phase(smoke):
    out = smoke.service("svc", (6, 10, 12), n=2)
    assert len(out["requests"]) == 2
    assert all(r["same_as_one_shot"] for r in out["requests"])


def test_parity_phase(smoke):
    out = smoke.parity("parity", shapes=((8, 8, 8), (16, 16)))
    assert set(out) == {"8x8x8", "16x16"}


def test_oracle_phase(smoke):
    out = smoke.oracle("oracle", shape=(6, 6, 6))
    assert out["mss_preserved"] and out["bound_ok"]


def test_phase_failure_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="phase-x: broken"):
        smoke.require(False, "phase-x", "broken")
