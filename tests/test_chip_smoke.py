"""CPU rehearsals of chip_smoke.py (the on-chip-measurement guide's §2,
rehearsals 1 and 2): the SAME script and the same ``cli.main`` path, at
a tiny size through a test-only override of the script's module-level
argv lists — not through a program option.  The kernels run in Pallas
interpret mode here, so this finds wrong paths, arguments, meshes and
sharding rules; it says nothing about the chip."""

import importlib.util
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TINY = ["--n_layers", "1", "--d_model", "32", "--n_heads", "2",
         "--d_ff", "64"]
_TEXT = ["--ngd", "--bs", "8", "--seq_len", "16", "--subset_stride", "64"]


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.syspath_prepend(_REPO)     # transformer_test / resnet50_test
    monkeypatch.setattr(mod, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(mod, "MIN_KERNELS",
                        dict.fromkeys(mod.MIN_KERNELS, 0))
    return mod


def test_fails_without_a_tpu(smoke, capsys):
    """No accelerator: a non-zero exit and no result line — never a
    fallback to the CPU."""
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_one_chip_phase_tiny(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "TRANSFORMER", _TEXT + _TINY)
    monkeypatch.setattr(smoke, "RESNET",
                        ["--model", "resnet18", "--ngd", "--bs", "8",
                         "--subset_stride", "128"])
    failures = []
    smoke.one_chip(failures)
    assert not failures


def test_four_chip_phase_on_virtual_devices(smoke, monkeypatch):
    """The mesh phase over the suite's virtual CPU devices, with the
    flash kernels and the Pallas MLP head FORCED onto the path so that
    the shard_map kernel layer's data-axis routes run end to end
    (default CPU routing has no kernel): default mesh (all devices on
    dp), dp=2,tp=2, and the one-device comparison — whose per-step
    losses the script itself holds to its stated tolerance."""
    kernels = ["--attention", "flash", "--mlp_impl", "pallas"]
    monkeypatch.setenv("FDT_FORCE_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "TRANSFORMER", _TEXT + _TINY + kernels)
    monkeypatch.setattr(smoke, "TRANSFORMER_TP", _TEXT + _TINY + kernels)
    failures = []
    smoke.four_chips(failures)
    assert not failures
