"""CPU rehearsal of chip_smoke.py: each phase runs its real entry point, at
a tiny size, with the Pallas kernels in interpret mode. The test steers the
program onto its TPU branch itself (the TPU tile space, the Pallas ops, a
``target="tpu"`` dispatch service); the program has no option for that."""

from __future__ import annotations

import importlib.util
import os

import pytest

from repro.dispatch import DispatchService
from repro.kernels import problems, util
from repro.kernels.spaces import TPU_TILES
from repro.launch import autotune
from repro.obs.metrics import MetricsRegistry

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_tpu_branch(monkeypatch):
    """Take the TPU branch of the autotune CLI and the campaign dims while
    the kernels still run interpreted on the CPU; LARGE syr2k shrinks to a
    size the interpreter times in well under a second."""
    monkeypatch.setattr(util, "default_target", lambda: "tpu")
    monkeypatch.setattr(autotune, "default_target", lambda: "tpu")
    monkeypatch.setitem(problems.LARGE_SHAPES, "syr2k", (40, 24))


def test_paper_loop_phase_rehearsal(on_tpu_branch):
    summary = _load_smoke().phase_autotune(evals=3)
    assert summary["dims"] == [40, 24]
    assert summary["evaluations"] == 3
    assert summary["best_config"]["bi"] in TPU_TILES   # the TPU tile space
    assert summary["best_rel_err"] <= summary["tolerance"]
    assert summary["default_rel_err"] <= summary["tolerance"]


def test_serving_phase_rehearsal():
    svc = DispatchService(target="tpu", metrics=MetricsRegistry())
    summary = _load_smoke().phase_serving(reduced=True, batch=2, prompt_len=8,
                                          gen=4, service=svc)
    for kernel in ("flash_attention", "decode_attention"):
        assert summary["kernels"][kernel]["impl"] == ["pallas"]
        assert summary["kernels"][kernel]["calls"] > 0
    assert summary["logits_rel_err"] <= summary["tolerance"]


def test_training_phase_rehearsal():
    summary = _load_smoke().phase_training(reduced=True, steps=2, batch=2,
                                           seq=16)
    assert summary["steps"] == 2


def test_chip_smoke_refuses_cpu(capsys):
    assert _load_smoke().main() != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
