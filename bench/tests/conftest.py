"""Fixtures of the benchmark's CPU tests."""

from __future__ import annotations

import pytest

from bench.tests import rehearsal


@pytest.fixture
def tpu_branch(monkeypatch):
    """Steer the system onto its TPU branch (TPU tile spaces, Pallas
    kernels) while the kernels run interpreted on the CPU."""
    from repro.dispatch import service
    from repro.kernels import util

    monkeypatch.setattr(util, "default_target", lambda: "tpu")
    monkeypatch.setattr(service, "default_target", lambda: "tpu")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # the tiny cells' limit lies between the bf16 system's widest gaps there
    # (0 to 0.022 over 11 seeds on the CPU) and the float8 control's (0.09
    # to 0.68 on the same seeds; the least where only two requests finish)
    return rehearsal.bench_copy(tmp_path_factory.mktemp("bench"), serve_limit=0.05)
