"""The correctness checks catch a broken timed path: each fault the cells
can have is planted under the system's public API, the harness drives the
rest of a run on the CPU, and ``correct`` must come out false."""

from __future__ import annotations

import pytest

from bench.tests import rehearsal
from bench.tests.rehearsal import DOCS, SERVE


def _break_serve_step(monkeypatch, fault):
    import repro.serve

    real = repro.serve.make_serve_step

    def make(cfg, **kw):
        step = real(cfg, **kw)

        def broken(params, cache, token, pos):
            nxt, logits, new = step(params, cache, token, pos)
            if fault == "state_unchanged":      # the step forgets its KV write
                return nxt, logits, cache
            return (nxt + 1) % cfg.vocab_size, logits, new   # a token altered

        return broken

    monkeypatch.setattr(repro.serve, "make_serve_step", make)


@pytest.mark.parametrize("cell", [SERVE, DOCS])
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_serving_faults_are_caught(tpu_branch, root, monkeypatch, fault, cell):
    _break_serve_step(monkeypatch, fault)
    rc, line, _ = rehearsal.run(root, cell, seconds=4.0)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["max_gap"]["value"] > line["checks"]["max_gap"]["limit"]
