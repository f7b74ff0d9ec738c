"""The one traffic generator: turns a traffic file's parameters and a seed
into requests.

Lengths come from fixed sets declared in the traffic file, so that every
seed serves the same work: requests are drawn in blocks, and each block
holds every prompt length and every output length once, paired and ordered
by the seed. Any run of consecutive requests therefore has nearly the same
length mix, whatever the seed; the seed changes the order, the pairing and
the token ids.

Length sets (``prompt_lens`` / ``output_lens``) are given as
``{"min", "max", "count", "spacing"}`` with spacing ``"log"`` (endpoints
included, geometric steps) or ``"log_quantiles"`` (the midpoints of
``count`` equal-probability bins of a log-uniform law on [min, max]).
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Request", "length_set", "request_stream"]


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray       # (prompt_len,) int32 token ids
    out_len: int             # output tokens to serve, the first included

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def length_set(spec: dict) -> list[int]:
    lo, hi, n = float(spec["min"]), float(spec["max"]), int(spec["count"])
    if spec["spacing"] == "log":
        q = np.arange(n) / max(n - 1, 1)
    elif spec["spacing"] == "log_quantiles":
        q = (np.arange(n) + 0.5) / n
    else:
        raise ValueError(f"unknown length spacing {spec['spacing']!r}")
    return [int(round(lo * (hi / lo) ** x)) for x in q]


def request_stream(traffic: dict, seed: int, vocab: int):
    """Endless, seeded stream of :class:`Request` in send order."""
    prompts = length_set(traffic["prompt_lens"])
    outputs = length_set(traffic["output_lens"])
    if len(prompts) != len(outputs):
        raise ValueError("prompt and output length sets must be equally long")
    rng = np.random.default_rng(seed)
    rid = 0
    while True:
        for i, j in zip(rng.permutation(len(prompts)),
                        rng.permutation(len(outputs))):
            prompt = rng.integers(0, vocab, size=prompts[i], dtype=np.int32)
            yield Request(rid, prompt, outputs[j])
            rid += 1
