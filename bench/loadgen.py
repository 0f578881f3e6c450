"""The one traffic generator, and the closed loop that offers its requests.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``):

    prompt_len       ids per prompt, drawn uniformly from the vocabulary by
                     the seed
    output_len       greedy tokens per request (the first comes from the
                     prefill)

One user offers them in a closed loop: the next request is sent when the
previous one completes. Set-up serves ``WARMUP_REQUESTS`` requests from a
stream of prompts the window never sends, so every shape the window uses is
compiled before it opens. Every seed gives the same sizes; the seed picks
the ids only.
"""
from __future__ import annotations

import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

WARMUP_REQUESTS = 1


def prompt(traffic: Dict, vocab: int, seed: int, index: int) -> np.ndarray:
    """Request ``index``'s prompt ids (the same for the same seed)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    return rng.integers(0, vocab, traffic["prompt_len"]).astype(np.int32)


@dataclass
class Request:
    prompt: np.ndarray
    sent: float
    token_times: List[float] = field(default_factory=list)
    served: List[int] = field(default_factory=list)
    contexts: List[int] = field(default_factory=list)    # per decode step:
                                                          # positions attended
    failed: bool = False


class Server:
    """The engine as a greedy single-user server. ``prefill`` and ``step``
    return the next token, or raise; a token comes from finite logits over
    the whole vocabulary."""

    def __init__(self, eng, vocab: int, annotate: Callable):
        self.eng, self.vocab, self.annotate = eng, vocab, annotate

    def _token(self, logits) -> int:
        logits = np.asarray(logits)
        if logits.shape != (1, self.vocab) or not np.isfinite(
                logits.astype(np.float32)).all():
            raise FloatingPointError(f"bad logits {logits.shape}")
        return int(np.argmax(logits[0]))

    def prefill(self, ids: np.ndarray) -> int:
        with self.annotate("prefill"):
            self.logits = self.eng.prefill(ids[None, :])
            return self._token(self.logits)

    def step(self) -> int:
        with self.annotate("decode"):
            self.eng.decode(self.logits, 1)
            self.logits = self.eng.last_logits
            return self._token(self.logits)

    @property
    def position(self) -> int:
        return int(self.eng.cur_len)


def closed_loop(server: Server, traffic: Dict, seed: int, deadline: float,
                start_index: int = 0, max_requests: Optional[int] = None) -> Dict:
    """Offer requests until ``deadline`` (a request in flight then runs to its
    end), or until ``max_requests`` were sent. Returns the requests and the
    counts of requests attempted and failed; a request fails on the first
    step that raises, and the loop stops there."""
    requests: List[Request] = []
    failed = 0
    n_out = traffic["output_len"]
    while time.perf_counter() < deadline and (
            max_requests is None or len(requests) < max_requests):
        req = Request(prompt(traffic, server.vocab, seed, start_index + len(requests)),
                      time.perf_counter())
        requests.append(req)
        try:
            req.served.append(server.prefill(req.prompt))
            req.token_times.append(time.perf_counter())
            while len(req.served) < n_out:
                pos = server.position
                req.served.append(server.step())
                req.token_times.append(time.perf_counter())
                req.contexts.append(pos + 1)
        except Exception:                   # a failed request is counted
            traceback.print_exc(file=sys.stderr)
            req.failed = True
            failed += 1
            break
    return {"requests": requests, "attempted": len(requests), "failed": failed}
