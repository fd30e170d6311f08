"""The one traffic generator: prompts and rewards from a traffic file.

The system gets only what this makes: prompt token arrays (through the
task's ``sample_prompt``) and the reward of each finished response (through
``reward``). Prompt lengths are uniform in ``[prompt_len_min,
prompt_len_max]`` and tokens uniform over the vocabulary slice, the stop
token left out; both come from the run's seed. The reward is a
deterministic function of the response that pays about half of them, so
group advantages are non-zero and the update moves the weights.
"""
from __future__ import annotations

import threading

import numpy as np

try:                                # host spans in the profiler's trace
    from jax.profiler import TraceAnnotation
except ImportError:                 # pragma: no cover
    TraceAnnotation = None


def parity_reward(response_tokens, eos_id: int) -> float:
    """1.0 when the sum of the response's tokens (stop token left out) is
    even. Over near-uniform tokens that is a fair coin per response."""
    toks = np.asarray(response_tokens, np.int64)
    toks = toks[toks != eos_id]
    return 1.0 if int(toks.sum()) % 2 == 0 else 0.0


REWARDS = {"parity": parity_reward}


class TrafficTask:
    """``sample_prompt`` / ``reward`` for the trainer, from a traffic file.

    Counts the prompts and prompt tokens it handed out (the window's
    required prefill work)."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int, *,
                 spans: bool = False):
        self.t = traffic
        self.vocab_size = vocab_size
        self.eos_id = int(traffic["eos_id"])
        self.rng = np.random.default_rng(np.random.SeedSequence(seed))
        self._reward = REWARDS[traffic["reward"]]
        self._spans = spans and TraceAnnotation is not None
        self._lock = threading.Lock()
        self.prompts = 0
        self.prompt_tokens = 0

    def _draw(self):
        lo, hi = self.t["prompt_len_min"], self.t["prompt_len_max"]
        n = int(self.rng.integers(lo, hi + 1))
        toks = self.rng.integers(2, self.vocab_size, n, dtype=np.int32)
        toks[toks == self.eos_id] = 0
        with self._lock:
            self.prompts += 1
            self.prompt_tokens += n
        return toks, None

    def sample_prompt(self):
        if self._spans:
            with TraceAnnotation("bench.prompt"):
                return self._draw()
        return self._draw()

    def reward_of(self, response_tokens) -> float:
        """The reward, without a span (for the reference)."""
        return self._reward(response_tokens, self.eos_id)

    def reward(self, response_tokens, answer) -> float:
        if self._spans:
            with TraceAnnotation("bench.reward"):
                return self._reward(response_tokens, self.eos_id)
        return self._reward(response_tokens, self.eos_id)
