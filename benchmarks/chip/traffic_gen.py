"""The one traffic generator: reads a mix file under `traffic/` and serves
prompts to the pipeline, timing every rollout from the draw of its prompt
to its reward.

A mix of kind "arithmetic" is a seeded copy of the program's synthetic
math task (sampler, character tokenizer and reward): prompts `<bos>a+b=`
or `<bos>a-b=` with operands below `max_operand`. The program receives
only the generated prompts; the reward is computed here as well, so the
call that scores a finished rollout also stamps its completion time.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import List, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

_CHARS = "0123456789+-*=() "
BOS, EOS = 1, 2
STOI = {ch: 3 + i for i, ch in enumerate(_CHARS)}
ITOS = {3 + i: ch for i, ch in enumerate(_CHARS)}


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def eos_id(mix: dict) -> int:
    """The token that ends a rollout, or -1 (none) where the mix sets
    `stop_at_eos` false: every rollout then runs to `max_len`, so every
    seed does the same amount of work."""
    return EOS if mix.get("stop_at_eos", True) else -1


def encode(text: str) -> List[int]:
    return [BOS] + [STOI[c] for c in text]


def decode(ids) -> str:
    return "".join(ITOS.get(int(i), "?") for i in ids if int(i) > EOS)


@dataclasses.dataclass(eq=False)
class Prompt:
    """What the engine admits (`prompt_ids`) plus the benchmark's timing."""
    prompt_ids: List[int]
    answer: int
    drawn_at: float = 0.0
    done_at: Optional[float] = None


class Traffic:
    """Prompt source and task for one run. `source()` is handed to the
    pipeline as its prompt source and `self` as its task."""

    def __init__(self, mix: dict, seed: int, clock=time.perf_counter):
        if mix["kind"] != "arithmetic":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        self.mix = mix
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed) % (1 << 64), 7]))
        self.clock = clock
        self.done: List[Prompt] = []

    def source(self) -> Prompt:
        m = self.mix
        a, b = (int(x) for x in self.rng.integers(0, m["max_operand"], 2))
        op = m["ops"][int(self.rng.integers(len(m["ops"])))]
        ans = a + b if op == "+" else a - b
        return Prompt(encode(f"{a}{op}{b}="), ans, drawn_at=self.clock())

    def reward(self, problem: Prompt, completion_ids: Sequence[int],
               max_new_tokens: int, soft_penalty_margin: int = 4) -> float:
        """1 for the exact answer, else 0, with the paper's soft penalty
        near the length limit (the program's task reward, copied)."""
        problem.done_at = self.clock()
        self.done.append(problem)
        text = decode(completion_ids).strip()
        body = ""
        for i, ch in enumerate(text):
            if ch.isdigit() or (ch == "-" and i == 0):
                body += ch
            else:
                break
        correct = body not in ("", "-") and int(body) == problem.answer
        r = 1.0 if correct else 0.0
        overrun = len(completion_ids) - (max_new_tokens - soft_penalty_margin)
        if overrun > 0:
            r -= 0.1 * overrun
        return float(r)
