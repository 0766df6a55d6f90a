"""Seeded traffic from a traffic file: lengths, arrivals and prompt text.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"loop": "open", "rate_per_s": 4.0, "ramp_s": 6, "drain_s": 60,
     "sizes_seed": 1, "check_tokens": 3000,
     "prompt_tokens": {"dist": "lognormal", "median": 1500, "sigma": 0.8,
                       "min": 32, "max": 4096},
     "output_tokens": {"dist": "lognormal", "median": 13, "sigma": 1.0,
                       "min": 2, "max": 256}}

or ``"loop": "closed"`` with ``"clients"`` and ``"requests_per_client"``
in place of the rate and the segments.  Keys the generator does not read
(``source``, ``assumed``) say where the parameters come from.

Lengths are i.i.d. draws from their distributions, clipped to [min,
max].  Open-loop arrivals are a Poisson process: each segment (ramp
before the window, the window, the tail after it) holds ``round(rate x
length)`` requests at times drawn i.i.d. uniform over the segment, which
is a Poisson process given its count, bursts included.  Both draws come
from ``sizes_seed``, so every run seed serves the same multiset of
requests, each a (gap before it, prompt length, output length); the
run's seed orders them and picks the words of each prompt.  The gaps of
uniform times are exchangeable, so a reordering of them is as much a
Poisson process as the draw.

Prompts are built from words whose token ids under the program's
tokenizer are known, so each prompt tokenizes to exactly its drawn
length.  Each starts with a word that spells its request index, so no
two prompts share a KV block and the prefix cache finds nothing to reuse.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

_CONSONANTS = "bcdfghjklmnprstvwz"
_VOWELS = "aeiou"
_SYLLABLES = [c + v for c in _CONSONANTS for v in _VOWELS]


@dataclasses.dataclass
class Request:
    idx: int
    segment: str             # "ramp" | "window" | "tail" (open) / "pool"
    due: float               # seconds after load start (open loop)
    prompt_ids: List[int]
    text: str
    max_new: int


@dataclasses.dataclass
class Traffic:
    spec: dict
    requests: List[Request]


def load_spec(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    return spec


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of ``seed`` (any whole number)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` i.i.d. lengths from ``dist``, clipped to [min, max]."""
    if dist["dist"] == "lognormal":
        vals = dist["median"] * np.exp(dist["sigma"]
                                       * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        vals = rng.integers(dist["min"], dist["max"] + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(vals), dist["min"], dist["max"]).astype(int)


class PromptBuilder:
    """Prompt text of an exact token count under ``tokenizer``.

    The tokenizer splits text into words before merging, so a text made
    of words each led by a space tokenizes to the concatenation of the
    words' own ids."""

    def __init__(self, tokenizer):
        self.tok = tokenizer
        self._ids: Dict[str, List[int]] = {}
        words = [a + b for a, b in itertools.product(_SYLLABLES, repeat=2)]
        words += [c for c in "abcdefghijklmnopqrstuvwxyz"]
        self.words = words
        by_len: Dict[int, List[str]] = {}
        for w in words:
            by_len.setdefault(len(self.ids(w)), []).append(w)
        self.by_len = by_len
        self.max_word = max(by_len)
        if 1 not in by_len:
            raise RuntimeError("no one-token word to fill prompts with")

    def ids(self, word: str) -> List[int]:
        got = self._ids.get(word)
        if got is None:
            got = self._ids[word] = self.tok.encode(" " + word)
        return got

    def header(self, idx: int) -> str:
        """A word of syllables that spells ``idx``: unique per request."""
        digits = []
        while True:
            idx, d = divmod(idx, len(_SYLLABLES))
            digits.append(_SYLLABLES[d])
            if idx == 0:
                break
        return "q" + "".join(reversed(digits))

    def build(self, idx: int, n_tokens: int,
              rng: np.random.Generator) -> tuple:
        words = [self.header(idx)]
        ids = list(self.ids(words[0]))
        if len(ids) > n_tokens:
            raise ValueError(f"prompt of {n_tokens} tokens is shorter than "
                             f"its {len(ids)}-token header")
        picks = rng.integers(0, len(self.words), size=n_tokens)
        for k in picks:
            if n_tokens - len(ids) <= self.max_word:
                break
            w = self.words[int(k)]
            words.append(w)
            ids.extend(self.ids(w))
        while len(ids) < n_tokens:
            need = min(n_tokens - len(ids), self.max_word)
            while need not in self.by_len:
                need -= 1
            pool = self.by_len[need]
            w = pool[int(rng.integers(0, len(pool)))]
            words.append(w)
            ids.extend(self.ids(w))
        return "".join(" " + w for w in words), ids


def build(spec: dict, seed: int, seconds: float, tokenizer,
          builder: Optional[PromptBuilder] = None) -> Traffic:
    """The whole run's requests for ``spec`` under ``seed``."""
    builder = builder or PromptBuilder(tokenizer)
    if spec["loop"] == "open":
        rate = spec["rate_per_s"]
        segments = [("ramp", spec["ramp_s"]), ("window", seconds),
                    ("tail", spec["drain_s"])]
    else:
        segments = [("pool", None)]
    requests: List[Request] = []
    t = 0.0
    for s_idx, (segment, span) in enumerate(segments):
        n = (max(1, round(rate * span)) if span is not None
             else spec["clients"] * spec["requests_per_client"])
        sizes = rng_for(spec["sizes_seed"], s_idx)
        p_len = draw_lengths(spec["prompt_tokens"], n, sizes)
        o_len = draw_lengths(spec["output_tokens"], n, sizes)
        order = rng_for(seed, 10 * s_idx + 1).permutation(n)
        if span is not None:
            times = np.sort(rng_for(spec["sizes_seed"], 10 * s_idx + 3)
                            .uniform(0, span, n))
            due = t + np.cumsum(np.diff(times, prepend=0.0)[order])
        else:
            due = np.zeros(n)
        words = rng_for(seed, 10 * s_idx + 4)
        for i, j in enumerate(order):
            idx = len(requests)
            text, ids = builder.build(idx, int(p_len[j]), words)
            requests.append(Request(idx, segment, float(due[i]), ids, text,
                                    int(o_len[j])))
        t += span or 0.0
    return Traffic(spec, requests)
