"""Request generator for every traffic mix: one general reader of the
parameter files under `bench/traffic/<mix>.json`.

Sizes come from the mix's own `size_seed`, in the same
order for every run seed: `--seed` draws the token ids only. A window
holds a few long requests, so which of them it admits decides much of
its work; with the sizes fixed, two runs of different seeds do the same
work on different tokens.

A mix file holds:

- `loop`: "closed", the one loop there is: `clients` callers, each
  sending its next request when the last one finished.
- `requests`: how many requests are drawn (more than a window serves).
- `prompt`, `output`: length distributions (see `draw`).
- `first_output`: the residual output budget of each client's first
  request, so completions are staggered from the start.
- `warmup`: the requests that set-up sends to compile every program.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Req:
    tokens: np.ndarray        # prompt token ids
    gen: int                  # tokens to serve, the first included


@dataclasses.dataclass
class Traffic:
    mix: dict
    requests: List[Req]       # in the order they are sent
    first: List[Req]          # each client's first request
    warmup: List[List[Req]]   # waves of set-up requests, sent in order

    @property
    def loop(self) -> str:
        return self.mix["loop"]

    @property
    def max_seq(self) -> int:
        return max(len(r.tokens) + r.gen - 1
                   for r in self.requests + self.first)


def load_mix(name: str, root: pathlib.Path = HERE) -> dict:
    path = root / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """`n` whole lengths from a distribution spec: uniform over
    [min, max] inclusive, or lognormal with `median` and `sigma`
    clipped to [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def seed_words(seed: int) -> list:
    """A run seed of any size as 32-bit words (numpy's SeedSequence
    takes a list of them)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def generate(mix: dict, seed: int, vocab: int) -> Traffic:
    """The mix's requests for one run seed.

    Everything that sizes the work is drawn from the mix's `size_seed`,
    in this order: each request's lengths, then the clients' first
    requests with their residual budgets. The run seed draws the token
    ids."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    sizes = np.random.default_rng(int(mix["size_seed"]))
    run = np.random.default_rng(seed_words(seed) + [0x6C6F6164])
    tok = lambda m: run.integers(0, vocab, int(m)).astype(np.int32)

    def batch(k: int) -> List[Req]:
        outs = draw(mix["output"], k, sizes)
        prompts = draw(mix["prompt"], k, sizes)
        return [Req(tok(p), int(g)) for p, g in zip(prompts, outs)]

    reqs = batch(int(mix["requests"]))
    c = int(mix["clients"])
    first = batch(c)
    # each client's first request serves only a residual budget
    for r, b in zip(first, draw(mix["first_output"], c, sizes)):
        r.gen = int(min(b, r.gen))
    w = mix["warmup"]
    warmup = [[Req(tok(w["prompt"]), int(w["output"]))
               for _ in range(int(w["requests"]))]]
    return Traffic(mix, reqs, first, warmup)


def describe(t: Traffic, n: Optional[int] = None) -> str:
    rs = t.requests if n is None else t.requests[:n]
    p = np.array([len(r.tokens) for r in rs])
    g = np.array([r.gen for r in rs])
    return (f"{t.loop} loop, {len(t.requests)} requests drawn: prompt "
            f"{p.min()}-{p.max()} (mean {p.mean():.0f}), output "
            f"{g.min()}-{g.max()} (mean {g.mean():.0f})")
