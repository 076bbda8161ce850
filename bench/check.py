"""The comparison that decides `correct`.

Once the window has closed and the program's state is freed, a sample
of the requests the window finished, drawn from the seed and always
holding the longest one (topped up, where few finished, with the tokens
that requests still in flight had streamed), is run through the plain
reference of the
configuration (`bench/reference/<name>.py`) over each prompt with the
tokens the engine served. For each served token the reference reads how
far that token's logit lies below its own best logit at that position.
The mean of those gaps over the sample is compared with the cell's limit
(`bench/cells/<cell>.json`, `check.limit.logit_gap_mean`, set from the
readings in PERF.md). The widest gap is printed beside it but not
compared: at a near-tie any rounding puts the second token first, so the
widest gap is the largest near-tie margin, alike for the program and a
control of lower precision (PERF.md). The reference imports nothing of
the program and takes nothing it made: it draws the same bf16 weights
from the seed itself, layer by layer.
"""
from __future__ import annotations

import importlib
import time
from typing import List

import numpy as np

from bench import loadgen


def reference(conf: dict):
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def ref_sizes(conf: dict) -> dict:
    return reference(conf).sizes(conf)


def sample(sent, seed: int, n: int, t1: float) -> List:
    """`n` requests of the run: the finished ones first (those the
    measured window finished, else any), the longest always and the rest
    drawn from the seed; then, where fewer finished, those still in
    flight at the close that had streamed the most tokens, so that a
    window of long requests is checked over hundreds of served tokens."""
    done = [s for s in sent if s.finished]
    inside = [s for s in done if s.events[-1].t < t1]
    pool = sorted(inside or done,
                  key=lambda s: (-(len(s.req.tokens) + s.req.gen), s.rid))
    chosen = pool[:1]
    if len(pool) > 1:
        rng = np.random.default_rng(loadgen.seed_words(seed) + [0x636B])
        rest = rng.permutation(len(pool) - 1)[:n - 1] + 1
        chosen += [pool[i] for i in sorted(rest)]
    flight = sorted((s for s in sent if s.events and not s.finished),
                    key=lambda s: (-len(s.events), s.rid))
    return chosen + flight[:max(n - len(chosen), 0)]


def run(spec, seed: int, rec: dict, sizes: dict, *, control: bool = False,
        log=print) -> dict:
    """Reference readings for the run's sample. Returns {"numbers":
    {name: (value, limit)}, "served_tokens", "control" (readings of the
    int4 control when asked, with its verdict), "seconds"}."""
    t = time.perf_counter()
    chosen = sample(rec["sent"], seed, int(spec.settings["check"]
                                          ["requests"]), rec["t1"])
    limit = spec.settings["check"]["limit"]["logit_gap_mean"]
    if not chosen:
        return {"numbers": {"logit_gap_mean": (None, limit)},
                "served_tokens": 0, "control": None, "seconds": 0.0}
    seqs = [np.concatenate([s.req.tokens, s.tokens]).astype(np.int32)
            for s in chosen]
    n_prompt = [len(s.req.tokens) for s in chosen]
    out = reference(spec.conf).gaps(spec.conf, seed, seqs, n_prompt,
                                    spec.settings["max_seq"],
                                    control=control)
    served = np.concatenate(out["served"])
    res = {"numbers": {"logit_gap_mean": (float(served.mean()), limit)},
           "served_tokens": int(served.size),
           "gap_max": float(served.max()),
           "argmax_agree": float(np.mean(served == 0.0)),
           "control": None, "seconds": time.perf_counter() - t}
    if control:
        # the control in the program's place, judged as a run is
        c = np.concatenate(out["control"])
        res["control"] = {"logit_gap_mean": float(c.mean()),
                          "gap_max": float(c.max()),
                          "argmax_agree": float(np.mean(c == 0.0)),
                          "correct": verdict({"logit_gap_mean":
                                              (float(c.mean()), limit)}, 0)}
    log(f"[check] {len(chosen)} requests, {served.size} served tokens: "
        f"mean logit gap {served.mean():.6g} (widest {res['gap_max']:.6g}, "
        f"{100 * res['argmax_agree']:.1f}% at the reference's best) in "
        f"{res['seconds']:.1f} s" + (
            f"; int4 control: mean {res['control']['logit_gap_mean']:.6g}, "
            f"widest {res['control']['gap_max']:.6g}, "
            f"{100 * res['control']['argmax_agree']:.1f}% at the best, "
            f"correct {res['control']['correct']}" if control else ""))
    return res


def verdict(numbers: dict, failed: int) -> bool:
    """Correct: no request failed, and every number at or under its
    limit (a number with no limit set fails)."""
    if failed:
        return False
    for value, limit in numbers.values():
        if value is None or limit is None or not value <= limit:
            return False
    return True
