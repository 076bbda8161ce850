"""The harness decides `correct` from what the timed path served: a run
at a CPU size passes, and the same run with the timed path broken
underneath it fails. This drives everything of `bench/run.py` but its
look for a chip."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import cell, check, run, tiny

CELL = "starcoder2-3b.decode-4k"
SECONDS = 2.0


def _argmin_tokens(engine):
    """Every decode step emits the token its logits rank last."""
    def step(params, tok, caches, pos):
        logits, caches = engine.model.decode_step(
            params, tok, caches, pos, ctx=engine.ctx,
            scales_groups=engine.scales_groups)
        return jnp.argmin(logits, -1)[:, None].astype(jnp.int32), caches
    engine._step = jax.jit(step, donate_argnums=(2,))


def _state_unchanged(engine):
    """Every decode step hands back the cache it was given: the K/V of
    the token it decoded is never kept."""
    def step(params, tok, caches, pos):
        out, _ = engine._step_fn(params, tok, caches, pos)
        return out, caches
    engine._step = jax.jit(step)


def _run(fault=None):
    spec = tiny.spec(CELL, width=512, vocab=2048)
    out = cell.run(spec, 2 ** 31 + 99, SECONDS, False, time.perf_counter(),
                   peaks=tiny.CPU_PEAKS, engine_hook=fault,
                   log=lambda m: None)
    return run.result_line(spec, out, False)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("fault", [_argmin_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault):
    res = _run(fault)
    assert not res["correct"], res["compared"]
    gap = res["compared"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]
