"""The whole step's share of the chip's int8 peak, in percent: model
FLOPs of every decoded token and every prompt token the engine
dispatched in the traced window (`costs/model_step.py`), over the
window's length times the int8 peak."""
from bench.costs import model_step


def read(obs):
    steps, chunks = obs.steps_in(), obs.chunks_in()
    if not steps and not chunks:
        return None
    flops = sum(model_step.decode_step(obs.sizes, s.ctx) for s in steps)
    flops += sum(model_step.chunk(obs.sizes, c.pos, c.completed)
                 for c in chunks)
    secs = obs.window[1] - obs.window[0]
    return 100.0 * flops / (secs * obs.peaks[model_step.PEAK])
