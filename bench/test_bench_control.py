"""The check's control at a CPU size: with the program's window run as
usual, the reference's int4 stream (one precision step below the
program's int8 codes, at its granularity), judged by `check.verdict`
against the limit at this size as a run of the program is, comes out
not correct, and reads a mean logit gap ten times the program's or
more. On the chip the same readings, at each cell's own size, set the
cell's limit (PERF.md)."""
import time

import pytest

from bench import cell, tiny

CELLS = ["starcoder2-3b.decode-4k", "mistral-large-123b-l4.offline-chat"]


@pytest.mark.parametrize("name", CELLS)
def test_int4_control_reads_far_above_the_program(name):
    spec = tiny.spec(name, width=512, vocab=2048)
    out = cell.run(spec, 2 ** 31 + 5, 2.0, False, time.perf_counter(),
                   peaks=tiny.CPU_PEAKS, log=print, control=True)
    chk = out["check"]
    program = chk["numbers"]["logit_gap_mean"][0]
    assert chk["served_tokens"] > 0
    assert chk["control"]["correct"] is False, chk["control"]
    assert chk["control"]["logit_gap_mean"] > max(10 * program, 0.1)
