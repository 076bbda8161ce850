"""Readings for a cell's check limit, in one process on the chip:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

With one seed, a process of its own gives the same set-up, window and
end-to-end readings as `bench/run.py` with that seed: the control runs
only in the check, after the window has closed.

For each seed: a whole run of the cell (set-up, window, the reference
over the sample), and beside the reference the int4 control
(`bench/reference/`: the reference one precision step below the
program's int8 codes) read over the same prompts and served tokens: the
gap of the token the control puts first, judged against the cell's
limit as a run of the program is (`control.correct` has to be false).
The program's mean gaps over many seeds give the limit's lower reading,
the control's its upper one (PERF.md). The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

# importing run puts the checkout and the program on sys.path
from run import T_PROCESS, compile_cache, log, peaks_for


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log("no TPU")
        return 2
    from bench import cell
    spec = cell.load_spec(args.workload)
    rows = []
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        # set-up as a run of bench/run.py counts it: from process start
        t_proc = T_PROCESS if i == 0 else time.perf_counter()
        out = cell.run(spec, seed, args.seconds, False, t_proc,
                       peaks=peaks_for(dev.device_kind), log=log,
                       control=True)
        chk = out["check"]
        row = {"seed": seed,
               "program_gap_mean": chk["numbers"]["logit_gap_mean"][0],
               "program_gap_max": chk.get("gap_max"),
               "program_argmax_agree": chk.get("argmax_agree"),
               "control": chk["control"], "served_tokens":
               chk["served_tokens"], "failed": out["failed"],
               "e2e": out["e2e"], "setup_s": out["setup_s"],
               "peak": out["peak"]}
        rows.append(row)
        log(json.dumps(row))
    print(json.dumps({"workload": args.workload, "runs": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
