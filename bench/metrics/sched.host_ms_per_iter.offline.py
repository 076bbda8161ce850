"""Host time per scheduler iteration spent outside the device programs,
in ms: the engine's `engine_step_phase_seconds` for its `retire` and
`admit` phases, per iteration, averaged over the measured window."""


def read(obs):
    r, a = obs.phases.get("retire", []), obs.phases.get("admit", [])
    n = min(len(r), len(a))
    if n == 0:
        return None
    return 1e3 * (sum(r[:n]) + sum(a[:n])) / n
