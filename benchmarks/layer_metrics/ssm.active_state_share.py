"""Layer: KV cache. Lane-steps of the state pool that belonged to a lane the
dispatch SERVED, as a share of the lane-steps whose state the window's
dispatches read and wrote, in percent: delta
``dyn_ssm_active_lane_steps_total`` / delta ``dyn_ssm_lane_steps_total``
(``harness/state.py``). A decode step reads and writes every lane of the
pool, so with every lane busy this reads 100 and with a quarter of them
25 (``engine.batch_occupancy`` / ``max_batch``): what a program that
gathered only the served lanes would save."""
from benchmarks.harness.launch import delta
from benchmarks.harness.state import ACTIVE, LANE_STEPS


def reduce(scrapes, trace, run):
    steps = delta(scrapes["before"], scrapes["after"], LANE_STEPS)
    if steps <= 0:
        return None
    return 100.0 * delta(scrapes["before"], scrapes["after"], ACTIVE) / steps
