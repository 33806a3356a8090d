"""Scheduler: mean share of the engine's slots active per decode step
over the window (``EngineStats`` occupancy counters)."""


def read(record):
    steps = record.get("occupancy_steps")
    if not steps:
        return None
    return 100.0 * record["occupancy_sum"] / steps / record["slots"]
