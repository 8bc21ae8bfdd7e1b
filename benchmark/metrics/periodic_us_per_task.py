"""Host side of the periodic device kernels (``WallBudget`` phases
``steal.cycle``, ``amm.cycle``, ``mirror.upload``, ``telemetry.fold``)
in the window, per task completed.  Absent without the cycle phases."""

PHASES = ("steal.cycle", "amm.cycle", "mirror.upload", "telemetry.fold")


def read(m):
    wall, n = m.get("wall_delta"), m.get("tasks_done")
    if wall is None or not n or not ("steal.cycle" in wall
                                     or "amm.cycle" in wall):
        return None
    return sum(wall.get(k, 0.0) for k in PHASES) / n * 1e6
