"""Worker self time on the loop (``WallBudget`` phases ``wengine.*``,
the state machine, and ``wshell.*``, the server around it) in the
window, per task completed.  Absent without the ``wshell.*`` phases."""


def read(m):
    wall, n = m.get("wall_delta"), m.get("tasks_done")
    if wall is None or not n or not any(k.startswith("wshell.")
                                        for k in wall):
        return None
    return sum(v for k, v in wall.items()
               if k.startswith(("wengine.", "wshell."))) / n * 1e6
