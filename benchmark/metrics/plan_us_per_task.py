"""Planner front-end self time on the loop (``WallBudget`` phases
``plan.*``: batch filter and snapshot, plan merge) in the window, per
task completed."""


def read(m):
    wall, n = m.get("wall_delta"), m.get("tasks_done")
    if wall is None or not n:
        return None
    got = [v for k, v in wall.items() if k.startswith("plan.")]
    return sum(got) / n * 1e6 if got else None
