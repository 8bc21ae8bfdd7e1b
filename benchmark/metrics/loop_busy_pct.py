"""The event loop's busy share of the window: 100 x (1 - ``loop.idle``
/ ``window_s``), ``loop.idle`` being the loop's selector wait
(``WallBudget``, one budget per event loop).  Whether the loop sets the
pace."""


def read(m):
    wall, window = m.get("wall_delta"), m.get("window_s")
    if wall is None or "loop.idle" not in wall or not window:
        return None
    return 100 * (1 - wall["loop.idle"] / window)
