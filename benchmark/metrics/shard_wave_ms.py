"""Device time of the sharded wave program (``_place_run_sharded``) per
traced placement, averaged over the chips of the mesh."""

PROGRAM = "_place_run_sharded"


def seconds(m):
    tr, n = m.get("trace"), m.get("traced_ops")
    if not tr or not n:
        return None
    s = tr["programs_s"].get(PROGRAM, 0.0)
    return s / n if s > 0 else None


def read(m):
    s = seconds(m)
    return None if s is None else s * 1e3
