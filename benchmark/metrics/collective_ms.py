"""Device time of the collectives (all-gathers and the like, by
operation name) per traced placement, averaged over the chips; 0.0 when
a trace holds none."""


def read(m):
    tr, n = m.get("trace"), m.get("traced_ops")
    if not tr or not n:
        return None
    return tr["collective_s"] / n * 1e3
