"""Mean host tile assembly and sharded upload of a placement on the
mesh (``timings["tiles_s"]``, span ``place.tiles``)."""

import numpy as np


def read(m):
    tiles = m.get("tiles_s")
    return float(np.mean(tiles)) * 1e3 if tiles else None
