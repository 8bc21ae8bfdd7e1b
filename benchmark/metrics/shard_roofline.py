"""The least time the chips' peaks allow the placement over the sharded
wave program's device time: ``roofline.least_seconds`` of the graph's
own bytes (``roofline.placement_bytes``, blind to how the program moves
them) spread over the HBM of every chip of the mesh, over the device
seconds that ``shard_wave_ms`` reads."""

from benchmark import roofline
from benchmark.metrics.shard_wave_ms import seconds


def read(m):
    s = seconds(m)
    if s is None or not m.get("peaks") or not m.get("bytes_per_op") \
            or not m.get("shards"):
        return None
    least = roofline.least_seconds(m["bytes_per_op"], m["peaks"],
                                   m["tasks_per_op"]) / m["shards"]
    return least / s * 100
