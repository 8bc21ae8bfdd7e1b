"""Device kernels: the scheduler co-processor and the mesh data plane.

Scheduler co-processor (consumed via ``scheduler.jax.*`` config gates):

- ``leveled``    — level-synchronous whole-graph placement (the north
                   star: a 1M-task DAG in ~213 ms on one v5e chip,
                   benchmark cell dag_1m_512w.random)
- ``placement``  — batched decide_worker cost kernels + sharded variant
- ``wavefront``  — round-1 wavefront placer (kept as oracle/fallback)
- ``stealing``   — vectorized (victim, level, thief) steal selection
- ``amm``        — replica-drop bin-unpacking for ReduceReplicas
- ``rebalance``  — sender/recipient move pairing for rebalance()

Mesh data plane / long context:

- ``ici``            — hash-shuffle + ring exchange over XLA collectives
- ``ring_attention`` — exact attention, sequence sharded over the mesh
- ``ulysses``        — all-to-all (seq<->head) context parallelism
- ``flash``          — pallas flash attention for the local block
"""

from __future__ import annotations


def __getattr__(name: str):
    import importlib

    if name in (
        "leveled", "placement", "wavefront", "stealing", "amm",
        "rebalance", "ici", "ring_attention", "ulysses", "flash",
    ):
        return importlib.import_module(f"distributed_tpu.ops.{name}")
    raise AttributeError(f"module 'distributed_tpu.ops' has no attribute {name!r}")
