"""The roofline of a call: the least time the chip could take for its
operations and bytes.

Each architecture family counts the FLOPs and bytes of every call of a PPO
iteration in its own ``calls`` (``chipbench/archs``); this is shared.
"""

from __future__ import annotations


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two bounds it."""
    t_f = cost["flops"] / peak["flops_per_s"]
    t_b = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
