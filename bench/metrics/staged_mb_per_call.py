"""Megabytes (1e6 bytes) of the nine host arrays handed to
``kernels.affinity.ops.affinity_batch`` per kernel call, over the window
(``staged_bytes`` / ``kernel_calls``, the kernel counters of
``core.jax_cycles.multi_cycle``, carried in the profile block in the
traced run)."""


def read(record):
    p = record["profile"]
    if not p.get("kernel_calls"):
        return None
    return p["staged_bytes"] / p["kernel_calls"] / 1e6
