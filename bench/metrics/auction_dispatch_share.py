"""Share of the window's timed seconds in the kernel calls of
``core.jax_cycles.multi_cycle``: ``kernels.affinity.ops.affinity_batch``
up to its return, which holds the host-to-device copy of the nine arrays
and the launch, the ``repro.auction.dispatch`` phase of the engines'
profile blocks (``profile=True`` in the traced run)."""


def read(record):
    p = record["profile"]
    if "auction.dispatch_s" not in p or not record["timed_s"]:
        return None
    return 100.0 * p["auction.dispatch_s"] / record["timed_s"]
