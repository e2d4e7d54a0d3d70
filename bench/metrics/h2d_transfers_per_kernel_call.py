"""Host-to-device arrays sent per kernel round of
``core.jax_cycles.multi_cycle``, over the window (``h2d_transfers`` /
``kernel_calls`` of the engines' profile blocks, ``profile=True`` in the
traced run): nine on an auction's first round, when its pair arrays go to
the device, and one live mask on each later round.  A program that does
not count its transfers has nothing to read."""


def read(record):
    p = record["profile"]
    if "h2d_transfers" not in p or not p.get("kernel_calls"):
        return None
    return p["h2d_transfers"] / p["kernel_calls"]
