"""Milliseconds of the ``repro.auction.pull`` phase per kernel round of
``core.jax_cycles.multi_cycle``, over the window (``auction.pull_s`` /
``kernel_calls`` of the engines' profile blocks, ``profile=True`` in the
traced run): what it costs the host to bring one round's outputs back
from the device."""


def read(record):
    p = record["profile"]
    if "auction.pull_s" not in p or not p.get("kernel_calls"):
        return None
    return 1000.0 * p["auction.pull_s"] / p["kernel_calls"]
