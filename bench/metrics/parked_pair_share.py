"""Share of the window's pairs that the dispatcher
(``core.jax_engine.BatchSimEngine._round_rides_kernel``) kept off the
kernel: the summed queue x pool pairs of the rendezvous rounds under
``AUCTION_MIN_PAIRS_ROUND`` over those of every round with pairs
(``parked_pairs`` / (``parked_pairs`` + ``ridden_pairs``), round counters
carried in the profile block in the traced run)."""


def read(record):
    p = record["profile"]
    total = p.get("parked_pairs", 0) + p.get("ridden_pairs", 0)
    if not total:
        return None
    return 100.0 * p["parked_pairs"] / total
