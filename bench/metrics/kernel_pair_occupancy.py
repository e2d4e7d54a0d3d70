"""Share of the pairs handed to the affinity kernel that are real: each
kernel round's unplaced tasks x VMs of its active members over the
pairs of the resident ``[B, T, V]`` bucket it rode, summed over the
window (``real_pairs`` / ``kernel_pairs``, the kernel counters of
``core.jax_cycles.multi_cycle``, carried in the profile block in the
traced run)."""


def read(record):
    p = record["profile"]
    if not p.get("kernel_pairs"):
        return None
    return 100.0 * p["real_pairs"] / p["kernel_pairs"]
