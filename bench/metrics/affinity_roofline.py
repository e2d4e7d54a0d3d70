"""The Pallas affinity kernel's share of its roofline in the traced
episode: the least time its calls could take (``bench.kernel_cost`` on the
shapes each call was handed, peaks from ``peaks.json``) over the summed
device time of its ``affinity_argmin`` events."""


def read(record):
    tr = record["trace"]
    if not tr or not tr["kernel_s"] or "least_kernel_s" not in record:
        return None
    if tr["kernel_calls"] != record["traced_kernel_calls"]:
        return None  # trace events and recorded shapes do not pair up
    return 100.0 * record["least_kernel_s"] / tr["kernel_s"]
