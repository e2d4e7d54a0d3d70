"""Affinity kernel calls (the harness's wrapper of
``kernels.affinity.ops.affinity_batch``) per batched auction
(``batched_calls``): the rounds of the ``core.jax_cycles.multi_cycle``
fixed point, over the window."""


def read(record):
    calls = record["dispatch"]["batched_calls"]
    return record["kernel_calls"] / calls if calls else None
