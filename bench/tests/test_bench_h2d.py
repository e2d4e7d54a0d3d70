"""The reader of ``h2d_transfers_per_kernel_call``: host-to-device arrays
per kernel round, from the engines' profile blocks."""
from __future__ import annotations

import copy

import pytest

from bench import generator as gen
from bench import harness as H
from bench import spec

METRIC = "h2d_transfers_per_kernel_call"


def _record(profile):
    return {"timed_s": 2.0, "profile": profile, "trace": None,
            "window_compiles": 0, "kernel_calls": 0, "dispatch": {}}


def test_the_reader_divides_transfers_by_kernel_rounds():
    read = spec.metric_reader(METRIC)
    # Two auctions: 2 first rounds of nine arrays, 6 later rounds of one.
    assert read(_record({"kernel_calls": 8, "h2d_transfers": 24})) == \
        pytest.approx(3.0)
    # A program that does not count its transfers, or ran no kernel round.
    assert read(_record({"kernel_calls": 8})) is None
    assert read(_record({"kernel_calls": 0, "h2d_transfers": 0})) is None


def test_a_tiny_grid_sends_nine_arrays_per_auction_and_a_mask_after():
    cell = copy.deepcopy(spec.resolve("grid-montage"))
    cell.conf["workload"]["workflows_per_cell"] = 3
    cell.conf["workload"]["sizes"] = ["small"]
    cfg = gen.platform_config(cell.conf)
    streams = gen.streams(cfg, cell.conf["workload"], cell.traffic)
    kernel = H.KernelCalls()
    try:
        ep = H.run_episode(cfg, cell, streams,
                           H.member_plan(cell, streams, 2**31 + 5), kernel,
                           profile=True)
    finally:
        kernel.close()
    prof = ep.dispatch["profile"]
    calls = prof["kernel_calls"]
    assert calls == len(ep.kernel_shapes) > 0
    # Each auction with a kernel round sends nine arrays once, then one.
    uploads = (prof["h2d_transfers"] - calls) / 8
    assert uploads == int(uploads)
    assert 1 <= uploads <= min(calls, ep.dispatch["batched_calls"])
    value = spec.metric_reader(METRIC)(_record(prof))
    assert 1.0 <= value <= 9.0
