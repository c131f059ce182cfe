"""Test configuration: force an 8-device virtual CPU platform so multi-chip
sharding tests run without TPU hardware (the reference's minikube-based
multi-node strategy, SURVEY.md §4, mapped to JAX's host-platform device
simulation).

The environment variables cover the subprocesses tests spawn; this
process is configured through jax.config before any backend is initialised.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses we spawn
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture
def v5e_peaks(monkeypatch):
    """The CPU backend's device kind has no row in utils/chips.py, so
    /perf carries null MFU/roofline fields here.  Tests of the roofline
    ARITHMETIC opt into a chip that has peaks."""
    from seldon_core_tpu.utils.perf import PerfObservatory

    peaks = {
        "device_kind": "TPU v5 lite", "platform": "tpu",
        "peak_bf16_tflops": 197.0, "peak_hbm_gbs": 819.0,
    }
    monkeypatch.setattr(PerfObservatory, "peaks", lambda self: peaks)


class SpanLog:
    """What ``jax.profiler.TraceAnnotation`` was opened with while the
    recorder stood in its place: ``log`` holds ``(thread id, "B" | "E",
    name, arguments)`` in the order the scheduler opened and closed them."""

    #: what a dispatching span says of the program it launches
    DECODE_WORK = {"seq", "rows", "real_rows", "nblk", "kv_positions",
                   "inplace", "passes", "blocks", "expert_slots"}
    PREFILL_WORK = {"seq", "rows", "real_rows", "nblk", "tokens",
                    "kv_positions", "attended", "expert_slots",
                    "carried_rows"}
    FUNCTIONS = {"decode": "GenServer._decode_round",
                 "prefill": "GenServer._prefill_tick"}

    def __init__(self):
        self.log = []

    def opened(self):
        """``(name, arguments, the span opened before it under the same
        parent or None)`` of every span, in opening order."""
        out, stack = [], [[None]]
        for _, kind, name, args in self.log:
            if kind == "B":
                out.append((name, args, stack[-1][-1]))
                stack[-1].append(name)
                stack.append([None])
            else:
                stack.pop()
        return out

    def dispatches(self, kind):
        """The arguments of every span that wraps the ``jit`` call of
        ``kind``'s program: ``/device`` where fenced, else the ``/build``
        that carries arguments.  Asserts on the way that each says all of
        its work, that the span before it is the argument-less ``/build``
        of the same function, and that no other ``/build`` says anything."""
        fn = self.FUNCTIONS[kind]
        want = self.DECODE_WORK if kind == "decode" else self.PREFILL_WORK
        found = []
        for name, args, before in self.opened():
            if name == fn + "/device" or (name == fn + "/build" and args):
                assert set(args) == want, (name, args)
                assert before == fn + "/build", (name, before)
                found.append(args)
        return found

    def carrying(self, suffix, kind):
        """The arguments of every ``<function>/<suffix>`` span."""
        return [args for _, k, name, args in self.log
                if k == "B" and name == self.FUNCTIONS[kind] + suffix]


@pytest.fixture
def recorded_spans(monkeypatch):
    """``jax.profiler.TraceAnnotation`` swapped for a recorder (no profiler
    session): the scheduler's spans and their arguments, as a ``SpanLog``."""
    import threading

    spans = SpanLog()

    class Recorder:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            spans.log.append(
                (threading.get_ident(), "B", self.name, self.args))
            return self

        def __exit__(self, *exc):
            spans.log.append(
                (threading.get_ident(), "E", self.name, self.args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return spans


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]


def pytest_collection_modifyitems(config, items):
    """Deterministic suite sharding for budgeted runs.

    The full suite compiles hundreds of XLA programs and can exceed a
    single CI/driver time slice on a 1-core box; ``TEST_SHARD=i/n`` (e.g.
    ``TEST_SHARD=1/3``) keeps only the i-th (1-based) of n hash-stable
    buckets of test FILES, so ``n`` consecutive budgeted runs cover the
    whole suite exactly once (ci/pipeline.yml runs the three shards as
    separate stages)."""
    shard = os.environ.get("TEST_SHARD", "").strip()
    if not shard:
        return
    import zlib

    idx, _, total = shard.partition("/")
    i, n = int(idx), int(total)
    if not (1 <= i <= n):
        raise pytest.UsageError(f"TEST_SHARD={shard!r}: need 1<=i<=n")
    keep, dropped = [], []
    for item in items:
        bucket = zlib.crc32(os.path.basename(str(item.fspath)).encode()) % n
        if bucket == i - 1:
            keep.append(item)
        else:
            dropped.append(item)
    items[:] = keep
    config.hook.pytest_deselected(items=dropped)  # 'N deselected' summary
    print(f"[TEST_SHARD {shard}] running {len(keep)} tests, "
          f"{len(dropped)} in other shards")


@pytest.fixture(autouse=True)
def _reset_learned_singletons():
    """Isolate the process-global LEARNED/STAGED singletons per test.

    The autopilot's per-key latency table and the brownout ladder's
    stage are process-global and change *decisions* (flush sizing,
    admission sheds, branch demotion, tier sheds) — state trained by one
    test must not steer a later one.  The concrete flake this fixes:
    ``test_chaos.py::test_hog_tenant_cannot_starve_victim`` left the
    AUTOPILOT trained on its throttled-engine latencies, and
    ``test_traffic_lifecycle.py::test_shadow_mirrors_and_diffs_live_-
    traffic`` then co-batched drained shadow mirrors differently enough
    to flip a near-0.5 argmax and score a spurious disagreement.

    The spine drains FIRST so a previous test's pending dispatch
    records fold into the OLD table, not the freshly-reset one.  The
    observation-only observatories (RECORDER / OBSERVATORY / QUALITY /
    TRACER / SPINE reservoirs) are left alone: they accumulate but do
    not decide, and tests that assert on them reset them explicitly —
    an autouse reset there would mask what those tests pin.
    """
    from seldon_core_tpu.runtime.autopilot import AUTOPILOT
    from seldon_core_tpu.runtime.brownout import BROWNOUT
    from seldon_core_tpu.utils.hotrecord import SPINE
    from seldon_core_tpu.utils.costledger import LEDGER
    from seldon_core_tpu.utils.quality import FLEET_BURN

    SPINE.drain()
    AUTOPILOT.reset()
    BROWNOUT.reset()
    # the fleet-truth burn view steers the brownout ladder and rollout
    # gates (utils/quality.py effective_burn_rate) — same decides-not-
    # observes rule as the two above
    FLEET_BURN.clear()
    # the cost ledger steers WFQ grant order when
    # SELDON_TPU_QOS_USAGE_WEIGHTED=1 (usage_advance scales virtual
    # finish tags) — one test's attributed spend must not reorder a
    # later test's admissions
    LEDGER.reset()
    yield
