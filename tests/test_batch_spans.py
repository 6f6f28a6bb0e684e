"""The serving path's batch spans and the plan's step names.

* Every batch a ``Server`` runs leaves ``serve.batch.collect`` (scheduler),
  ``serve.batch.stage`` with its ``pad``, ``put`` and ``launch`` children
  and ``serve.batch.wait`` (the device's worker), and
  ``serve.batch.complete`` (completer) in the flight ring, with the
  batch's device, bucket and frames as integer fields.
* The per-batch events they replace are gone.
* The compiled executor carries each plan step's name in its ``op_name``
  metadata, and ``plan.executor.traces`` counts its traces.
"""

import re

import numpy as np
import pytest

import repro
from repro import obs, serve
from repro.core import plan as plan_mod
from repro.core.quant import MX_43, W4A4
from repro.serve import batcher

REFERENCE = repro.Options(backend="reference")
KINDS = ("collect", "stage", "pad", "put", "launch", "wait", "complete")


@pytest.fixture()
def ring():
    prev = obs.get_flight()
    recorder = obs.install(obs.FlightRecorder(capacity=4096, name="test"))
    try:
        yield recorder
    finally:
        if prev is not None:
            obs.install(prev)
        else:
            obs.uninstall()


@pytest.fixture(scope="module")
def program():
    return repro.Program.from_pipeline("edge_detect", 16, 16, 3)


def _serve(program, sizes, **cfg):
    server = serve.Server(serve.ServeConfig(**cfg))
    server.register("edge", program, REFERENCE)
    server.start(warm=True)
    rng = np.random.default_rng(3)
    futs = [server.submit("edge", rng.random((n, 16, 16, 3), np.float32))
            for n in sizes]
    for f in futs:
        f.result(timeout=60)
    server.stop()
    return server


def _batch_spans(dump):
    """name kind -> [(ring, t0_us, t1_us, args)] of the serve.batch.*
    spans, and the names of every record."""
    out = {k: [] for k in KINDS}
    names = set()
    for e in dump["traceEvents"]:
        if e["ph"] == "M":
            continue
        names.add(e["name"])
        if e["name"].startswith("serve.batch."):
            kind = e["name"][len("serve.batch."):]
            out[kind].append((e["args"]["ring"], e["ts"],
                              e["ts"] + e["dur"], e["args"]))
    return out, names


def test_every_batch_leaves_its_spans(ring, program):
    sizes = [1, 3, 2, 4, 1, 1, 2, 3, 4, 1]
    server = _serve(program, sizes, max_batch=4, max_wait_ms=2.0)
    batches = server.stats()["pool"]["per_device"][0]["batches"]
    spans, names = _batch_spans(ring.dump())
    # one of each per batch (one chunk each: no batch exceeds the bucket)
    for kind in KINDS:
        assert len(spans[kind]) == batches, kind
    for kind in KINDS:
        assert sum(a["frames"] for *_, a in spans[kind]) == sum(sizes)
        for *_, a in spans[kind]:
            assert a["device"] == 0
            assert a["bucket"] == batcher.pick_bucket(
                a["frames"], server._programs["edge"].buckets)
    # the worker's lane: stage and its children and the wait share one
    # ring, and each child lies inside a stage of the same batch
    worker = {r for r, *_ in spans["stage"]}
    assert len(worker) == 1
    for kind in ("pad", "put", "launch", "wait"):
        assert {r for r, *_ in spans[kind]} == worker
    for kind in ("pad", "put", "launch"):
        for _, t0, t1, a in spans[kind]:
            assert any(s0 <= t0 and t1 <= s1 + 1e-3
                       and sa["frames"] == a["frames"]
                       for _, s0, s1, sa in spans["stage"]), kind
    # pad -> put -> launch, in order, inside each stage
    for _, s0, s1, _ in spans["stage"]:
        inside = sorted((t0, kind) for kind in ("pad", "put", "launch")
                        for _, t0, t1, _ in spans[kind]
                        if s0 <= t0 and t1 <= s1 + 1e-3)
        assert [k for _, k in inside] == ["pad", "put", "launch"]
    # collect (scheduler) and complete (completer): lanes of their own
    assert {r for r, *_ in spans["collect"]}.isdisjoint(worker)
    assert {r for r, *_ in spans["complete"]}.isdisjoint(worker)
    # the per-batch events the spans replace are gone
    assert not names & {"batcher.pick_bucket", "batcher.split",
                        "serve.pool.place"}
    assert "serve.submit" in names


def test_a_chunked_batch_pads_puts_and_launches_each_chunk(ring, program):
    # one request of 6 frames over a largest bucket of 4: two chunks
    _serve(program, [6], max_batch=4, max_wait_ms=0.0,
           batch_buckets=(2, 4))
    spans, _ = _batch_spans(ring.dump())
    assert len(spans["stage"]) == 1
    assert [a["frames"] for *_, a in spans["pad"]] == [4, 2]
    assert [a["frames"] for *_, a in spans["launch"]] == [4, 2]
    assert spans["stage"][0][3]["frames"] == 6


def test_an_enabled_collector_gets_them_too(ring, program):
    """While a collector is enabled it gets the batch spans the ring gets,
    with the same fields, beside the request timelines; once it is
    disabled, only the ring does."""
    trace = obs.enable()
    try:
        _serve(program, [1, 2, 1], max_batch=4, max_wait_ms=2.0)
    finally:
        obs.disable()
    got = {s["name"] for s in trace.spans()}
    assert "serve.request.queue_wait" in got
    collected = sorted(
        (s["name"], s["t0_ns"], s["t1_ns"], tuple(sorted(s["attrs"].items())))
        for s in trace.spans() if s["name"].startswith("serve.batch."))
    spans, _ = _batch_spans(ring.dump())
    assert {n[len("serve.batch."):] for n, *_ in collected} == set(KINDS)
    assert len(collected) == sum(len(v) for v in spans.values())
    for name, t0, t1, attrs in collected:
        assert dict(attrs).keys() == {"device", "bucket", "frames"}
        assert t0 <= t1
    _serve(program, [1], max_batch=4, max_wait_ms=2.0)
    assert len([s for s in trace.spans()
                if s["name"].startswith("serve.batch.")]) == len(collected)


# ---------------------------------------------------------------------------
# Plan step names and executor traces
# ---------------------------------------------------------------------------

def _op_scopes(program, scheme):
    exe = program.compile(repro.Options(scheme=scheme))
    ops = re.findall(r'op_name="([^"]*)"', exe.compiled_text(2))
    return exe.plan, {p for o in ops for p in o.split("/")}


@pytest.mark.parametrize("name,scheme,fused", [
    ("lenet", W4A4, ["conv1+conv2"]), ("vgg9", MX_43, [])],
    ids=["lenet", "vgg9-ca"])
def test_compiled_executor_names_every_plan_step(name, scheme, fused):
    plan, scopes = _op_scopes(repro.Program.from_model(name), scheme)
    assert [plan_mod.segment_name(s) for s in plan.fused_segments] == fused
    in_segment = {n for s in plan.fused_segments for n in s.names}
    want = {plan_mod.step_name(s) for s in plan.steps
            if getattr(s, "name", None) not in in_segment}
    want |= set(fused) | {"input"}
    assert want <= scopes, want - scopes
    if name == "vgg9":
        assert "ca" in scopes


def test_executor_traces_are_counted(program):
    exe = program.compile(REFERENCE)
    counter = obs.counter("plan.executor.traces")
    before = counter.get()
    frames = np.zeros((5, 16, 16, 3), np.float32)   # a shape new here
    np.asarray(exe.run_per_frame(frames))
    traced = counter.get() - before
    np.asarray(exe.run_per_frame(frames))             # cached: no trace
    assert traced >= 1 and counter.get() - before == traced
