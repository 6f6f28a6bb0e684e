"""The readers of the program's host spans: ``stage_ms`` is the mean
``serve.batch.stage`` span that the program's collector held over the
run's spans part, and nothing where the program records no such span."""

import pytest

import cells
from conftest import ROOT

STAGE_READERS = ["stage_ms.offline", "stage_ms.stream"]


def _span(name, t0_ns, t1_ns, **attrs):
    return {"name": name, "ph": "X", "t0_ns": t0_ns, "t1_ns": t1_ns,
            "attrs": attrs}


SPANS = [
    _span("serve.request.queue_wait", 0, 1_000_000),
    _span("serve.batch.stage", 0, 2_000_000, device=0, bucket=8, frames=8),
    _span("serve.batch.launch", 1_000_000, 2_000_000, device=0, bucket=8,
          frames=8),
    _span("serve.batch.stage", 5_000_000, 8_500_000, device=0, bucket=8,
          frames=3),
    _span("serve.batch.wait", 8_500_000, 9_000_000, device=0, bucket=8,
          frames=3),
]


@pytest.mark.parametrize("name", STAGE_READERS)
def test_stage_readers(name):
    read = cells.metric_reader(ROOT, name)
    assert read({"spans": SPANS}) == pytest.approx(2.75)


@pytest.mark.parametrize("name", STAGE_READERS)
def test_a_program_without_batch_spans_gives_nothing(name):
    """A program from before the batch spans: its collector holds the
    request timelines only."""
    read = cells.metric_reader(ROOT, name)
    assert read({"spans": SPANS[:1]}) is None
    assert read({"spans": []}) is None


def test_batch_spans_from_a_served_run():
    """A CPU server's spans part, as a traced run collects it: the readers
    give the mean of the stage spans the flight ring recorded too."""
    import numpy as np
    import repro
    from repro import obs, serve
    prev = obs.get_flight()
    ring = obs.install(obs.FlightRecorder(capacity=1 << 12, name="test"))
    try:
        prog = repro.Program.from_pipeline("edge_detect", 16, 16, 3)
        server = serve.Server(serve.ServeConfig(max_batch=4,
                                                max_wait_ms=2.0))
        server.register("edge", prog, repro.Options(backend="reference"))
        server.start(warm=True)
        trace = obs.enable()
        try:
            rng = np.random.default_rng(1)
            futs = [server.submit("edge",
                                  rng.random((16, 16, 3), np.float32))
                    for _ in range(9)]
            for f in futs:
                f.result(timeout=60)
        finally:
            obs.disable()
        server.stop()
        dump = ring.dump()
    finally:
        obs.uninstall()
        if prev is not None:
            obs.install(prev)
    stages = [e["dur"] / 1e3 for e in dump["traceEvents"]
              if e["name"] == "serve.batch.stage"]
    spans = trace.spans()
    collected = [s for s in spans if s["name"] == "serve.batch.stage"]
    assert len(collected) == len(stages) > 0
    assert sum(s["attrs"]["frames"] for s in collected) == 9
    for name in STAGE_READERS:
        got = cells.metric_reader(ROOT, name)({"spans": spans})
        assert got == pytest.approx(sum(stages) / len(stages), rel=1e-3)
