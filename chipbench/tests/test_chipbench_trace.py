"""The trace reduction on a trace recorded on the chip: it gives the
numbers that the chip run printed from the same trace.

``data/trace_short.xplane.pb.gz`` is the profile of a traced 8-s run of
``bids-max.saturate`` on one TPU v5e, 0.25 s of it traced, less what the
reduction never reads, gzipped; ``data/trace_short.result.json`` is that
run's result line.  ``record_trace.py`` makes both."""

from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(REPO))

from chipbench import run, trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "trace_short.xplane.pb.gz").read_bytes()))
    return trace_reduce.reduce_file(str(path))


@pytest.fixture(scope="module")
def printed():
    return json.loads((DATA / "trace_short.result.json").read_text())


@pytest.mark.parametrize("metric", ["device_idle_share", "keyed_step_ms",
                                    "rollup_ms", "seg_scan_roofline"])
def test_trace_metrics_match_the_chip_run(reduced, printed, metric):
    spec = [{"name": metric, "unit": printed["metrics"][metric]["unit"]}]
    rec = {"trace": reduced, "device_kind": printed["device"]["kind"]}
    got = run.read_metrics(REPO, spec, rec)[metric]["value"]
    assert got == pytest.approx(printed["metrics"][metric]["value"],
                                rel=1e-9)


def test_busy_window_and_breakdown_match_the_chip_run(reduced, printed):
    assert reduced["busy_s"] == pytest.approx(printed["device"]["busy_s"],
                                              rel=1e-9)
    assert reduced["window_s"] == pytest.approx(
        printed["device"]["window_s"], rel=1e-9)
    assert json.loads(json.dumps(reduced["breakdown"])) == \
        printed["breakdown"]
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
