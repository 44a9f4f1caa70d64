"""The reader of ``sgns.table_rows`` on hand-made spans."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip.layer import LayerContext

READER = (Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
          / "layer_metrics" / "sgns.table_rows.py")


def read(spans, kind="refresh"):
    spec = importlib.util.spec_from_file_location("sgns_table_rows", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(LayerContext(kind=kind, window=(0.0, 10.0), spans=spans))


def refresh(t, **train):
    return [{"name": "serve.retrain", "ts": t, "dur": 2.0, "attrs": {}},
            {"name": "retrain.train", "ts": t + 0.5, "dur": 1.0,
             "attrs": dict(steps=100, pairs=409600, **train)}]


def test_mean_rows_of_the_windows_refreshes():
    spans = (refresh(1.0, rows=1536)
             + refresh(4.0, rows=2048))
    assert read(spans) == pytest.approx(1792.0)


@pytest.mark.parametrize("spans, kind", [
    (refresh(1.0), "refresh"),  # a program whose spans carry no rows
    ([], "refresh"),  # no refresh in the window
    (refresh(1.0, rows=1536), "ingest"),  # another cell kind
], ids=["no-rows", "no-refresh", "ingest"])
def test_nothing_to_read(spans, kind):
    assert read(spans, kind) is None
