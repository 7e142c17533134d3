"""The harris cell resolves to its configuration, traffic and metrics, and
its two readers read what they say: the mean compiled band of the window's
``compile`` events, and harris's roofline as add's reader reads it."""

from __future__ import annotations

import pytest

from chipbench import peaks
from chipbench.harness import BENCH_DIR, RunView, load_cell, load_module
from chipbench.window import Sample

NEW = {"harris_roofline", "compile_body_kpixels"}


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def _compile(t, body_pixels=None):
    ev = {"ev": "stage", "t": t, "dur": 1.0, "stage": "compile", "src": "main",
          "key": f"(8, 128, {t})"}
    if body_pixels is not None:
        ev.update(body_pixels=body_pixels, pad_rows=0)
    return ev


def _view(events, **kw):
    best = Sample(t=12.0, src="main", key="k", config={}, value=4e-3, repeats=[4e-3],
                  stage=None)
    return RunView(start=10.0, deadline=20.0, samples=[best], events=events,
                   workers=["main"], best=best, bytes_moved=2 * 8192 * 8192 * 4,
                   peak=peaks.peak("TPU v5 lite"), **kw)


def test_harris_cell_loads_its_configuration_traffic_and_metrics():
    cell = load_cell("harris-8192.ga25")
    assert cell.chips == 1
    assert cell.config["kernel"] == "harris"
    assert (cell.config["x"], cell.config["y"]) == (8192, 8192)
    assert cell.traffic["name"] == "ga25-cold"
    assert NEW <= {m["name"] for m in cell.per_layer}


def test_add_cell_is_given_neither_harris_metric():
    names = {m["name"] for m in load_cell("add-8192.ga25").per_layer}
    assert "best_kernel_roofline" in names
    assert not NEW & names


def test_compile_body_kpixels_is_the_window_mean():
    events = [
        _compile(9.5, 999_999),             # ended before the window
        _compile(11.0, 98_352),             # 8-row band at 8192 wide
        _compile(13.0),                     # no counter: not counted
        {"ev": "stage", "t": 14.0, "dur": 0.1, "stage": "time", "body_pixels": 1},
        _compile(16.0, 622_896),            # 72-row band
        _compile(21.0, 999_999),            # ended after it
    ]
    assert reader("compile_body_kpixels")(_view(events)) == pytest.approx(
        (98_352 + 622_896) / 2 / 1e3)


def test_compile_body_kpixels_is_absent_without_the_counter():
    assert reader("compile_body_kpixels")(_view([_compile(11.0), _compile(16.0)])) is None
    assert reader("compile_body_kpixels")(_view([])) is None


@pytest.mark.parametrize("device_s", [None, 3.93e-3, 2.6e-3])
def test_harris_roofline_reads_as_best_kernel_roofline(device_s):
    view = _view([], best_device_s=device_s)
    assert reader("harris_roofline")(view) == reader("best_kernel_roofline")(view)
    if device_s is not None:
        assert reader("harris_roofline")(view) == pytest.approx(
            100 * 2 * 8192 * 8192 * 4 / 819e9 / device_s)
