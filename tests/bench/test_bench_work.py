"""The yardstick's fixed parts: algorithmic work from shapes, the peaks
table, and the shape of ``BENCHMARK.json`` against the files it names."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from bench import harness
from bench.work import hdiff, vadv

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_hdiff_work_at_a_small_domain():
    assert hdiff.ops((4, 5, 3)) == 23 * 60
    # in_phi read over (4+6) x (5+6) x 3, out_phi written over 4 x 5 x 3, float32
    assert hdiff.bytes_moved((4, 5, 3), 4) == 4 * (10 * 11 * 3 + 60)


def test_vadv_work_at_a_small_domain():
    assert vadv.ops((4, 5, 3)) == 8 * 60
    assert vadv.bytes_moved((4, 5, 3), 4) == 4 * 5 * 60


def test_step_share_of_a_known_step():
    from bench import roofline

    dom = (4, 5, 3)
    cell = harness.Cell(name="x", chips=1, config={}, traffic={}, end_to_end=[], per_layer=[])
    rec = harness.Record(cell=cell, window_s=2.0, device_kind="TPU v5 lite")
    rec.counters.update(steps=4, itemsize=4, stencils={"hdiff": dom, "vadv": dom}, calls={"hdiff": 11, "vadv": 11})
    step_bytes = 11 * (hdiff.bytes_moved(dom, 4) + vadv.bytes_moved(dom, 4))
    assert roofline.step_share(rec) == pytest.approx(100 * step_bytes / 819e9 / 0.5)


def test_peaks_of_a_v5e_and_refusal_of_an_unknown_device():
    p = harness.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("TPU v99")


def test_every_cell_metric_and_configuration_resolves_to_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for section, keys in KEYS.items():
        for entry in bm[section]:
            assert set(entry) - {"workloads"} == keys, entry
            for text in (entry.get("why"), entry.get("layer"), entry.get("source")):
                assert text is None or (0 < len(text) <= 200 and "\n" not in text and "\t" not in text)
            if "unit" in entry:
                assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    assert all(0.01 <= m["bound"] <= 0.25 for m in bm["end_to_end"])
    for path in bm["paths"]:
        assert (ROOT / path).is_dir()
    for c in bm["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for name in names:
        assert NAME.match(name) and (ROOT / "bench" / "metrics" / f"{name}.py").is_file()
    e2e = {m["name"] for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"], bm)
        assert (ROOT / "bench" / "drivers" / f"{cell.driver}.py").is_file()
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2 and cell.per_layer
        assert all(m["moves"] in reported and m["moves"] in e2e for m in cell.per_layer)
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(1, len(bm["workloads"]) // 2)
