import json
import shutil

import pytest

from harness.manifest import BENCH_DIR, ROOT, Manifest, ManifestError


def doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "-lead",
                                 "x" * 65, "τ"])
def test_refuses_bad_names(bad):
    d = doc()
    d["workloads"][0]["name"] = bad
    with pytest.raises(ManifestError):
        Manifest(d)


@pytest.mark.parametrize("bad", ["tokens per second", "", "µs",
                                 "x" * 17])
def test_refuses_bad_units(bad):
    d = doc()
    d["end_to_end"][0]["unit"] = bad
    with pytest.raises(ManifestError):
        Manifest(d)


def test_every_declared_cell_loads():
    m = Manifest.load()
    for name in m.workloads:
        cell = m.cell(name)
        assert any(x.name == "setup_s" for x in cell.end_to_end)
        assert cell.per_layer and set(cell.readers) == {
            x.name for x in cell.per_layer}


def test_new_files_are_found_by_name_alone(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", ".traces", "__pycache__"))
    cfg = json.loads((bench / "configs" / "dsv3-rome-x9.json").read_text())
    cfg["name"] = "new-config"
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "decode-poisson.json").read_text())
    tr["arrivals"]["rate_rps"] = 5e5
    (bench / "traffic" / "new-traffic.json").write_text(json.dumps(tr))
    (bench / "metrics" / "new_metric.py").write_text(
        'LAYER = "device"\nMOVES = "sim_steps_per_s"\n\n\n'
        'def read(ctx):\n    return None\n')
    d = doc()
    d["configs"].append(dict(d["configs"][1], name="new-config",
                             file="bench/configs/new-config.json"))
    d["workloads"].append({"name": "new-config.new-traffic",
                           "config": "new-config", "traffic": "new-traffic",
                           "chips": 1, "why": "test"})
    d["per_layer"].append({"name": "new_metric", "unit": "1",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "sim_steps_per_s",
                           "workloads": ["new-config.new-traffic"]})
    cell = Manifest(d, bench).cell("new-config.new-traffic")
    assert cell.config["name"] == "new-config"
    assert cell.traffic["arrivals"]["rate_rps"] == 5e5
    assert "new_metric" in cell.readers


def test_reader_must_agree_with_manifest():
    d = doc()
    for m in d["per_layer"]:
        if m["name"] == "census_ms_per_step":
            m["layer"] = "cycle engine"
    with pytest.raises(ManifestError):
        Manifest(d).cell("dsv3-rome-x9.decode-poisson")
