"""``BENCHMARK.json`` loads, every file it names is there, and the
loader refuses names, units and keys outside the contract."""
import copy
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, drivers, manifest, programs  # noqa: E402


@pytest.fixture(scope="module")
def doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_loads_and_every_cell_resolves():
    man = manifest.load()
    assert man.cells
    for cell in man.cells.values():
        manifest.load_config(cell.config)
        t = manifest.load_traffic(cell.traffic)
        assert drivers.load(t["driver"]).program_names(t)
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2
        assert cell.per_layer


def test_every_cell_program_has_a_spec():
    man = manifest.load()
    for cell in man.cells.values():
        t = manifest.load_traffic(cell.traffic)
        for name in drivers.load(t["driver"]).program_names(t):
            programs.parse(name)
            spec = check.load_spec(manifest.BENCH, cell.config, name)
            assert spec["hazard_violations"] == 0


def test_config_files_match_the_manifest(doc):
    for c in doc["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and path.parts[-3] == "bench"
        cfg = json.loads(path.read_text())
        assert cfg["reduced"] == c["reduced"]


def test_unknown_cell_is_refused():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load().cell("no_such_cell")


@pytest.mark.parametrize("edit,match", [
    (lambda d: d["end_to_end"][0].update(name="bad name"), "valid name"),
    (lambda d: d["end_to_end"][0].update(name="a/b"), "valid name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"),
     "unit"),
    (lambda d: d["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda d: d["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda d: d["end_to_end"][0].update(better="more"), "better"),
    (lambda d: d["per_layer"][0].update(source="guess"), "source"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["workloads"][0].update(config="nope"), "unknown config"),
    (lambda d: d.update(extra=1), "keys"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0])),
     "two cells"),
])
def test_contract_breaches_are_refused(doc, edit, match):
    bad = copy.deepcopy(doc)
    edit(bad)
    with pytest.raises(manifest.ManifestError, match=match):
        manifest.Manifest(bad)


def test_metric_reader_is_found_by_name():
    with pytest.raises(manifest.ManifestError, match="no reader"):
        manifest.metric_reader("no_such_metric")


@pytest.mark.parametrize("name", ["no_such_driver", "../run", "Drain", ""])
def test_traffic_driver_is_found_by_name(name):
    assert drivers.load("drain").run and drivers.load("serve").run
    with pytest.raises(manifest.ManifestError, match="no traffic driver"):
        drivers.load(name)
