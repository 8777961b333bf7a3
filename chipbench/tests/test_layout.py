"""Every piece BENCHMARK.json names is found by name, and the file keeps
to the benchmark's format."""
import json
import pathlib
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()
    for word in BENCH["command"][1:]:
        assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_names_units_and_one_line_fields():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")


def test_configs_found_and_used():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        config = json.loads((spec.ROOT / c["file"]).read_text())
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert spec.reference_path(config["reference"]).is_file()
        spec.model_config(config)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_pieces_found(workload):
    cell = spec.cell(workload)
    assert spec.traffic_path(cell["workload"]["traffic"]).is_file()
    assert set(cell["limits"]["limits"]) == {"token_gap", "kv_rel_err",
                                             "errored"}
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        mod = spec.load_module(spec.metric_path(m["name"]), m["name"])
        assert callable(mod.read)
        assert m["moves"] in names


#: readers whose metric no cell reports yet: ``collective_share`` waits
#: for the first cell whose layers are shared over several chips (on one
#: chip it would read 0)
AWAITING_A_CELL = {"collective_share"}


def test_every_metric_file_is_named():
    read_by = {spec.metric_path(m["name"]).name[:-3]
               for m in BENCH["per_layer"]}
    files = {p.name[:-3] for p in (spec.PKG / "metrics").glob("*.py")}
    assert read_by <= files
    assert files - read_by <= AWAITING_A_CELL


def test_a_mesh_must_match_the_chips(tmp_path):
    """A configuration that shares its layers over four chips cannot run
    in a cell that asks for one, nor one chip's configuration in a cell
    of four."""
    for chips, mesh in ((1, 4), (4, 1)):
        bench = json.loads(json.dumps(BENCH))
        w = bench["workloads"][0]
        w["chips"] = chips
        c = next(c for c in bench["configs"] if c["name"] == w["config"])
        config = json.loads((spec.ROOT / c["file"]).read_text())
        config["mesh"] = {"model": mesh}
        c["file"] = str(tmp_path / "config.json")
        pathlib.Path(c["file"]).write_text(json.dumps(config))
        with pytest.raises(spec.SpecError, match="mesh"):
            spec.cell(w["name"], bench)
    assert spec.mesh_size(spec.cell(CELLS[0])["config"]) == 1


def test_run_without_a_chip_prints_no_result():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.PKG / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode not in (0, None)
    assert p.stdout == ""
