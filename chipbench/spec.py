"""Finds every piece of a cell by the names ``BENCHMARK.json`` gives.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration is the JSON file its ``configs`` entry names; the
traffic mix is ``traffic/<traffic>.json``; the limits of the comparison
that decides ``correct`` are ``limits/<cell>.json``; each per-layer metric
is read by ``metrics/<metric>.py`` (or its base's, see
:func:`metric_path`).  Nothing here knows a cell by name.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

PKG = pathlib.Path(__file__).resolve().parent
ROOT = PKG.parent


class SpecError(RuntimeError):
    pass


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def traffic_path(name: str) -> pathlib.Path:
    return PKG / "traffic" / f"{name}.json"


def limits_path(workload: str) -> pathlib.Path:
    return PKG / "limits" / f"{workload}.json"


def metric_path(name: str) -> pathlib.Path:
    """``metrics/<name>.py``.  A metric split by the end-to-end metric it
    moves (``<base>.<split>``, as ``idle_share.tput``) that has no reader
    of its own is read by its base's."""
    path = PKG / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        base = PKG / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
        if base.is_file():
            return base
    return path


def reference_path(name: str) -> pathlib.Path:
    return PKG / "references" / f"{name}.py"


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric names hold dots, so they are not
    module names)."""
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str, bench: dict | None = None) -> dict:
    """Everything one run of ``workload`` needs, read from its files."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; known: "
                        f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _read_json(ROOT / centry["file"])
    if mesh_size(config) != int(w["chips"]):
        raise SpecError(f"{workload}: the configuration's mesh shares each "
                        f"layer over {mesh_size(config)} chips, but the "
                        f"cell asks for {w['chips']}")
    traffic = _read_json(traffic_path(w["traffic"]))
    limits = _read_json(limits_path(workload))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return {"workload": w, "config_entry": centry, "config": config,
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": per_layer}


def mesh_size(config: dict) -> int:
    """Chips that share every layer: the configuration's ``mesh`` gives
    the size of its tensor-parallel ``model`` axis (1 when absent)."""
    return int(config.get("mesh", {}).get("model", 1))


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Starts from the registry entry ``config["arch"]`` and sets every size
    from the published keys, so the file is what runs; fails where the
    registry entry has a mechanism flag the file does not state."""
    from repro.configs import get_config
    hf = config["hf_config"]
    cfg = get_config(config["arch"]).replace(
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        d_ff=hf["intermediate_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim",
                        hf["hidden_size"] // hf["num_attention_heads"]),
        vocab_size=hf["vocab_size"], norm_eps=hf["rms_norm_eps"],
        rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        policy=config["numerics"]["policy"])
    want = {"qk_norm": hf["model_type"] == "qwen3",
            "qkv_bias": hf["model_type"] == "qwen2"
            or bool(hf.get("attention_bias", False))}
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise SpecError(f"{config['arch']}: registry {k}="
                            f"{getattr(cfg, k)} but the file implies {v}")
    return cfg
