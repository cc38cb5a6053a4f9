"""BENCHMARK.json, the data files it names and the modules they name,
found by name alone."""
import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
# the two places data is looked for; tests point them at a directory of
# their own to show that a cell is nothing but files
ROOT = os.path.dirname(HERE)      # holds BENCHMARK.json
DATA = HERE                       # holds configs/ traffic/ metrics/ limits/


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(w: dict) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == w["config"]:
            return load_json(ROOT, c["file"])
    raise KeyError(f"no config {w['config']!r} in BENCHMARK.json")


def metric_file(name: str) -> dict:
    return load_json(DATA, "metrics", f"{name}.json")


def peaks() -> dict:
    return load_json(DATA, "peaks.json")


def module_names(kind: str):
    """The modules ``benchmark/<kind>/*.py`` that a data file may name."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(HERE, kind))
                  if f.endswith(".py") and not f.startswith("_"))


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``: a driver, a reader, a family or a
    reference is a module that exists."""
    if name not in module_names(kind):
        raise ValueError(f"no benchmark/{kind}/{name}.py; there are: "
                         f"{', '.join(module_names(kind))}")
    return importlib.import_module(f"{__package__}.{kind}.{name}")


def family_of(cfg: dict):
    """The family module of a configuration (``benchmark/families``)."""
    if "family" not in cfg:
        raise KeyError("the configuration names no \"family\"; there are: "
                       f"{', '.join(module_names('families'))}")
    return module("families", cfg["family"])


def metrics_for(workload_name: str, kind: str):
    """Names of the ``end_to_end`` or ``per_layer`` metrics a cell reports:
    those that list it under ``workloads``, and those that list nothing
    (``setup_s``, or a per-layer metric owed by every cell that reports
    the end-to-end metric it moves)."""
    bench = benchmark()
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is None and kind == "per_layer":
            cells = [w["name"] for w in bench["workloads"]
                     if m["moves"] in metrics_for(w["name"], "end_to_end")]
        if cells is None or workload_name in cells:
            out.append(m["name"])
    return out
