"""Find a cell's files by the names in ``BENCHMARK.json``. No JAX here."""

import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``, group by group."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], value)
        else:
            out[key] = value
    return out


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise CellError(f"no BENCHMARK.json in {root}")
    return _load(path)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: str = ROOT, rehearsal: bool = False) -> dict:
    """Everything a run of cell ``name`` needs, as one JSON-able dict.

    ``rehearsal`` lays each file's ``rehearsal`` group over it: toy widths
    and a toy job for the CPU, the same control flow.
    """
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise CellError(f"{name}: no config {cell['config']!r}")
    entry = configs[cell["config"]]
    config = _load(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, os.path.basename(HERE))
    job_path = os.path.join(bench_dir, "jobs", cell["traffic"] + ".json")
    if not os.path.isfile(job_path):
        raise CellError(f"{name}: no job file {job_path}")
    job = _load(job_path)
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        job = _merge(job, job.get("rehearsal", {}))
    config.pop("rehearsal", None)
    job.pop("rehearsal", None)
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in bench["per_layer"]
        if _applies(m, name) and m["moves"] in moved
    ]
    return {
        "name": name, "chips": cell["chips"], "config_name": cell["config"],
        "traffic": cell["traffic"], "family": config["family"],
        "config": config, "job": job, "rehearsal": rehearsal,
        "toy": rehearsal,
        "end_to_end": copy.deepcopy(end_to_end),
        "per_layer": copy.deepcopy(per_layer),
        "bench_dir": bench_dir,
    }


def family_module(kind: str, family: str, bench_dir: str = HERE):
    """``models/<family>.py`` or ``reference/<family>.py``, by file."""
    return load_module(os.path.join(bench_dir, kind, family + ".py"))


def load_module(path: str):
    import importlib.util

    if not os.path.isfile(path):
        raise CellError(f"no file {path}")
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
