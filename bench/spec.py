"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, metric or check
lives in a file of its own, named after it:

    bench/configs/<config>.json     model sizes as run, source, cuts; its
                                    ``arch`` names the architecture module
    bench/archs/<arch>.py           everything one architecture's layers
                                    need: the program's ModelConfig, the
                                    served leaves, the reference step, the
                                    work of a forward, the cache rows the
                                    check compares (``bench/archs/__init__``)
    bench/traffic/<traffic>.json    traffic parameters for bench.traffic
    bench/traffic_kinds/<kind>.py   how requests of a mix's ``kind`` go out
    bench/formats/<format>.py       a stored weight-code format, named by a
                                    configuration's ``serving.format``
    bench/metrics/<metric>.py       a reader: ``read(ctx) -> float | None``
    bench/checks/<workload>.json    what ``correct`` compares, and its limits

so a new cell, format, traffic kind or metric is added with files and a
``BENCHMARK.json`` entry, never by editing an existing file.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path


BENCH = Path(__file__).resolve().parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass
class Cell:
    root: Path                    # checkout root (holds BENCHMARK.json)
    name: str
    chips: int
    config: dict                  # contents of the configuration file
    traffic: dict                 # contents of the traffic file
    check: dict                   # contents of the check file
    end_to_end: list              # metric entries this cell reports
    per_layer: list


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def bench_dir(root: Path) -> Path:
    return Path(root) / "bench"


def load_module(bench: Path, folder: str, name: str):
    """The module ``<bench>/<folder>/<name>.py``.  File names carry dots,
    so modules are loaded by path."""
    path = Path(bench) / folder / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {folder} file {path}")
    tag = f"{folder}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"bench_{tag}", path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _arch_module(bench: str, name: str):
    return load_module(Path(bench), "archs", name)


def arch(cfg: dict):
    """The architecture module ``archs/<cfg["arch"]>.py`` of the benchmark
    tree the configuration was loaded from (``bench_dir``; default: this
    one).  There is no default architecture."""
    name = cfg.get("arch")
    if not isinstance(name, str) or not name:
        raise SpecError(f"configuration {cfg.get('name')!r} names no arch")
    return _arch_module(str(cfg.get("bench_dir", BENCH)), name)


def applies(metric: dict, workload: str) -> bool:
    """A metric without a ``workloads`` list is reported by every cell."""
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    root = Path(root)
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    centry = configs[w["config"]]
    config = _load_json(root / centry["file"])
    config.setdefault("name", centry["name"])
    bd = bench_dir(root)
    # where the configuration's architecture and weight format are found
    config["bench_dir"] = str(bd)
    arch(config)
    traffic = _load_json(bd / "traffic" / f"{w['traffic']}.json")
    load_module(bd, "traffic_kinds", traffic.get("kind", ""))
    load_module(bd, "formats", config["serving"]["format"])
    check = _load_json(bd / "checks" / f"{workload}.json")
    e2e = [m for m in spec.get("end_to_end", []) if applies(m, workload)]
    layer = [m for m in spec.get("per_layer", []) if applies(m, workload)]
    for m in e2e + layer:
        if not (bd / "metrics" / f"{m['name']}.py").is_file():
            raise SpecError(f"metric {m['name']!r} has no reader "
                            f"bench/metrics/{m['name']}.py")
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic,
                check=check, end_to_end=e2e, per_layer=layer)


def load_reader(root, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    mod = load_module(bench_dir(root), "metrics", name)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"bench/metrics/{name}.py defines no read(ctx)")
    return mod.read
