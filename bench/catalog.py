"""Finds the benchmark's parts by name: a cell in ``workloads/<name>.json``,
a configuration in ``configs/<name>.json``, the reference and FLOP count of
its layout's kind in ``models/<kind>.py``, a traffic mix in
``traffic/<name>.json`` and its generator in ``traffic/<generator>.py``, a
per-layer metric's reader in ``metrics/<name>.py``, and the chip peaks in
``peaks.json``. Adding any of them is adding a file; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re
from types import ModuleType
from typing import Dict

ROOT = pathlib.Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def _module(path: pathlib.Path) -> ModuleType:
    """Import one file by its path (names may hold dots and dashes)."""
    mod_name = "bench_part_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell file, with its configuration and traffic mix resolved under
    ``"config_spec"`` and ``"traffic_spec"``."""
    cell = _json(root / "workloads" / f"{_checked(name)}.json")
    cell["name"] = name
    cell["config_spec"] = load_config(cell["config"], root)
    cell["traffic_spec"] = load_traffic(cell["traffic"], root)
    return cell


def load_config(name: str, root: pathlib.Path = ROOT) -> dict:
    return _json(root / "configs" / f"{_checked(name)}.json")


def load_traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    return _json(root / "traffic" / f"{_checked(name)}.json")


def traffic_generator(mix: dict, root: pathlib.Path = ROOT) -> ModuleType:
    return _module(root / "traffic" / f"{_checked(mix['generator'])}.py")


def model(kind: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The model of one layout kind: the functions ``models/__init__.py``
    names. A kind with no file of its own is an error, never a default."""
    path = root / "models" / f"{_checked(kind)}.py"
    if kind.startswith("_") or not path.is_file():
        raise LookupError(f"no model kind {kind!r}: {path} is not a kind's "
                          "file")
    return _kind_module(path)


@functools.lru_cache(maxsize=None)
def _kind_module(path: pathlib.Path) -> ModuleType:
    """A kind's file, imported once per path: the reference and the FLOP
    count ask for it on every call."""
    return _module(path)


def metric_readers(root: pathlib.Path = ROOT) -> Dict[str, ModuleType]:
    """Every per-layer metric reader, by metric name (its file name)."""
    return {p.stem: _module(p)
            for p in sorted((root / "metrics").glob("*.py"))
            if not p.name.startswith("_")}


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of one chip of ``device_kind``. A kind that is not
    in the table is an error, never a default."""
    table = _json(root / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to bench/peaks.json with its source")
    return table[device_kind]
