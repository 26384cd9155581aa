"""Finds the benchmark's pieces by name.

Every cell, configuration, traffic mix, step mode, metric reader, work
function and reference is a file of its own under this directory:

    workloads/<cell>.json      configs/<config>.json   traffic/<mix>.json
    modes/<mode>.py            metrics/<metric>.py     flops/<name>.py
    references/<name>.py

A later cell or metric is added by adding its file; nothing here names
one.  ``root`` lets a test point the lookups at a copy of the directory.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))


def _path(kind: str, name: str, ext: str, root: Optional[str]) -> str:
    path = os.path.join(root or ROOT, kind, name + ext)
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} named {name!r} (looked for {path})")
    return path


def load_json(kind: str, name: str, root: Optional[str] = None) -> dict:
    with open(_path(kind, name, ".json", root), encoding="utf-8") as f:
        return json.load(f)


def names(kind: str, ext: str, root: Optional[str] = None) -> List[str]:
    """Every name of ``kind`` that has a file, sorted."""
    d = os.path.join(root or ROOT, kind)
    return sorted(f[: -len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and not f.startswith("_"))


def load_module(kind: str, name: str, root: Optional[str] = None):
    """The module in ``<kind>/<name>.py``; names may hold dots, so the
    file is loaded by its path, not by an import of its name."""
    path = _path(kind, name, ".py", root)
    key = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, root: Optional[str] = None) -> Dict:
    """A cell with its configuration and traffic mix resolved:
    ``{"name", "workload", "config", "traffic"}``."""
    w = load_json("workloads", name, root)
    return {"name": name, "workload": w,
            "config": load_json("configs", w["config"], root),
            "traffic": load_json("traffic", w["traffic"], root)}


def local_model(config: Dict) -> Dict:
    """The model as one chip runs it: the published sizes in ``model``,
    with each key that ``tensor_parallel_share`` names divided by its tp
    degree (the Megatron share of a tp rank)."""
    m = dict(config["model"])
    share = config.get("tensor_parallel_share")
    if share:
        tp = share["tp"]
        for k in share["divides"]:
            if m[k] % tp:
                raise ValueError(f"{k}={m[k]} is not divisible by tp={tp}")
            m[k] //= tp
    return m


def flops(model: Dict, root: Optional[str] = None):
    """The model FLOP count of ``model``'s family: ``flops/<family>.py``
    where there is one, else ``flops/model.py``."""
    family = model["family"]
    return load_module("flops", family if family in names(
        "flops", ".py", root) else "model", root)


def peaks(device_kind: str, root: Optional[str] = None) -> Dict:
    """Published peaks of one chip of ``device_kind``.  A kind that is
    not in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(root or ROOT, "peaks.json"),
              encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
