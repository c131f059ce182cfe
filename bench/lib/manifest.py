"""Finding the benchmark's data files by the names BENCHMARK.json gives.

A root is a directory that holds ``BENCHMARK.json`` and ``bench/``; the
default is the checkout run.py lives in.  Tests point it at a temporary
copy to show that cells, configurations, architectures, mixes and layer
metrics are added by adding files."""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(Exception):
    """A name that resolves to no file, or a file that says something else
    than the manifest."""


def _load(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


_ARCH_MODULES: dict = {}     # file path -> module


def arch_module(bench: str, config: dict, module: str, optional=False):
    """``<bench>/archs/<config["arch"]>/<module>.py`` as a module: the
    plain reference (``reference``), the bytes and FLOPs a program needs
    (``needs``) or how a round is driven and what of it is judged
    (``drive``) of the block a configuration declares.  A configuration
    without the key, or a key without its file, is an error: there is no
    default block — but for a file an architecture may leave out
    (``optional``: ``drive``), where ``None`` comes back."""
    arch = config.get("arch")
    if not arch or not NAME_RE.match(str(arch)):
        raise ManifestError(
            f"config {config.get('name')!r} names no 'arch' "
            f"(a directory of {os.path.join(bench, 'archs')})")
    path = os.path.join(bench, "archs", arch, module + ".py")
    if not os.path.isfile(path):
        if optional:
            return None
        raise ManifestError(f"arch {arch!r}: no such file: {path}")
    if path not in _ARCH_MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{module}_{len(_ARCH_MODULES)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ARCH_MODULES[path] = mod
    return _ARCH_MODULES[path]


def reserved_ids(config: dict) -> tuple:
    """The ids a configuration keeps out of the traffic and out of every
    answer (``reserved_ids``: ``[{"id", "why"}, ...]``, a mask id, a pad);
    none where the key is absent."""
    return tuple(r["id"] for r in config.get("reserved_ids", ()))


class Manifest:
    def __init__(self, root: str = DEFAULT_ROOT):
        self.root = os.path.abspath(root)
        self.bench = os.path.join(self.root, "bench")
        self.doc = _load(os.path.join(self.root, "BENCHMARK.json"))

    def path(self, kind: str, name: str) -> str:
        return os.path.join(self.bench, kind, name + ".json")

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"BENCHMARK.json has no workload {name!r}")

    def cell(self, name: str) -> dict:
        """bench/cells/<name>.json, checked against the manifest entry."""
        entry = self.workload(name)
        cell = _load(self.path("cells", name))
        for key, theirs in (("config", entry["config"]),
                            ("mix", entry["traffic"]),
                            ("chips", entry["chips"])):
            if cell.get(key) != theirs:
                raise ManifestError(
                    f"cell {name!r}: file says {key}={cell.get(key)!r}, "
                    f"BENCHMARK.json says {theirs!r}")
        return cell

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                path = os.path.join(self.root, c["file"])
                doc = _load(path)
                if doc.get("reduced", []) != c["reduced"]:
                    raise ManifestError(
                        f"config {name!r}: 'reduced' differs between "
                        f"{c['file']} and BENCHMARK.json")
                return doc
        raise ManifestError(f"BENCHMARK.json has no config {name!r}")

    def mix(self, name: str) -> dict:
        return _load(self.path("traffic", name))

    def deployment(self, cell: dict, config: dict) -> dict:
        """The scheduler settings a run boots with: the configuration's,
        with the cell's own overrides (each with its reason in the cell
        file) on top."""
        return {**config["deployment"], **cell.get("deployment", {})}

    def metrics_for(self, cell_name: str, group: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` reported in a cell."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_metric(self, name: str) -> dict:
        return _load(self.path("layer_metrics", name))
