"""Find a cell, its configuration, its traffic mix and its per-layer
readers by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``perfbench/``:

    configs/<config>.json     the sizes as run, source, assumed, runner kind,
                              and the name of its model
    models/<model>.py         everything the benchmark knows about one
                              architecture: sizes, seeded weights, plain
                              reference, counts (``MODEL_NEEDS``)
    traffic/<traffic>.json    parameters for one of the general generators
    metrics/<metric>.py       ``read(ctx)`` -> number, or None if there is
                              nothing to read in this run

so a later PR adds a cell with files and entries and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# What a model file defines, by the kind of runner that asks for it. Every
# ``sizes(src)`` returns at least ``SIZES_EVERY_MODEL_HAS``.
MODEL_NEEDS = {
    "train": ("sizes", "make_params", "follow_training", "ADAM_B1",
              "train_flops_per_token"),
    "serve": ("sizes", "make_params", "served_token_gaps", "gaps_of",
              "reference_positions"),
}
SIZES_EVERY_MODEL_HAS = ("vocab_size", "n_positions")


class BenchmarkError(Exception):
    """The benchmark's own files are wrong or missing."""


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(
            f"{what} {name!r}: a name starts with a letter, a digit or "
            f"'_' and holds at most 64 letters, digits, '_', '.', '-'")
    return name


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing file {os.path.relpath(path, ROOT)}"
                             ) from None


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[Dict[str, Any]], name: str, what: str
             ) -> Dict[str, Any]:
    for e in entries:
        if e.get("name") == name:
            return e
    raise BenchmarkError(
        f"{what} {name!r} is not in BENCHMARK.json "
        f"(have {[e.get('name') for e in entries]})")


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name: str, root: str = ROOT,
                 bench: Optional[Dict[str, Any]] = None):
        check_name(name, "workload")
        self.root = root
        self.bench = bench if bench is not None else load_benchmark(root)
        self.entry = _by_name(self.bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        if self.chips not in (1, 4):
            raise BenchmarkError(f"{name}: chips must be 1 or 4")
        cfg_entry = _by_name(self.bench["configs"],
                             check_name(self.entry["config"], "config"),
                             "config")
        self.config_name = cfg_entry["name"]
        self.config = _read_json(os.path.join(root, cfg_entry["file"]))
        self.traffic_name = check_name(self.entry["traffic"], "traffic")
        self.traffic = _read_json(os.path.join(
            root, "perfbench", "traffic", self.traffic_name + ".json"))
        self.kind = self.config.get("runner")
        if self.kind not in MODEL_NEEDS:
            raise BenchmarkError(
                f"{cfg_entry['file']}: \"runner\" must be 'train' or "
                f"'serve', got {self.kind!r}")
        if "model" not in self.config:
            raise BenchmarkError(
                f"{cfg_entry['file']}: no \"model\" key; it names the "
                f"configuration's file under perfbench/models/")
        self.model = load_model(self.config["model"], root, self.kind)

    def sizes(self, rehearse: bool = False) -> Dict[str, int]:
        """The sizes as run, as the model reads them from the
        configuration's own keys (a rehearsal: from ``rehearsal.sizes``)."""
        src = self.config["rehearsal"]["sizes"] if rehearse else self.config
        sizes = self.model.sizes(src)
        lacks = [k for k in SIZES_EVERY_MODEL_HAS if k not in sizes]
        if lacks:
            raise BenchmarkError(
                f"{self.model.__file__}: sizes() returns no {lacks}, which "
                f"every model gives the runners")
        return sizes

    def _reports(self, metric: Dict[str, Any], e2e_names) -> bool:
        cells = metric.get("workloads")
        if cells is not None:
            return self.name in cells
        moved = metric.get("moves")
        return moved is None or moved in e2e_names

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if self._reports(m, ())]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self._reports(m, e2e)]


def _load_file(kind: str, name: str, root: str, lacks: str):
    """Import ``perfbench/<kind>s/<name>.py`` as a module of its own;
    ``lacks`` says what is missing where the file is not there."""
    check_name(name, kind)
    path = os.path.join(root, "perfbench", kind + "s", name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"{lacks} at {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric_name: str, root: str = ROOT
                ) -> Callable[[Any], Optional[float]]:
    """``perfbench/metrics/<metric>.py`` must define ``read(ctx)``."""
    module = _load_file("metric", metric_name, root,
                        f"per-layer metric {metric_name!r} has no reader")
    if not callable(getattr(module, "read", None)):
        raise BenchmarkError(f"{module.__file__} defines no read(ctx)")
    return module.read


def load_model(model_name: str, root: str = ROOT,
               runner_kind: Optional[str] = None):
    """``perfbench/models/<model>.py``, holding what ``runner_kind`` asks
    of a model (``MODEL_NEEDS``; None asks for nothing, as a tool or a
    test that calls the file's own functions may)."""
    module = _load_file("model", model_name, root,
                        f"model {model_name!r} has no file")
    lacks = [n for n in MODEL_NEEDS.get(runner_kind, ())
             if not hasattr(module, n)]
    if lacks:
        raise BenchmarkError(
            f"{os.path.relpath(module.__file__, root)} lacks "
            f"{', '.join(lacks)}, which a configuration of runner "
            f"{runner_kind!r} needs")
    return module
