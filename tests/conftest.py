"""Force an 8-device virtual CPU platform before JAX initializes.

This is the JAX analog of the reference's in-process-server trick
(SURVEY.md §4): the reference could exercise its full gRPC ps/worker
path on one machine by pointing ps_hosts/worker_hosts at localhost;
we exercise the full SPMD psum path on one machine with
--xla_force_host_platform_device_count=8. Both variables are set here
before jax is imported (JAX_PLATFORMS is read at import, XLA_FLAGS when
the backend first initializes), which is all it takes.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

from tensorflow_distributed_tpu.utils.compilecache import (  # noqa: E402
    enable_persistent_cache)

# CPU test compiles of 8-device SPMD programs are the suite's wall-clock;
# cache them across runs.
enable_persistent_cache()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices8):
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    return make_mesh(MeshConfig(data=8), devices8)


@pytest.fixture(scope="session")
def mesh1(devices8):
    from tensorflow_distributed_tpu.parallel.mesh import single_device_mesh
    return single_device_mesh(devices8[0])


@pytest.fixture(scope="session")
def tiny_data():
    from tensorflow_distributed_tpu.data.mnist import synthetic_mnist
    return synthetic_mnist(n_train=2048, n_test=512, validation_size=256, seed=0)


# Committed real-idx fixture (shared by test_data / test_loop_cli).
FIXTURE_DIR = __file__.rsplit("/", 1)[0] + "/fixtures/mnist"


def _less_later_configs(bench, later, and_what_followed=False):
    """``bench`` less the configurations ``later`` (names), their cells,
    every metric that lists their cells alone, and their cells' names in
    the ``workloads`` lists that remain. ``and_what_followed``: less
    every metric that stands after the first of those too (entries are
    appended, so it came after the later configuration: a later PR's
    reader of an older cell)."""
    cells = {w["name"] for w in bench["workloads"] if w["config"] in later}
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] not in later]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in cells]
    for key in ("end_to_end", "per_layer"):
        theirs = [bool(m.get("workloads")) and set(m["workloads"]) <= cells
                  for m in bench[key]]
        if and_what_followed and any(theirs):
            bench[key] = bench[key][:theirs.index(True)]
        else:
            bench[key] = [m for m, t in zip(bench[key], theirs) if not t]
        for m in bench[key]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"]
                                  if w not in cells]
    return bench


def _configs_after_cell(bench, cell):
    """The configurations appended after ``cell``'s own."""
    own = next(w["config"] for w in bench["workloads"]
               if w["name"] == cell)
    names = [c["name"] for c in bench["configs"]]
    return set(names[names.index(own) + 1:])


def _configs_after_entries(bench, entry_names):
    """The configurations that came after the LAST of the per-layer
    entries ``entry_names``: those with a metric of their own (one that
    lists a cell of theirs FIRST: a list is only appended to, so its
    first cell is the one the metric came with, whoever reads it since;
    PR 43's cell reads all six of the granite cell's), the first of
    which stands after that entry. A configuration whose own metrics
    stand before it was there when the entry came, whatever the entry
    lists (PR 34's reader lists the GLM cell alone and came after
    A.X-K1)."""
    names = [m["name"] for m in bench["per_layer"]]
    at = [names.index(n) for n in entry_names if n in names]
    if not at:
        return set()
    later = set()
    for cfg in bench["configs"]:
        cells = {w["name"] for w in bench["workloads"]
                 if w["config"] == cfg["name"]}
        first = next((i for i, m in enumerate(bench["per_layer"])
                      if m.get("workloads")
                      and m["workloads"][0] in cells), None)
        if first is not None and first > max(at):
            later.add(cfg["name"])
    return later


@pytest.fixture(autouse=True)
def _cell_tests_see_the_benchmark_their_cell_left(request, tmp_path_factory,
                                                  monkeypatch):
    """A module under ``tests/benchmark/`` holds the benchmark to what it
    was when its PR came: a ``test_<x>_cell.py`` takes its cell (the
    module's ``CELL``) out of a copy and adds it again, and expects its
    name at the END of the lists of the readers it shares and ALONE in
    those of the readers it brought; a metric's module (one with an
    ``ENTRY``, or ``ENTRIES`` by name) holds its entries to stand LAST in
    ``per_layer`` with the ``workloads`` they came with. A later
    configuration's cell breaks each of these (PR 33's broke the GLM
    module, PR 35's the A.X-K1 module, PR 41's the SALA module, whose
    ``serve.state_live_share`` the new cell reads too, and PR 39's, whose
    six entries list four cells exactly). So every such module sees the
    benchmark AS ITS OWN PR LEFT IT, whatever it loads it through (its
    ``load_benchmark``, a ``Cell`` built without a ``bench``, the
    ``benchmark_copy``'s ``BENCHMARK.json`` and the ``ROOT`` beside it):

    - less every configuration that came LATER, with its cells, its own
      metrics and its cells' names in the lists that remain. Later than a
      cell's module: appended after the cell's own configuration. Later
      than a metric's module: its own metrics (those that list its cells
      alone) stand after the module's last entry
      (:func:`_configs_after_entries`);
    - a metric's module: cut after its last entry too (PR 38's reader
      lists the GLM cell AFTER PR 34's, whose module holds its own entry
      to be the last that cell names);
    - a cell's module with ``NEW_READERS``: less the readers LATER PRs
      gave its cell, since it may hold its cell's ``per_layer`` names to
      an exact set (PR 35's does) and its own readers to stand last:
      every entry of the benchmark as it stands (one a test itself
      appends to a copy stays) that stands after the last of its
      ``NEW_READERS`` and is in none of its reader tuples (PR 39's
      six; PR 44's ``serve.moe_combine_ms_per_ktoken``, which the newest
      cell's module meets in its ``benchmark_copy`` too), and the readers
      later PRs gave OLDER cells (PR 48's two list the steady cell alone
      and stand after the newest cell's four).

    The newest cell's module sees the benchmark as it stands, less such
    readers. A PR that
    may edit ``tests/benchmark/`` should move this into that directory's
    conftest (PERF.md section 7)."""
    import json
    import sys

    mod = request.module
    module = mod.__name__.rsplit(".", 1)[-1]
    cell = getattr(mod, "CELL", None)
    is_cell_module = (module.startswith("test_") and module.endswith("_cell")
                      and cell is not None)
    entries = [e["name"] for e in (
        [getattr(mod, "ENTRY", None)]
        + list((getattr(mod, "ENTRIES", None) or {}).values()))
        if isinstance(e, dict) and "name" in e]
    if not (is_cell_module or entries):
        yield
        return
    own = getattr(mod, "NEW_READERS", None)
    known = set(own or ()).union(getattr(mod, "SHARED_READERS", ()),
                                 getattr(mod, "GENERIC_READERS", ()))
    # what came later, by the benchmark as it stands (a copy a test has
    # taken a cell out of, or added one to, is stripped of the same names)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        whole = json.load(f)
    later = (_configs_after_cell(whole, cell) if is_cell_module
             else _configs_after_entries(whole, entries))
    standing = {m["name"] for m in whole["per_layer"]}

    def less_later_readers(bench):
        if own:
            names = [m["name"] for m in bench["per_layer"]]
            last = max((names.index(n) for n in own if n in names),
                       default=len(names))
            bench["per_layer"] = [
                m for i, m in enumerate(bench["per_layer"])
                if i <= last or m["name"] in known
                or (cell not in (m.get("workloads") or ())
                    and m["name"] not in standing)]
        return bench

    def as_left(bench):
        if is_cell_module:
            return less_later_readers(_less_later_configs(bench, later))
        bench = _less_later_configs(bench, later)
        names = [m["name"] for m in bench["per_layer"]]
        at = [names.index(n) for n in entries if n in names]
        if at:
            bench["per_layer"] = bench["per_layer"][:max(at) + 1]
        return bench

    load = getattr(mod, "load_benchmark", None)
    if load is not None:
        monkeypatch.setattr(mod, "load_benchmark",
                            lambda *args, **kw: as_left(load(*args, **kw)))
    real_cell = getattr(mod, "Cell", None)
    if is_cell_module and own and real_cell is not None:
        def cell_as_its_pr_left_it(name, root=None, bench=None):
            from harness.loader import load_benchmark
            kw = {} if root is None else {"root": root}
            if bench is None:
                bench = as_left(load_benchmark(**kw))
            return real_cell(name, bench=bench, **kw)

        monkeypatch.setattr(mod, "Cell", cell_as_its_pr_left_it)
    if "benchmark_copy" not in request.fixturenames:
        yield
        return
    if not is_cell_module:
        # the copy a metric's module takes its metric out of and adds it
        # to again
        path = os.path.join(request.getfixturevalue("benchmark_copy"),
                            "BENCHMARK.json")
        with open(path) as f:
            bench = as_left(json.load(f))
        with open(path, "w") as f:
            json.dump(bench, f)
        yield
        return
    bench = less_later_readers(_less_later_configs(
        json.loads(json.dumps(whole)), later, and_what_followed=True))
    if bench == whole:
        yield
        return
    left = str(tmp_path_factory.mktemp("as_the_cell_left_it"))
    with open(os.path.join(left, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    os.symlink(os.path.join(root, "perfbench"),
               os.path.join(left, "perfbench"))
    conftest = next(m for m in list(sys.modules.values())
                    if getattr(m, "__file__", None) == os.path.join(
                        root, "tests", "benchmark", "conftest.py"))
    monkeypatch.setattr(conftest, "ROOT", left)
    monkeypatch.setattr(mod, "ROOT", left)
    yield
