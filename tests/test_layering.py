"""Which subpackage may import which (PR 29): the declared graph.

One case per subpackage of ``heat_tpu`` (``core.linalg`` apart from
``core``): what its modules import of the other subpackages, read from the
source by ``ast`` (imports inside functions and ``import_module("heat_tpu...")``
calls included), is within ``ALLOWED`` plus ``DEBTS``. An entry is a
subpackage (``"core"``: any module of it) or one module (``"core.gates"``).

``observability`` is a leaf service: it imports ``core.gates`` (and
``hlo.py``, the inspector, ``core.dndarray`` / ``core.jit``) and nothing
else, so every layer may use its four instruments. ``DEBTS`` are the edges
that point up (ROADMAP Design 14). They may shrink, not grow: a debt that
is paid leaves the table.
"""

import ast
import functools
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "heat_tpu")

#: the instruments any layer may use (``hlo`` is not one: it imports ``core``)
_OBS = {
    "observability.events", "observability.instrument",
    "observability.telemetry", "observability.tracing",
}

ALLOWED = {
    "analysis": {"core", "kernels.quant", "observability.hlo", "redistribution.schedule", "sparse"},
    "classification": {"core", "spatial"},
    "cluster": {"core", "core.linalg", "graph", "redistribution.staging", "spatial"} | _OBS,
    "core": {"core.linalg", "kernels", "redistribution", "version"} | _OBS,
    "core.linalg": {"core", "kernels.cmatmul", "redistribution"} | _OBS,
    "datasets": set(),
    "graph": {"core", "core.linalg", "redistribution.staging", "sparse"},
    "kernels": {"core", "observability.telemetry", "observability.tracing"},
    "naive_bayes": {"core"},
    "nn": {"core"},
    "observability": {"core.gates", "core.dndarray", "core.jit"},
    "optim": {"core", "kernels.quant", "nn"},
    "preprocessing": {"core", "redistribution.staging", "sparse"} | _OBS,  # since PR 38: spans and observed programs
    "redistribution": {"core", "kernels.quant", "kernels.relayout"} | _OBS,
    "regression": {"core"},
    "resilience": {"core", "redistribution", "version"} | _OBS,
    # the top of the tree: endpoints over the estimators, `import heat_tpu`
    "serving": {"heat_tpu", "analysis.memcheck", "classification", "cluster", "core",
                "resilience.elastic", "version"} | _OBS,
    "sparse": {"core", "kernels.spmm"},
    "spatial": {"core"},
    "utils": {"core", "core.linalg", "observability.telemetry"},
}

#: edges that point up, with what holds each (ROADMAP Design 14)
DEBTS = {
    "core": {
        "analysis.numcheck",  # core/jit.py: ht.jit's trace-time precision lint
    },
    "core.linalg": {
        "analysis.memcheck",  # factorizations.py: the solver endpoint's HBM proof
        "serving.dispatcher",  # factorizations.py: solve_endpoint builds an Endpoint
    },
    "redistribution": {
        "analysis.boundaries",  # executor.py: the declared host-sync boundaries
        "resilience.elastic",  # executor.py: the world-epoch fence on every execute
    },
    "kernels": {
        "redistribution.planner",  # cmatmul.py: the ring's overlap gate and budget
    },
    "cluster": {
        "resilience.checkpoint",  # kmeans.py: checkpointed fits
        "resilience.elastic",  # kmeans.py: resume on a resized world
    },
}


def _node(parts):
    """Module path under ``heat_tpu`` -> (subpackage, module)."""
    if not parts:
        return "heat_tpu", "heat_tpu"
    if parts[:2] == ("core", "linalg"):
        return "core.linalg", ".".join(parts[:3])
    return parts[0], ".".join(parts[:2])


def _is_module(parts):
    path = os.path.join(PKG, *parts)
    return os.path.isdir(path) or os.path.isfile(path + ".py")


def _targets(tree, package):
    """Every ``heat_tpu`` module a parsed source imports, as path tuples."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif (
            isinstance(node, ast.Call)
            and getattr(node.func, "attr", getattr(node.func, "id", "")) == "import_module"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            names = [node.args[0].value]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = ("heat_tpu",) + package
                base = base[: len(base) - (node.level - 1)]
            else:
                base = ()
            base += tuple(node.module.split(".")) if node.module else ()
            if base[:1] != ("heat_tpu",):
                continue
            for a in node.names:
                # `from ..core import tiers` names a module, `from ..core.tiers import X` does not
                yield base[1:] + (a.name,) if _is_module(base[1:] + (a.name,)) else base[1:]
            continue
        else:
            continue
        for name in names:
            parts = tuple(name.split("."))
            if parts[0] == "heat_tpu":
                yield parts[1:]


@functools.cache
def _imports():
    """subpackage -> {imported module: {importing files}}. Parsed by the
    first case that asks, not by every worker that collects the file."""
    graph = {}
    for dirpath, dirnames, filenames in os.walk(PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in filenames:
            if not fname.endswith(".py") or dirpath == PKG:
                continue
            path = os.path.join(dirpath, fname)
            parts = tuple(os.path.relpath(path, PKG)[:-3].split(os.sep))
            package = parts[:-1]
            if parts[-1] == "__init__":
                parts = package
            src, _ = _node(parts)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for target in _targets(tree, package):
                pkg, module = _node(target)
                if pkg != src:
                    graph.setdefault(src, {}).setdefault(module, set()).add(
                        os.path.relpath(path, PKG)
                    )
    return graph


def _covered(module, entries):
    return module in entries or _node(tuple(module.split(".")))[0] in entries


def test_the_table_names_every_subpackage():
    found = {
        d for d in os.listdir(PKG) if os.path.isfile(os.path.join(PKG, d, "__init__.py"))
    } | {"core.linalg"}
    assert found == set(ALLOWED)
    assert set(DEBTS) <= set(ALLOWED)


@pytest.mark.parametrize("sub", sorted(ALLOWED))
def test_imports_are_within_the_declared_graph(sub):
    imported = _imports().get(sub, {})
    may = ALLOWED[sub] | DEBTS.get(sub, set())
    stray = {m: sorted(files) for m, files in imported.items() if not _covered(m, may)}
    assert not stray, f"{sub} imports outside its declared layer: {stray}"
    paid = sorted(d for d in DEBTS.get(sub, ()) if d not in imported)
    assert not paid, f"{sub} no longer imports {paid}: take the entry out of DEBTS"
