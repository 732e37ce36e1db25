"""The device layers (``ops/``, ``models/``, ``parallel/``) know nothing
of the host plane: from the package they import only ``exceptions``,
``operators``, ``operands``, ``obs.spans`` and each other. A trainer
that reaches into ``utils.tuning``, ``comm`` or the rest of ``obs`` has
grown a fork that only the host stack can switch on (ISSUE 28)."""

import ast
import os

import pytest

PKG = "ytk_mp4j_tpu"
ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)
DEVICE_DIRS = ("ops", "models", "parallel")
ALLOWED = frozenset(f"{PKG}.{m}" for m in
                    ("exceptions", "operators", "operands", "obs.spans"))

FILES = sorted(
    f"{d}/{name}" for d in DEVICE_DIRS
    for name in os.listdir(os.path.join(ROOT, d)) if name.endswith(".py"))


def _allowed(module: str) -> bool:
    return module in ALLOWED or any(
        module == f"{PKG}.{d}" or module.startswith(f"{PKG}.{d}.")
        for d in DEVICE_DIRS)


def package_imports(source: str, rel: str) -> list[tuple[int, str]]:
    """(line, module) of every import of a ``ytk_mp4j_tpu`` module in
    ``source``, function bodies included. ``from M import n`` counts as
    ``M`` where ``M`` is itself allowed and as ``M.n`` otherwise, so
    ``from ytk_mp4j_tpu.obs import spans`` reads as ``obs.spans``."""
    here = [PKG] + rel.split("/")[:-1]
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            found += [(node.lineno, a.name) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:
                base = here[:len(here) - node.level + 1]
                module = ".".join(base + ([module] if module else []))
            if _allowed(module):
                found.append((node.lineno, module))
            else:
                found += [(node.lineno, f"{module}.{a.name}")
                          for a in node.names]
    return sorted((line, m) for line, m in found
                  if m == PKG or m.startswith(PKG + "."))


def test_the_walk_sees_what_it_should():
    src = ("from ytk_mp4j_tpu.obs import spans\n"
           "from . import mesh\n"
           "def f():\n"
           "    from ytk_mp4j_tpu.utils import tuning\n"
           "    import ytk_mp4j_tpu.comm.master\n"
           "    import numpy\n")
    got = [m for _, m in package_imports(src, "parallel/x.py")]
    assert got == [f"{PKG}.obs.spans", f"{PKG}.parallel",
                   f"{PKG}.utils.tuning", f"{PKG}.comm.master"]
    assert [_allowed(m) for m in got] == [True, True, False, False]
    assert [_host_plane(m) for m in got] == [False, False, False, True]
    assert _host_plane(f"{PKG}.obs.health")


@pytest.mark.parametrize("rel", FILES)
def test_device_layers_import_no_host_plane(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        imports = package_imports(fh.read(), rel)
    bad = [f"{rel}:{line} imports {m}" for line, m in imports
           if not _allowed(m)]
    assert not bad, "\n".join(bad)


# The device drivers and the chip checks sit one step up: they may use
# the rest of ``comm`` and ``utils``, but a chip run must not need the
# socket backend's master, its slave, its transports, or the planes
# built on them (ISSUE 41).
DRIVERS = ("comm/tpu_comm.py", "comm/distributed.py",
           "check/checktpu.py", "check/checkaot.py", "../chip_smoke.py")
HOST_PLANE = tuple(f"{PKG}.{m}" for m in (
    "resilience", "serve", "analysis", "transport", "comm.master",
    "comm.process_comm", "obs"))


def _host_plane(module: str) -> bool:
    return module != f"{PKG}.obs.spans" and any(
        module == h or module.startswith(h + ".") for h in HOST_PLANE)


@pytest.mark.parametrize("rel", DRIVERS)
def test_device_drivers_import_no_socket_backend(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        imports = package_imports(fh.read(), rel)
    assert imports, f"{rel}: the walk found no package import at all"
    bad = [f"{rel}:{line} imports {m}" for line, m in imports
           if _host_plane(m)]
    assert not bad, "\n".join(bad)
