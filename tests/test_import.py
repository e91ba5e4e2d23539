"""Importing ``repro`` initializes no JAX backend.

A chip belongs to the first process that initializes a backend on it, so a
library import that asks JAX for a device (``jax.default_backend()``,
``jax.devices()``, an array built at module level) claims the chip before
the program has chosen to.  The modules are imported afresh with JAX's
backend lookup refusing every call; the ``repro.launch`` command-line entry
points are left out (``dryrun`` sets ``XLA_FLAGS`` when imported).
"""

import importlib
import pkgutil
import sys

from jax._src import xla_bridge

import repro


def test_importing_repro_initializes_no_backend(monkeypatch):
    requested = []

    def refuse(*args, **kwargs):
        requested.append(args)
        raise AssertionError("a JAX backend was requested at import")

    monkeypatch.setattr(xla_bridge, "get_backend", refuse)
    monkeypatch.setattr(xla_bridge, "backends", refuse)
    names = ["repro"] + [
        m.name for m in pkgutil.walk_packages(repro.__path__, "repro.")
        if not m.name.startswith("repro.launch")
    ]
    saved = {k: v for k, v in sys.modules.items()
             if k == "repro" or k.startswith("repro.")}
    try:
        for k in saved:
            del sys.modules[k]
        for name in names:
            importlib.import_module(name)
    finally:
        for k in [k for k in sys.modules if k == "repro" or k.startswith("repro.")]:
            del sys.modules[k]
        sys.modules.update(saved)
    assert len(names) > 40 and requested == []
