"""Find the benchmark's parts by name, as files under ``chipbench/``.

* a traffic driver: ``traffic/<name>.py``;
* a data generator: ``generators/<name>.py``, numpy only, whose
  ``tenant_streams(seed, cfg)`` gives one stream per tenant, each with
  ``rows(lo, hi)`` → keys int32 (n,), ts float32 (n,), values (n,) or
  (n, k);
* a reference fold: ``folds/<monoid>.py``;
* a metric's reader: ``metrics/<name>.py``, whose ``read(rec)`` gives a
  number or None.

Standard library only: the traffic client and the reference import it
without JAX.
"""

from __future__ import annotations

import importlib.util
import pathlib


def load_module(path: pathlib.Path, name: str):
    """The Python file ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(root, kind: str, name: str):
    return load_module(
        pathlib.Path(root) / "chipbench" / kind / f"{name}.py",
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}")


def driver(root, name: str):
    return _find(root, "traffic", name)


def generator(root, name: str):
    return _find(root, "generators", name)


def fold(root, monoid: str):
    return _find(root, "folds", monoid)


def metric(root, name: str):
    return _find(root, "metrics", name)


def tenant_streams(root, seed: int, cfg: dict) -> list:
    """The streams of a configuration's ``generator`` section ``cfg``,
    from the generator that its ``name`` names (there is no default)."""
    return generator(root, cfg["name"]).tenant_streams(seed, cfg)
