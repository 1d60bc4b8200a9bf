"""A configuration's model family, and the cut it states of the program's
configuration.

A configuration file names its family with ``"family": "<name>"``, which
is ``bench/families/<name>.py`` (``dense_gqa`` where the key is absent;
the interface is in that module's docstring). It may cut the program's
registered configuration with ``"program": {"<ModelConfig field>":
value}``: each field's file key (the family's ``MODEL_FIELDS`` name for
it, else the field's own name) has to be in the file's ``reduced``, or the
harness refuses to run it. A nested configuration (``moe``, ``mla``) is
cut by a JSON object of its own fields. The ``model`` block states the
cut value, since ``check_model`` holds it to the cut program.

Adding a configuration takes new files only:

* ``bench/configs/<name>.json``: the published config's numbers in a
  ``model`` block, ``arch`` (the program's registered name),
  ``dtype``, ``deployment``, ``budget_ratio``, ``limits``, and optionally
  ``family`` and ``program``;
* ``bench/families/<family>.py``, where no existing family fits: the
  interface of ``dense_gqa.py``;
* ``bench/metrics/<kernel>_roofline.py`` for each kernel the family
  costs in ``kernel_cost``: ``return readers.kernel_roofline(r,
  "<program>")``;
* its entries in ``BENCHMARK.json`` (configuration, cells, metrics).

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path
from types import ModuleType

DEFAULT = "dense_gqa"
_LOADED = {}


def load(name: str, root: Path) -> ModuleType:
    """The family module ``root/bench/families/<name>.py``, loaded once."""
    path = (Path(root) / "bench" / "families" / f"{name}.py").resolve()
    if not path.is_file():
        raise SystemExit(f"no model family {name!r}: {path} is missing")
    if path not in _LOADED:
        mod_name = f"family_{name}_{len(_LOADED)}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def of(cfg: dict, root: Path) -> ModuleType:
    """The family a configuration file names, with its cut checked."""
    fam = load(cfg.get("family", DEFAULT), root)
    cut(cfg, fam)
    return fam


def cut(cfg: dict, fam: ModuleType) -> dict:
    """The file's ``program`` overrides, each one's file key listed in the
    file's ``reduced``; a key missing there stops the run."""
    overrides = cfg.get("program", {})
    key_of = {attr: key for key, attr in fam.MODEL_FIELDS.items()
              if isinstance(attr, str)}
    for field in overrides:
        key = key_of.get(field, field)
        if key not in cfg.get("reduced", []):
            raise SystemExit(f"{cfg['arch']}: the file cuts the program's "
                             f"{field!r}, but its key {key!r} is not in the "
                             f"file's reduced")
    return overrides


def replace(obj, overrides: dict):
    """``obj`` (a dataclass) with ``overrides`` applied, nested
    dataclasses field by field."""
    changes = {}
    for field, value in overrides.items():
        cur = getattr(obj, field)
        if dataclasses.is_dataclass(cur) and isinstance(value, dict):
            value = replace(cur, value)
        changes[field] = value
    return dataclasses.replace(obj, **changes)


def program_value(mc, attr):
    """What the program's configuration ``mc`` says for a ``MODEL_FIELDS``
    entry: an attribute's name, or a function of ``mc``."""
    return attr(mc) if callable(attr) else getattr(mc, attr)
