"""A model family's side of the benchmark, found by name.

A configuration file names its `family`; `benchmarks/families/<family>/` is
a package of four modules, and the generic code (`serve.py`, `check.py`,
`check_seeds.py`, `readers.py`) asks them for these names and no others:

- `weights`: `of_config(seed, config, dtype)` draws the checkpoint on the
  device, `program_tree(w)` lays it out as the program's family loads it;
- `reference`: `forward(w, ids, config, control=None)`, the plain float32
  `highest` forward of one sequence, which imports nothing of the program,
  and `CONTROLS`, the names of its lower-precision controls;
- `compare`: the program's side. `check_sizes(config, cfg)`,
  `program(family, cfg, params, ids, shape)`, which calls the program's
  forward as the engine's programs call it and reads out the cache it
  leaves, in the reference's own layout, and `readings(got, want)`, the
  numbers the family holds itself to, by the names `check.limits` of the
  configuration file gives their limits under;
- `roofline`: `cost(config, trace, slot_steps, mean_context)`, the bytes
  and operations of the decode steps of a traced span from the
  configuration's shapes, and which of the counts `trace.reduce` handed on
  is the family's decode steps.

A new family is a new directory: nothing here, and no file beside it, is
edited for one.
"""

from __future__ import annotations

import importlib
import os
import types

HERE = os.path.dirname(os.path.abspath(__file__))
PARTS = ("weights", "reference", "compare", "roofline")


def names() -> list:
    """The families there are: the packages of this directory."""
    return sorted(d for d in os.listdir(HERE) if os.path.isfile(
        os.path.join(HERE, d, "__init__.py")))


def load(name: str, parts=PARTS) -> types.SimpleNamespace:
    """The family's modules, `parts` of them (a reader of the trace needs
    `roofline` alone, and not jax with the others)."""
    if name not in names():
        raise KeyError(f"no family is called {name!r}: "
                       f"benchmarks/families has {names()}")
    return types.SimpleNamespace(name=name, **{
        part: importlib.import_module(f"benchmarks.families.{name}.{part}")
        for part in parts})


def of_config(config: dict, parts=PARTS) -> types.SimpleNamespace:
    """The family a configuration file names."""
    if "family" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"`family`: benchmarks/families has {names()}")
    return load(config["family"], parts)
